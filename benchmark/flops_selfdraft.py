"""What a draft-and-verify step of a self-drafting latent-attention decoder
costs, from the configuration alone (``model_type: joyai_llm_flash`` keys:
``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``first_k_dense_replace``,
``intermediate_size``, ``moe_intermediate_size``, ``n_routed_experts`` (the
experts HELD), ``router_experts`` (the router's width),
``num_nextn_predict_layers``): parameters by part, the bytes a step MUST
move, and the operations and bytes of the window's latent paged-attention
kernel. Written against the configuration, not against the implementation:
nothing here knows how the program lays its step out.

One step, every slot: the main stack's ``num_hidden_layers`` layers over a
WINDOW of two rows (the last sure token and the draft), the head, then the
prediction module (its projection, ONE more decoder layer of the expert
kind over its own latent rows, its norm) and the head again for the next
draft. The second pass over the head is a must, not a choice of the
program's: the module reads the embedding of the token the first pass made."""

from __future__ import annotations

from benchmark import flops_latent

LANES = flops_latent.LANES
attention_params = flops_latent.attention_params
attention_norm_params = flops_latent.attention_norm_params
dense_ffn_params = flops_latent.dense_ffn_params
expert_params = flops_latent.expert_params
shared_expert_params = flops_latent.shared_expert_params
latent_row_width = flops_latent.latent_row_width
WINDOW_ROWS = 2


def modules(config: dict) -> int:
    return int(config.get("num_nextn_predict_layers", 0))


def expert_layers(config: dict) -> int:
    """Expert layers a step runs: the main stack's and the module's."""
    return (config["num_hidden_layers"] - config["first_k_dense_replace"]
            + modules(config))


def cached_layers(config: dict) -> int:
    """Layers of the latent pool: every attention, the module's too."""
    return config["num_hidden_layers"] + modules(config)


def router_params(config: dict) -> int:
    """The router's kernel and selection bias at its WHOLE width (a chip
    that holds half the experts routes over all of them)."""
    e = config.get("router_experts") or config["n_routed_experts"]
    return config["hidden_size"] * e + e


def module_own_params(config: dict) -> int:
    """What a module has beside its decoder layer: the [embedding | stream]
    -> hidden projection and its three norms."""
    h = config["hidden_size"]
    return 2 * h * h + 3 * h


def expert_layer_params(config: dict) -> int:
    """One expert layer as held: attention, router, the held routed experts,
    the shared expert, two norms."""
    return (attention_params(config) + attention_norm_params(config)
            + router_params(config)
            + config["n_routed_experts"] * expert_params(config)
            + shared_expert_params(config) + 2 * config["hidden_size"])


def dense_layer_params(config: dict) -> int:
    return (attention_params(config) + attention_norm_params(config)
            + dense_ffn_params(config) + 2 * config["hidden_size"])


def total_params(config: dict) -> int:
    h, v = config["hidden_size"], config["vocab_size"]
    head = 0 if config.get("tie_word_embeddings") else h * v
    return (v * h + head + h
            + config["first_k_dense_replace"] * dense_layer_params(config)
            + expert_layers(config) * expert_layer_params(config)
            + modules(config) * module_own_params(config))


def once_a_step_weight_bytes(config: dict, weight_bytes: int = 2) -> int:
    """Weights a step reads whatever its routing: every attention sub-layer
    (the module's too), the dense feed-forward, the routers, the shared
    experts, the norms, the module's projection, and the head ONCE a pass
    (main, then the module's: two). The embedding is a lookup of rows."""
    h = config["hidden_size"]
    layers = cached_layers(config)
    return weight_bytes * (
        layers * (attention_params(config) + attention_norm_params(config)
                  + 2 * h)
        + config["first_k_dense_replace"] * dense_ffn_params(config)
        + expert_layers(config) * (router_params(config)
                                   + shared_expert_params(config))
        + modules(config) * module_own_params(config)
        + h + (1 + modules(config)) * h * config["vocab_size"])


def expert_bytes(config: dict, experts_hit: float,
                 weight_bytes: int = 2) -> float:
    """Bytes the grouped matmuls must stream for ``experts_hit`` (layer,
    held expert) pairs: each HIT expert's gate, up and down once, however
    many of the window's rows chose it."""
    return experts_hit * expert_params(config) * weight_bytes


def latent_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    """Cache bytes a token costs over all cached layers, as stored."""
    return cached_layers(config) * latent_row_width(config) * dtype_bytes


def step_bytes(config: dict, live_tokens: float, experts_hit: float
               ) -> float:
    """Bytes one draft-and-verify step must move through HBM: the weights
    every step reads, the experts HIT in it, and every live token's latent
    row in every cached layer ONCE a window (both rows of a slot walk the
    same pages: once, not once a row). Activations, embedding rows and the
    written rows are left out (under 1 %): a share this feeds reads a
    little low, never high."""
    return (once_a_step_weight_bytes(config)
            + expert_bytes(config, experts_hit)
            + latent_bytes_per_token(config) * live_tokens)


def kernel_bytes(config: dict, live_pages: float, page_size: int,
                 dtype_bytes: int = 2) -> float:
    """Bytes ONE call of the window's latent kernel (one layer) must read:
    each live page once a slot, as the pool stores it."""
    return live_pages * page_size * latent_row_width(config) * dtype_bytes


def kernel_flops(config: dict, live_tokens: float,
                 rows: int = WINDOW_ROWS) -> float:
    """Operations of ONE call (one layer) in the absorbed form, ``rows``
    queries a slot: every head's query against every live row for the
    scores, and the probabilities against the rows' first kv_lora_rank
    values."""
    return flops_latent.kernel_flops(config, live_tokens, queries=rows)
