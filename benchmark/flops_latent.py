"""What a latent-attention decoder with a multi-stream residual path and
sparse experts costs, from shapes alone: parameters by part, the bytes a
decode step must move, the latent bytes a token, and the operations and
bytes of the latent paged-attention kernel.

``config`` is a configuration file of ``benchmark/configs/`` as a dict with
the published ``xing4_0`` keys (``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``first_k_dense_replace``, ``intermediate_size``, ``moe_intermediate_size``,
``n_routed_experts``, ``n_shared_experts``, ``hc_mult``). A decoder layer is
an attention sub-layer and a feed-forward one, each with its own norm and
its own hyper-connection."""

from __future__ import annotations

LANES = 128


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def attention_params(config: dict) -> int:
    """One attention sub-layer's kernels: q_a, q_b, kv_a, kv_b, o."""
    h, n = config["hidden_size"], config["num_attention_heads"]
    rq, r = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    return (h * rq + rq * n * (dn + dr) + h * (r + dr)
            + r * n * (dn + dv) + n * dv * h)


def attention_norm_params(config: dict) -> int:
    """The q latent's and the kv latent's RMSNorm weights."""
    return config["q_lora_rank"] + config["kv_lora_rank"]


def hyper_connection_params(config: dict) -> int:
    """One sub-layer's maps: the norm over n*C values, phi [n*C, 2n + n*n],
    three scalars, two bias vectors [n] and a bias matrix [n, n]."""
    n = config["hc_mult"]
    nc = n * config["hidden_size"]
    return nc + nc * (2 * n + n * n) + 3 + 2 * n + n * n


def dense_ffn_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["intermediate_size"]


def expert_params(config: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_expert_params(config: dict) -> int:
    return expert_params(config) * config.get("n_shared_experts", 1)


def router_params(config: dict) -> int:
    """The router's kernel and its selection bias."""
    e = config["n_routed_experts"]
    return config["hidden_size"] * e + e


def sub_layer_overhead(config: dict) -> int:
    """What every sub-layer carries beside its mixer: its pre-norm and its
    hyper-connection."""
    return config["hidden_size"] + hyper_connection_params(config)


def dense_layer_params(config: dict) -> int:
    return (attention_params(config) + attention_norm_params(config)
            + dense_ffn_params(config) + 2 * sub_layer_overhead(config))


def expert_layer_params(config: dict) -> int:
    return (attention_params(config) + attention_norm_params(config)
            + router_params(config)
            + config["n_routed_experts"] * expert_params(config)
            + shared_expert_params(config) + 2 * sub_layer_overhead(config))


def total_params(config: dict) -> int:
    h, v = config["hidden_size"], config["vocab_size"]
    head = 0 if config.get("tie_word_embeddings") else h * v
    return (v * h + head + h
            + config["first_k_dense_replace"] * dense_layer_params(config)
            + expert_layers(config) * expert_layer_params(config))


def latent_row_width(config: dict, padded: bool = True) -> int:
    """Values of a token's latent row in one layer: kv_lora_rank +
    qk_rope_head_dim (576), as a page stores it padded to whole 128-lane
    tiles (640)."""
    w = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return -(-w // LANES) * LANES if padded else w


def latent_bytes_per_token(config: dict, dtype_bytes: int = 2,
                           padded: bool = True) -> int:
    """Cache bytes a token costs over all layers: ONE latent row a layer."""
    return (config["num_hidden_layers"] * latent_row_width(config, padded)
            * dtype_bytes)


def once_a_step_weight_bytes(config: dict, weight_bytes: int = 2) -> int:
    """Weights a decode step reads whatever its routing: every attention
    sub-layer, the dense feed-forward, the routers, the shared experts, the
    norms, the head (bf16) and the hyper-connections' maps (float32). The
    embedding is a lookup of a row a slot."""
    L, Le = config["num_hidden_layers"], expert_layers(config)
    h = config["hidden_size"]
    bf16 = (L * (attention_params(config) + attention_norm_params(config)
                 + 2 * h)
            + config["first_k_dense_replace"] * dense_ffn_params(config)
            + Le * (router_params(config) + shared_expert_params(config))
            + h + h * config["vocab_size"])
    return weight_bytes * bf16 + 4 * 2 * L * hyper_connection_params(config)


def expert_bytes(config: dict, experts_hit: float,
                 weight_bytes: int = 2) -> float:
    """Bytes the grouped matmuls must stream for ``experts_hit`` (layer,
    expert) pairs: each HIT expert's gate, up and down once."""
    return experts_hit * expert_params(config) * weight_bytes


def decode_step_bytes(config: dict, live_tokens: float,
                      experts_hit_per_step: float) -> float:
    """Bytes one decode step must move through HBM: the weights every step
    reads once, the experts HIT in it, and every live token's latent row in
    every layer (each slot reads its own chain of pages, shared document or
    not). Activations, embedding rows and the written rows are left out
    (under 1 %), so a roofline share this feeds reads a little low, never
    high."""
    return (once_a_step_weight_bytes(config)
            + expert_bytes(config, experts_hit_per_step)
            + latent_bytes_per_token(config) * live_tokens)


def kernel_bytes(config: dict, live_pages: float, page_size: int,
                 dtype_bytes: int = 2) -> float:
    """Bytes ONE call of the latent paged-attention kernel (one layer) must
    read: each live page once a slot, as the pool stores it (padded rows)."""
    return live_pages * page_size * latent_row_width(config) * dtype_bytes


def kernel_flops(config: dict, live_tokens: float, queries: int = 1
                 ) -> float:
    """Operations of ONE call (one layer) in the absorbed form: every head's
    query against every live row for the scores (kv_lora_rank +
    qk_rope_head_dim values) and the probabilities against the rows' first
    kv_lora_rank values."""
    n = config["num_attention_heads"]
    return 2.0 * queries * n * live_tokens * (
        latent_row_width(config, padded=False) + config["kv_lora_rank"])


def expanded_over_absorbed(config: dict) -> float:
    """Values multiplied a head a key: the expanded form's (nope + rope +
    v) over the absorbed form's (2 kv_lora_rank + rope)."""
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    return (dn + dr + dv) / (2 * config["kv_lora_rank"] + dr)
