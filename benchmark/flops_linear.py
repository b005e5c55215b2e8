"""What a decoder of Kimi Delta Attention (KDA) layers beside latent
attention and sparse experts costs, from shapes alone: parameters by layer
kind, the bytes a decode step must move, and the operations and bytes of
the chunked delta rule.

``config`` is a configuration file of ``benchmark/configs/`` as a dict with
the published ``kimi_linear`` keys (``linear_attn_config`` with its layer
lists, ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``first_k_dense_replace``, ``intermediate_size``,
``moe_intermediate_size``, ``num_shared_experts``); ``num_experts`` counts
the experts HELD here and ``router_experts`` (absent: the same) the
router's outputs. A decoder layer is a mixer (``K`` or ``*``) and a
feed-forward (``D`` or ``E``), each with its own norm."""

from __future__ import annotations

LANES = 128
CHUNK = 64          # the chunked form's chunk (ops/kda.py CHUNK)


def _kda(config: dict) -> tuple[int, int, int]:
    la = config["linear_attn_config"]
    return la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]


def layers(config: dict, kind: str) -> int:
    """Layers of a kind: ``K``, ``*`` (mixers), ``D``, ``E`` (feed-forwards)."""
    la = config["linear_attn_config"]
    dense = config.get("first_k_dense_replace", 0)
    return {"K": len(la["kda_layers"]), "*": len(la["full_attn_layers"]),
            "D": dense, "E": config["num_hidden_layers"] - dense}[kind]


def kda_layer_params(config: dict) -> int:
    """One ``K`` mixer: its norm, W_in [H, 3nd + 2d + n] (q | k | v | decay
    low-rank | gate low-rank | beta), the conv over q | k | v, the low-rank
    pairs' second halves, A_log, dt_bias, the head norm's weight, W_o."""
    H = config["hidden_size"]
    n, d, k = _kda(config)
    nd = n * d
    return (H + H * (3 * nd + 2 * d + n) + k * 3 * nd + 2 * d * nd
            + n + nd + d + nd * H)


def attention_layer_params(config: dict) -> int:
    """One ``*`` mixer: its norm, the direct q, kv_a, the latent's norm,
    kv_b, o."""
    H, N = config["hidden_size"], config["num_attention_heads"]
    r, dn, dr, dv = (config["kv_lora_rank"], config["qk_nope_head_dim"],
                     config["qk_rope_head_dim"], config["v_head_dim"])
    return (H + H * N * (dn + dr) + H * (r + dr) + r + r * N * (dn + dv)
            + N * dv * H)


def dense_layer_params(config: dict) -> int:
    H = config["hidden_size"]
    return H + 3 * H * config["intermediate_size"]


def expert_params(config: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_expert_params(config: dict) -> int:
    return expert_params(config) * config.get("num_shared_experts", 1)


def router_params(config: dict) -> int:
    """The router's kernel and its selection bias."""
    width = config.get("router_experts", config["num_experts"])
    return config["hidden_size"] * width + width


def expert_layer_params(config: dict) -> int:
    """One ``E`` feed-forward: norm, router, the experts held here, the
    shared expert."""
    return (config["hidden_size"] + router_params(config)
            + config["num_experts"] * expert_params(config)
            + shared_expert_params(config))


def total_params(config: dict) -> int:
    H, V = config["hidden_size"], config["vocab_size"]
    head = 0 if config.get("tie_word_embeddings") else H * V
    return (V * H + head + H
            + layers(config, "K") * kda_layer_params(config)
            + layers(config, "*") * attention_layer_params(config)
            + layers(config, "D") * dense_layer_params(config)
            + layers(config, "E") * expert_layer_params(config))


def once_a_step_weight_bytes(config: dict, weight_bytes: int = 2) -> int:
    """Weights a decode step reads whatever its routing: both kinds of
    mixer, the dense feed-forward, the routers, the shared experts, the
    final norm and the head. (The embedding is a lookup of a row a slot;
    the few float32 vectors are counted at ``weight_bytes``: low, never
    high.)"""
    H, V = config["hidden_size"], config["vocab_size"]
    per_e = H + router_params(config) + shared_expert_params(config)
    return weight_bytes * (
        layers(config, "K") * kda_layer_params(config)
        + layers(config, "*") * attention_layer_params(config)
        + layers(config, "D") * dense_layer_params(config)
        + layers(config, "E") * per_e + H + H * V)


def expert_bytes(config: dict, experts_hit: float,
                 weight_bytes: int = 2) -> float:
    """Bytes the grouped matmuls must stream for ``experts_hit`` (layer,
    held expert) pairs: each HIT expert's gate, up and down once."""
    return experts_hit * expert_params(config) * weight_bytes


def state_bytes_per_slot(config: dict) -> int:
    """One slot's recurrent state in ONE ``K`` layer: S [n, d, d] in
    float32 and the conv's K-1 pre-activation columns over q | k | v in
    bfloat16."""
    n, d, k = _kda(config)
    return n * d * d * 4 + (k - 1) * 3 * n * d * 2


def state_step_bytes(config: dict, live_slots: float) -> float:
    """State bytes a decode step moves: every live slot's state in every
    ``K`` layer read once and written once."""
    return 2.0 * layers(config, "K") * live_slots * state_bytes_per_slot(
        config)


def latent_row_width(config: dict, padded: bool = True) -> int:
    """Values of a token's latent row in one ``*`` layer (576), as a page
    stores it padded to whole 128-lane tiles (640)."""
    w = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return -(-w // LANES) * LANES if padded else w


def latent_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    """Cache bytes a token costs: ONE padded latent row a ``*`` layer; the
    ``K`` layers cost a token nothing."""
    return layers(config, "*") * latent_row_width(config) * dtype_bytes


def mla_kernel_bytes(config: dict, live_latent_tokens: float,
                     dtype_bytes: int = 2) -> float:
    """Bytes ONE call of the latent paged-attention kernel (one ``*``
    layer) must read: each live row once a slot, as the pool stores it."""
    return live_latent_tokens * latent_row_width(config) * dtype_bytes


def mla_kernel_flops(config: dict, live_latent_tokens: float) -> float:
    """Operations of ONE call (one ``*`` layer) in the absorbed form: every
    head's query against every live row for the scores (kv_lora_rank +
    qk_rope_head_dim values) and the probabilities against the rows' first
    kv_lora_rank values."""
    return 2.0 * config["num_attention_heads"] * live_latent_tokens * (
        latent_row_width(config, padded=False) + config["kv_lora_rank"])


def decode_step_bytes(config: dict, live_latent_tokens: float,
                      experts_hit_per_step: float, live_slots: float
                      ) -> float:
    """Bytes one decode step must move through HBM: the weights every step
    reads once, the held experts HIT in it, the live slots' recurrent state
    read and written, the live latent rows. Activations, embedding rows,
    norms' vectors and the written rows are left out (under 1 %), so a
    roofline share this feeds reads a little low, never high."""
    return (once_a_step_weight_bytes(config)
            + expert_bytes(config, experts_hit_per_step)
            + state_step_bytes(config, live_slots)
            + latent_bytes_per_token(config) * live_latent_tokens)


def chunk_flops_per_token(config: dict, chunk: int = CHUNK) -> float:
    """Operations the chunked delta rule must do for one token in ONE ``K``
    layer, a head: the causal half of the two pair products k.k and q.k
    with their decays (2 Q d), its row of the triangular solve against
    [beta V | beta K exp(G)] (Q 2d), the causal half of (q.k) U (Q d), and
    against the carried state W S_0, q S_0 and the state's update (3 x
    2 d d). The projections and the conv are outside."""
    n, d, _ = _kda(config)
    return n * (2.0 * chunk * d + 2.0 * chunk * d + chunk * d + 6.0 * d * d)


def chunk_bytes_per_token(config: dict, dtype_bytes: int = 2) -> float:
    """Bytes the chunked form must move for one token in ONE ``K`` layer:
    q, k, v and the decay's input read, o written. (The carried state is
    read and written once a window, not a token: left out, so the floor
    reads low.)"""
    n, d, _ = _kda(config)
    return 5.0 * n * d * dtype_bytes
