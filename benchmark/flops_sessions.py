"""What a decoder of delta-rule (Kimi Delta Attention) layers beside a gated
softmax layer over plain K/V pages and sparse experts costs, from shapes
alone: parameters by layer kind and the bytes a decode step must move.

``config`` is a configuration file of ``benchmark/configs/`` as a dict with
the published ``solar_open2`` keys (``gqa_layers`` 0-indexed,
``linear_attn_config``, ``num_attention_heads`` / ``num_key_value_heads`` /
``head_dim``, ``use_gqa_gate``, ``moe_intermediate_size``,
``n_shared_experts``); ``n_routed_experts`` counts the experts HELD here and
``router_experts`` (absent: the same) the router's outputs. A decoder layer
is a mixer (``*`` or ``K``) and an expert feed-forward, each with its own
norm."""

from __future__ import annotations


def _kda(config: dict) -> tuple[int, int, int]:
    la = config["linear_attn_config"]
    return la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]


def layers(config: dict, kind: str) -> int:
    """Layers of a kind: ``*``, ``K`` (mixers), ``E`` (feed-forwards)."""
    n, softmax = config["num_hidden_layers"], len(config["gqa_layers"])
    return {"*": softmax, "K": n - softmax, "E": n}[kind]


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
    """One ``*`` mixer: its norm, q, k, v, the gate (``use_gqa_gate``), o."""
    H, N, Nkv, d = (config["hidden_size"], config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"])
    gate = H * N * d if config.get("use_gqa_gate") else 0
    return H + 2 * H * N * d + 2 * H * Nkv * d + gate


def expert_params(config: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_expert_params(config: dict) -> int:
    return expert_params(config) * config.get("n_shared_experts", 1)


def router_params(config: dict) -> int:
    """The router's kernel and its selection bias."""
    width = config.get("router_experts", config["n_routed_experts"])
    return config["hidden_size"] * width + width


def expert_layer_params(config: dict) -> int:
    """One ``E`` feed-forward: norm, router, the experts held here, the
    shared expert."""
    return (config["hidden_size"] + router_params(config)
            + config["n_routed_experts"] * expert_params(config)
            + shared_expert_params(config))


def total_params(config: dict) -> int:
    H, V = config["hidden_size"], config["vocab_size"]
    head = 0 if config.get("tie_word_embeddings") else H * V
    return (V * H + head + H
            + layers(config, "K") * kda_layer_params(config)
            + layers(config, "*") * attention_layer_params(config)
            + layers(config, "E") * expert_layer_params(config))


def once_a_step_weight_bytes(config: dict, weight_bytes: int = 2) -> int:
    """Weights a decode step reads whatever its routing: both kinds of
    mixer, the routers, the shared experts, the final norm and the head.
    (The embedding is a lookup of a row a slot; the few float32 vectors are
    counted at ``weight_bytes``: low, never high.)"""
    H, V = config["hidden_size"], config["vocab_size"]
    per_e = H + router_params(config) + shared_expert_params(config)
    return weight_bytes * (
        layers(config, "K") * kda_layer_params(config)
        + layers(config, "*") * attention_layer_params(config)
        + layers(config, "E") * per_e + H + H * V)


def expert_bytes(config: dict, experts_hit: float,
                 weight_bytes: int = 2) -> float:
    """Bytes the grouped matmuls must stream for ``experts_hit`` (layer,
    held expert) pairs: each HIT expert's gate, up and down once."""
    return experts_hit * expert_params(config) * weight_bytes


def state_bytes_per_slot(config: dict) -> int:
    """One slot's recurrent state in ONE ``K`` layer: S [n, d, d] in
    float32 and the conv's K-1 pre-activation columns over q | k | v in
    bfloat16. A snapshot entry is this times the ``K`` layers."""
    n, d, k = _kda(config)
    return n * d * d * 4 + (k - 1) * 3 * n * d * 2


def state_step_bytes(config: dict, live_slots: float) -> float:
    """State bytes a decode step moves: every live slot's state in every
    ``K`` layer read once and written once."""
    return 2.0 * layers(config, "K") * live_slots * state_bytes_per_slot(
        config)


def kda_operand_bytes(config: dict, live_slots: float) -> float:
    """What the one-step kernel reads and writes beside the state, a ``K``
    layer a live slot: q, k, v, the decays and beta's broadcast in float32
    and the output, each [n, d]."""
    n, d, _ = _kda(config)
    return layers(config, "K") * live_slots * 6.0 * n * d * 4


def kv_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    """Cache bytes a token costs: K and V of every key/value head in each
    ``*`` layer; the ``K`` layers cost a token nothing."""
    return (layers(config, "*") * 2 * config["num_key_value_heads"]
            * config["head_dim"] * dtype_bytes)


def decode_step_bytes(config: dict, live_kv_tokens: float,
                      experts_hit_per_step: float, live_slots: float
                      ) -> float:
    """Bytes one decode step must move through HBM: the weights every step
    reads once, the held experts HIT in it, the live slots' recurrent state
    read and written, the live K/V rows. Activations, embedding rows,
    norms' vectors, the written rows and whatever a prompt's piece that
    rides the step adds are left out, so a roofline share this feeds reads
    low, never high."""
    return (once_a_step_weight_bytes(config)
            + expert_bytes(config, experts_hit_per_step)
            + state_step_bytes(config, live_slots)
            + kv_bytes_per_token(config) * live_kv_tokens)
