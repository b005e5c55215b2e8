"""What a hybrid state-space / attention / sparse-expert decoder's work
costs, from shapes alone: parameters by layer kind, the bytes a decode step
must move and the operations and bytes of the prefill scan.

``config`` is a configuration file of ``benchmark/configs/`` as a dict with
the published ``nemotron_h`` keys (``hybrid_override_pattern``,
``mamba_num_heads``, ``mamba_head_dim``, ``ssm_state_size``, ``n_groups``,
``conv_kernel``, ``chunk_size``, ``moe_intermediate_size``,
``moe_shared_expert_intermediate_size``, ``num_experts_per_tok``);
``n_routed_experts`` counts the experts HELD here and ``router_experts``
(absent: the same) the router's outputs."""

from __future__ import annotations


def _ssm(config: dict) -> tuple[int, int, int, int, int]:
    return (config["mamba_num_heads"], config["mamba_head_dim"],
            config["ssm_state_size"], config["n_groups"],
            config["conv_kernel"])


def layers(config: dict, kind: str) -> int:
    return config["hybrid_override_pattern"].count(kind)


def conv_channels(config: dict) -> int:
    nh, p, n, g, _ = _ssm(config)
    return nh * p + 2 * g * n


def mamba_layer_params(config: dict) -> int:
    """One ``M`` layer: its norm, W_in [H, d_in + C + nh], the conv's
    kernel and bias, dt_bias / A_log / D, the gated norm's weight, W_out."""
    H = config["hidden_size"]
    nh, p, _, _, k = _ssm(config)
    d_in, c = nh * p, conv_channels(config)
    return (H + H * (d_in + c + nh) + (k + 1) * c + 3 * nh + d_in
            + d_in * H)


def attention_layer_params(config: dict) -> int:
    """One ``*`` layer: its norm and the q, k, v, o projections."""
    H, D = config["hidden_size"], config["head_dim"]
    nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    return H + 2 * H * nq * D + 2 * H * nkv * D


def expert_params(config: dict) -> int:
    """One routed expert: up and down (no gate)."""
    return 2 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_expert_params(config: dict) -> int:
    return (2 * config["hidden_size"]
            * config["moe_shared_expert_intermediate_size"]
            * config.get("n_shared_experts", 1))


def router_params(config: dict) -> int:
    """The router's kernel and its selection bias."""
    width = config.get("router_experts", config["n_routed_experts"])
    return config["hidden_size"] * width + width


def expert_layer_params(config: dict) -> int:
    """One ``E`` layer: norm, router, the experts held here, the shared
    expert."""
    return (config["hidden_size"] + router_params(config)
            + config["n_routed_experts"] * expert_params(config)
            + shared_expert_params(config))


def total_params(config: dict) -> int:
    H, V = config["hidden_size"], config["vocab_size"]
    head = 0 if config.get("tie_word_embeddings") else H * V
    return (V * H + head + H
            + layers(config, "M") * mamba_layer_params(config)
            + layers(config, "*") * attention_layer_params(config)
            + layers(config, "E") * expert_layer_params(config))


def once_a_step_weight_bytes(config: dict, weight_bytes: int = 2) -> int:
    """Weights a decode step reads whatever its routing: the mixers, the
    attention layers, the routers, the shared experts and the head. (The
    embedding is a lookup of a row a slot.)"""
    H, V = config["hidden_size"], config["vocab_size"]
    per_e = H + router_params(config) + shared_expert_params(config)
    return weight_bytes * (
        layers(config, "M") * mamba_layer_params(config)
        + layers(config, "*") * attention_layer_params(config)
        + layers(config, "E") * per_e + H * V)


def expert_bytes(config: dict, experts_hit: float,
                 weight_bytes: int = 2) -> float:
    """Bytes the grouped matmuls must stream for ``experts_hit`` (layer,
    held expert) pairs: each HIT expert's up and down once."""
    return experts_hit * expert_params(config) * weight_bytes


def state_bytes_per_slot(config: dict) -> int:
    """One slot's recurrent state in ONE ``M`` layer: h [nh, P, N] in
    float32 and the conv's K-1 pre-activation columns in bfloat16."""
    nh, p, n, _, k = _ssm(config)
    return nh * p * n * 4 + (k - 1) * conv_channels(config) * 2


def state_step_bytes(config: dict, live_slots: float) -> float:
    """State bytes a decode step moves: every live slot's state in every
    ``M`` layer read once and written once."""
    return 2.0 * layers(config, "M") * live_slots * state_bytes_per_slot(
        config)


def kv_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    return (2 * layers(config, "*") * config["num_key_value_heads"]
            * config["head_dim"] * dtype_bytes)


def decode_step_bytes(config: dict, live_kv_tokens: float,
                      experts_hit_per_step: float, live_slots: float
                      ) -> float:
    """Bytes one decode step must move through HBM: the weights every step
    reads once, the held experts HIT in it, the live slots' state read and
    written, the live keys and values. Activations, embedding rows, norms'
    vectors and the written K/V are left out (under 1 %), so a roofline
    share this feeds reads a little low, never high."""
    return (once_a_step_weight_bytes(config)
            + expert_bytes(config, experts_hit_per_step)
            + state_step_bytes(config, live_slots)
            + kv_bytes_per_token(config) * live_kv_tokens)


def scan_flops_per_token(config: dict) -> float:
    """Operations of the chunked scan for one token in ONE ``M`` layer: in
    its chunk of Q the C.B^T scores (2 Q G N) and their product with x
    (2 Q nh P), its part of the chunk's state (2 nh P N) and the carried
    state's contribution (2 nh P N). The in/out projections are matmuls
    outside the scan."""
    nh, p, n, g, _ = _ssm(config)
    q = config["chunk_size"]
    return 2.0 * q * (g * n + nh * p) + 4.0 * nh * p * n


def scan_bytes_per_token(config: dict, dtype_bytes: int = 2) -> float:
    """Bytes the scan must move for one token in ONE ``M`` layer: x, B, C
    and dt read, y written (the chunk states are 1/Q of a token's)."""
    nh, p, n, g, _ = _ssm(config)
    q = config["chunk_size"]
    return (dtype_bytes * (2 * nh * p + 2 * g * n) + 4 * nh
            + 2.0 * 4 * nh * p * n / q)
