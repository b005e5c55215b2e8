"""What the work of a decoder with attention AND a Mamba-2 mixer in every
layer costs, from shapes alone: parameters by branch, the bytes a decode
step must move and the operations and bytes of the prefill scan.

``config`` is a configuration file of ``benchmark/configs/`` as a dict with
the published ``falcon_h1`` keys (``num_hidden_layers``, ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``intermediate_size``, ``vocab_size``, ``mamba_n_heads``, ``mamba_d_head``,
``mamba_d_state``, ``mamba_n_groups``, ``mamba_d_conv``,
``mamba_chunk_size``). Every published layer keeps BOTH kinds of state: K
and V rows a token, and one fixed-size recurrent state a slot."""

from __future__ import annotations


def _ssm(config: dict) -> tuple[int, int, int, int, int]:
    return (config["mamba_n_heads"], config["mamba_d_head"],
            config["mamba_d_state"], config["mamba_n_groups"],
            config["mamba_d_conv"])


def layers(config: dict) -> int:
    return config["num_hidden_layers"]


def conv_channels(config: dict) -> int:
    nh, p, n, g, _ = _ssm(config)
    return nh * p + 2 * g * n


def attention_params(config: dict) -> int:
    """The attention branch: the q, k, v and o projections (no bias)."""
    H, D = config["hidden_size"], config["head_dim"]
    nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    return 2 * H * nq * D + 2 * H * nkv * D


def mamba_params(config: dict) -> int:
    """The state-space branch: W_in [H, 2 d_in + 2 G N + nh], the conv's
    kernel and bias, dt_bias / A_log / D, the gated norm's weight, W_out."""
    H = config["hidden_size"]
    nh, p, _, _, k = _ssm(config)
    d_in, c = nh * p, conv_channels(config)
    return H * (d_in + c + nh) + (k + 1) * c + 3 * nh + d_in + d_in * H


def mlp_params(config: dict) -> int:
    """The gated MLP: gate, up and down."""
    return 3 * config["hidden_size"] * config["intermediate_size"]


def layer_params(config: dict) -> int:
    """One published layer: both branches under one norm, the MLP under a
    second."""
    return (attention_params(config) + mamba_params(config)
            + mlp_params(config) + 2 * config["hidden_size"])


def total_params(config: dict) -> int:
    H, V = config["hidden_size"], config["vocab_size"]
    head = 0 if config.get("tie_word_embeddings") else H * V
    return V * H + head + H + layers(config) * layer_params(config)


def head_weight_bytes(config: dict, weight_bytes: int = 2) -> int:
    return weight_bytes * config["hidden_size"] * config["vocab_size"]


def once_a_step_weight_bytes(config: dict, weight_bytes: int = 2) -> int:
    """Weights a decode step reads: every layer's and the head. (The
    embedding is a lookup of a row a slot.)"""
    return (weight_bytes * layers(config) * layer_params(config)
            + head_weight_bytes(config, weight_bytes))


def state_bytes_per_slot(config: dict) -> int:
    """One slot's recurrent state in ONE layer: h [nh, P, N] in float32 and
    the conv's K-1 pre-activation columns in bfloat16."""
    nh, p, n, _, k = _ssm(config)
    return nh * p * n * 4 + (k - 1) * conv_channels(config) * 2


def state_step_bytes(config: dict, live_slots: float) -> float:
    """State bytes a decode step moves: every live slot's state in every
    layer read once and written once."""
    return 2.0 * layers(config) * live_slots * state_bytes_per_slot(config)


def kv_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    """K and V rows of one token over all the layers."""
    return (2 * layers(config) * config["num_key_value_heads"]
            * config["head_dim"] * dtype_bytes)


def decode_step_bytes(config: dict, live_kv_tokens: float,
                      live_slots: float) -> float:
    """Bytes one decode step must move through HBM: the weights once, the
    live slots' state read and written, the live keys and values.
    Activations, embedding rows, the float32 logits, the norms' vectors and
    the written K/V are left out, so a roofline share this feeds reads a
    little low, never high."""
    return (once_a_step_weight_bytes(config)
            + state_step_bytes(config, live_slots)
            + kv_bytes_per_token(config) * live_kv_tokens)


def scan_flops_per_token(config: dict) -> float:
    """Operations of the chunked scan for one token in ONE layer: in its
    chunk of Q the C.B^T scores (2 Q G N) and their product with x
    (2 Q nh P), its part of the chunk's state (2 nh P N) and the carried
    state's contribution (2 nh P N). The in/out projections are matmuls
    outside the scan."""
    nh, p, n, g, _ = _ssm(config)
    q = config["mamba_chunk_size"]
    return 2.0 * q * (g * n + nh * p) + 4.0 * nh * p * n


def scan_bytes_per_token(config: dict, dtype_bytes: int = 2) -> float:
    """Bytes the scan must move for one token in ONE layer: x, B, C and dt
    read, y written. The state is NOT counted: a window's state before it is
    read and its state after it written OUTSIDE the scan's scope (``ops/ssm.py
    slot_state`` / ``write_slot_state`` / ``arm_slot_state``, once a window
    for all the layers), and the chunk states between are what a kernel
    would keep in fast memory; counted, they put this cell's riding pieces
    (ONE chunk a window) at 143 % of the byte roof (my chip run, PR 49, call
    1). With them out the scan is bound by its operations."""
    nh, p, n, g, _ = _ssm(config)
    return dtype_bytes * (2 * nh * p + 2 * g * n) + 4 * nh
