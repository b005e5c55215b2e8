"""What a LOOPED decoder's work costs, from shapes alone (``model_type:
ouro``: one stack of sandwich-normed layers walked ``total_ut_steps`` times
over one set of weights, K and V kept per (pass, layer)): parameters, the
bytes a token's K and V cost the pool, and the bytes and operations of a
decode step.

``config`` everywhere is a configuration file of ``benchmark/configs/`` as a
dict (the model's own ``config.json`` keys at the top level)."""

from __future__ import annotations


def _dims(config: dict) -> tuple[int, int, int, int, int, int, int, int]:
    h = config["hidden_size"]
    nq = config["num_attention_heads"]
    return (config["total_ut_steps"], config["num_hidden_layers"], h,
            config["intermediate_size"], nq, config["num_key_value_heads"],
            config.get("head_dim", h // nq), config["vocab_size"])


def layer_matmul_params(config: dict) -> int:
    """One layer's q / k / v / o projections and gated feed-forward."""
    _, _, H, F, Nq, Nkv, D, _ = _dims(config)
    return H * Nq * D + 2 * H * Nkv * D + Nq * D * H + 3 * H * F


def layer_params(config: dict) -> int:
    """... and its FOUR norms' scales."""
    return layer_matmul_params(config) + 4 * config["hidden_size"]


def close_params(config: dict) -> int:
    """What closes a pass: the final norm's scale, the exit gate's kernel
    and bias."""
    return 2 * config["hidden_size"] + 1


def total_params(config: dict) -> int:
    _, L, H, _, _, _, _, V = _dims(config)
    tables = 1 if config.get("tie_word_embeddings") else 2
    return L * layer_params(config) + tables * V * H + close_params(config)


def planes(config: dict) -> int:
    """Planes of a K or V pool: one a (pass, layer)."""
    T, L, *_ = _dims(config)
    return T * L


def kv_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    """K and V rows of one token over every plane."""
    _, _, _, _, _, Nkv, D, _ = _dims(config)
    return 2 * planes(config) * Nkv * D * dtype_bytes


def weight_bytes_a_step(config: dict, weight_bytes: int = 2) -> int:
    """Weights a decode step must read: every layer's (norms and all) and
    what closes a pass ONCE A PASS (the layers' 4.93 GB do not stay on the
    chip between passes), the head once. The embedding is a lookup of a row
    a slot (``decode_step_bytes`` counts the rows, not the table)."""
    T, L, H, _, _, _, _, V = _dims(config)
    return weight_bytes * (T * (L * layer_params(config)
                                + close_params(config)) + H * V)


def decode_step_bytes(config: dict, live_kv_tokens: float, slots: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step must move through HBM: ``weight_bytes_a_step``,
    a gathered embedding row a slot, every live K and V row of every plane
    read once, and the step's own rows written (one a slot and plane).
    Activations are left out, so a roofline share this feeds reads a little
    low, never high."""
    return (weight_bytes_a_step(config)
            + slots * config["hidden_size"] * dtype_bytes
            + kv_bytes_per_token(config, dtype_bytes)
            * (live_kv_tokens + slots))


def decode_step_flops(config: dict, rows: float, live_kv_tokens: float
                      ) -> float:
    """Operations of a decode step over ``rows`` rows (the slots' and a
    riding piece's): 2 a matmul parameter a row a pass, the head once a
    row, and scores and values over the live rows of every plane."""
    T, L, H, _, Nq, _, D, V = _dims(config)
    return (2.0 * rows * (T * L * layer_matmul_params(config) + H * V)
            + 4.0 * T * L * Nq * D * live_kv_tokens)
