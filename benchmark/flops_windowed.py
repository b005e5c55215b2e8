"""What the work of a decoder with WINDOW layers beside full ones costs, from
shapes alone (``model_type: mellum``: ``layer_types``, ``sliding_window``,
every layer sparse experts): parameters, the bytes a token's K and V cost
each of the two pools, and the bytes of a decode step and of the window
layers' page kernel.

``config`` everywhere is a configuration file of ``benchmark/configs/`` as a
dict (the model's own ``config.json`` keys at the top level;
``moe_intermediate_size`` is ONE expert's width)."""

from __future__ import annotations


def _dims(config: dict) -> tuple[int, int, int, int, int, int, int, int, int]:
    h = config["hidden_size"]
    nq = config["num_attention_heads"]
    return (config["num_hidden_layers"], h, config["moe_intermediate_size"],
            nq, config["num_key_value_heads"],
            config.get("head_dim", h // nq), config["vocab_size"],
            config["num_experts"], config["num_experts_per_tok"])


def layers(config: dict, kind: str) -> int:
    """Layers of ``kind`` ("sliding_attention" | "full_attention")."""
    return sum(t == kind for t in config["layer_types"])


def expert_params(config: dict) -> int:
    """One expert's gate, up and down kernels."""
    _, H, F, *_ = _dims(config)
    return 3 * H * F


def attention_params(config: dict) -> int:
    """One layer's q / k / v / o projections (no bias)."""
    _, H, _, Nq, Nkv, D, *_ = _dims(config)
    return H * Nq * D + 2 * H * Nkv * D + Nq * D * H


def layer_params(config: dict) -> int:
    """One layer: attention, router, every expert, two norms and the q/k
    head norms' scales."""
    _, H, _, _, _, D, _, E, _ = _dims(config)
    return (attention_params(config) + H * E + E * expert_params(config)
            + 2 * H + 2 * D)


def total_params(config: dict) -> int:
    L, H, *_, V, _, _ = _dims(config)
    tables = 1 if config.get("tie_word_embeddings") else 2
    return L * layer_params(config) + tables * V * H + H


def kv_row_bytes(config: dict, dtype_bytes: int = 2) -> int:
    """K and V of one token in ONE layer: every kv head."""
    *_, Nkv, D, _, _, _ = _dims(config)
    return 2 * Nkv * D * dtype_bytes


def kv_bytes_per_token(config: dict, kind: str = "full_attention",
                       dtype_bytes: int = 2) -> int:
    """K and V rows of one token over the layers of ``kind``: what a token
    costs the full layers' pool, or a ring."""
    return layers(config, kind) * kv_row_bytes(config, dtype_bytes)


def shared_weight_bytes(config: dict, weight_bytes: int = 2) -> int:
    """Weights every decode step reads whatever its routing: the layers'
    attention projections, routers and norms, the final norm and the head.
    The embedding is a lookup of a row a slot (``decode_step_bytes`` counts
    the rows, not the table)."""
    L, H, _, _, _, D, V, E, _ = _dims(config)
    return weight_bytes * (L * (attention_params(config) + H * E + 2 * H
                                + 2 * D) + H + H * V)


def expert_bytes(config: dict, experts_hit: float,
                 weight_bytes: int = 2) -> float:
    """Bytes of the kernels of ``experts_hit`` (layer, expert) pairs: an
    expert no live token chose is not read."""
    return experts_hit * expert_params(config) * weight_bytes


def window_attention_bytes(config: dict, window_rows: float,
                           dtype_bytes: int = 2) -> float:
    """Bytes the window layers' page kernel must read in a decode step:
    ``window_rows`` (the sum over live slots of min(length, window), times
    the window layers: the engine's own counter) K and V rows of every kv
    head."""
    return window_rows * kv_row_bytes(config, dtype_bytes)


def decode_step_bytes(config: dict, full_rows: float, window_rows: float,
                      experts_hit: float, slots: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step must move through HBM: ``shared_weight_bytes``,
    the experts HIT in the step, a gathered embedding row a slot, the full
    layers' live rows (``full_rows``: rows x full layers) and the window
    layers' visible rows (``window_rows``: rows x window layers), and the
    step's own rows written (one a slot and layer). Activations are left
    out, so a roofline share this feeds reads a little low, never high."""
    L, H, *_ = _dims(config)
    return (shared_weight_bytes(config) + expert_bytes(config, experts_hit)
            + slots * H * dtype_bytes
            + kv_row_bytes(config, dtype_bytes)
            * (full_rows + window_rows + slots * L))
