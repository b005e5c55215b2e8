"""What the work of a decoder of gated short convolutions, grouped-query
attention and sparse experts costs, from shapes alone: parameters by layer
kind and the bytes a decode step must move.

``config`` is a configuration file of ``benchmark/configs/`` as a dict with
the published ``lfm2_moe`` keys (``layer_types``, ``num_dense_layers``,
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``intermediate_size``, ``moe_intermediate_size``, ``num_experts``,
``conv_L_cache``, ``vocab_size``). A ``conv`` layer keeps ``conv_L_cache -
1`` rows of ``hidden_size`` a slot and no pages; a ``full_attention`` layer
keeps K and V rows a token; every expert is held here."""

from __future__ import annotations


def head_dim(config: dict) -> int:
    return config.get("head_dim") or (config["hidden_size"]
                                      // config["num_attention_heads"])


def layers(config: dict, kind: str) -> int:
    """Decoder layers of ``kind``: ``conv`` | ``full_attention`` (mixers),
    ``dense`` | ``experts`` (feed-forwards)."""
    n, dense = len(config["layer_types"]), config["num_dense_layers"]
    if kind == "dense":
        return min(dense, n)
    if kind == "experts":
        return n - min(dense, n)
    return config["layer_types"].count(kind)


def conv_mixer_params(config: dict) -> int:
    """One ``conv`` mixer: W_in [H, 3 H], the taps [K, H], W_out [H, H]."""
    H = config["hidden_size"]
    return 3 * H * H + config["conv_L_cache"] * H + H * H


def attention_params(config: dict) -> int:
    """One attention mixer: the q, k, v and o projections (no bias) and the
    two [D] scales of the head norms."""
    H, D = config["hidden_size"], head_dim(config)
    nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    return 2 * H * nq * D + 2 * H * nkv * D + 2 * D


def dense_mlp_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["intermediate_size"]


def expert_params(config: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def router_params(config: dict) -> int:
    """The router's kernel and its expert bias."""
    return (config["hidden_size"] + 1) * config["num_experts"]


def total_params(config: dict) -> int:
    """Every parameter held here (two norms a decoder layer, the final
    norm, ONE table where the embeddings are tied)."""
    H, V = config["hidden_size"], config["vocab_size"]
    tables = 1 if config.get("tie_word_embeddings") else 2
    return (tables * V * H + H + 2 * H * len(config["layer_types"])
            + layers(config, "conv") * conv_mixer_params(config)
            + layers(config, "full_attention") * attention_params(config)
            + layers(config, "dense") * dense_mlp_params(config)
            + layers(config, "experts") * (
                router_params(config)
                + config["num_experts"] * expert_params(config)))


def once_a_step_weight_bytes(config: dict, weight_bytes: int = 2) -> int:
    """Weights a decode step reads whatever its routing: the mixers, the
    dense MLPs, the routers, the norms and the head (the embedding's
    transpose, read whole; the embedding itself is a lookup of a row a
    slot)."""
    H, V = config["hidden_size"], config["vocab_size"]
    return weight_bytes * (
        H * V + H + 2 * H * len(config["layer_types"])
        + layers(config, "conv") * conv_mixer_params(config)
        + layers(config, "full_attention") * attention_params(config)
        + layers(config, "dense") * dense_mlp_params(config)
        + layers(config, "experts") * router_params(config))


def expert_bytes(config: dict, experts_hit: float,
                 weight_bytes: int = 2) -> float:
    """Bytes the grouped matmuls must stream for ``experts_hit`` (layer,
    expert) pairs: each HIT expert's three kernels once."""
    return experts_hit * expert_params(config) * weight_bytes


def window_bytes_per_slot(config: dict, dtype_bytes: int = 2) -> int:
    """One slot's conv window in ONE ``conv`` layer: K-1 rows of H."""
    return (config["conv_L_cache"] - 1) * config["hidden_size"] * dtype_bytes


def window_step_bytes(config: dict, slots: float) -> float:
    """Window bytes a decode step moves: every slot's rows in every
    ``conv`` layer read once and written once (the step shifts the whole
    [K-1, slots, H] tile of a layer, idle slots' rows with it)."""
    return 2.0 * layers(config, "conv") * slots * window_bytes_per_slot(
        config)


def mixer_step_bytes(config: dict, slots: float,
                     weight_bytes: int = 2) -> float:
    """Bytes the ``conv`` mixers of a decode step must move: their weights
    once and the windows read and written."""
    return (weight_bytes * layers(config, "conv") * conv_mixer_params(config)
            + window_step_bytes(config, slots))


def kv_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    """K and V rows of one token over the attention layers, as a true
    head_dim stores them (a pair of 64-wide heads fills a 128-lane row: no
    lane is padding)."""
    return (2 * layers(config, "full_attention")
            * config["num_key_value_heads"] * head_dim(config) * dtype_bytes)


def decode_step_bytes(config: dict, live_kv_tokens: float,
                      experts_hit_per_step: float, slots: float) -> float:
    """Bytes one decode step must move through HBM: the weights every step
    reads once, the experts HIT in it, the conv windows read and written,
    the live keys and values. Activations, embedding rows and the written
    K/V are left out (under 1 %), so a roofline share this feeds reads a
    little low, never high."""
    return (once_a_step_weight_bytes(config)
            + expert_bytes(config, experts_hit_per_step)
            + window_step_bytes(config, slots)
            + kv_bytes_per_token(config) * live_kv_tokens)
