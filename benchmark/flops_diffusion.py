"""What a block-diffusion sparse-expert decoder's work costs, from shapes
alone: its parameters, the bytes a denoise FORWARD must read and the
operations it needs. (``flops_moe.py`` reads ``intermediate_size`` as one
expert's width; an ``sdar_moe`` file states it under
``moe_intermediate_size``, and its q/k norms are one [head_dim] vector.)

``config`` is a configuration file of ``benchmark/configs/`` as a dict with
the published keys. A forward is one pass of every slot's window of
``block_length`` rows through the layers: what the decode dispatch chains
(``serve/decode.py denoise_scan``)."""

from __future__ import annotations


def _dims(config: dict) -> tuple[int, int, int, int, int, int, int, int, int]:
    h = config["hidden_size"]
    nq = config["num_attention_heads"]
    return (config["num_hidden_layers"], h, config["moe_intermediate_size"],
            nq, config["num_key_value_heads"], config.get("head_dim", h // nq),
            config["vocab_size"], config["num_experts"],
            config["num_experts_per_tok"])


def expert_params(config: dict) -> int:
    """One expert's gate, up and down kernels."""
    _, H, F, *_ = _dims(config)
    return 3 * H * F


def attention_params(config: dict) -> int:
    """One layer's q, k, v and o kernels."""
    _, H, _, Nq, Nkv, D, *_ = _dims(config)
    return H * Nq * D + 2 * H * Nkv * D + Nq * D * H


def layer_params(config: dict) -> int:
    """Attention, the two block norms, the two per-head q/k norms, the
    router and all the experts of one layer."""
    _, H, _, _, _, D, _, E, _ = _dims(config)
    head_norms = 2 * D if config.get("qk_norm") == "head" else 0
    return (attention_params(config) + 2 * H + head_norms + H * E
            + E * expert_params(config))


def total_params(config: dict) -> int:
    L, H, *_, V, _, _ = _dims(config)
    head = 0 if config.get("tie_word_embeddings") else H * V
    return V * H + head + H + L * layer_params(config)


def shared_matmul_params(config: dict) -> int:
    """Matrix-multiplication parameters every row uses whatever its
    routing: the layers' attention projections and routers, and the output
    head. (The embedding is a lookup; the norms are vectors.)"""
    L, H, *_, V, E, _ = _dims(config)
    return L * (attention_params(config) + H * E) + H * V


def kv_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    L, _, _, _, Nkv, D, *_ = _dims(config)
    return 2 * L * Nkv * D * dtype_bytes


def expert_bytes(config: dict, experts_hit: float,
                 weight_bytes: int = 2) -> float:
    """Bytes of the experts' kernels a forward's grouped matmuls must
    stream for ``experts_hit`` (layer, expert) pairs: each HIT expert's
    gate, up and down once."""
    return experts_hit * expert_params(config) * weight_bytes


def page_bytes(config: dict, live_pages: float, page_size: int) -> float:
    """Bytes of K and V the block kernel copies in one forward: the pages
    the slots' windows reach (``live_pages``: summed over the slots), whole,
    in every layer."""
    return live_pages * page_size * kv_bytes_per_token(config)


def forward_bytes(config: dict, live_pages: float, page_size: int,
                  experts_hit: float, weight_bytes: int = 2) -> float:
    """Bytes one forward must read from HBM: the attention, router and head
    weights once, the experts HIT in it once (summed over the layers: at
    most L x E) and the live K/V pages. Embedding rows, activations, norms
    and the window's written K/V are left out (under 1 %), so a roofline
    share this feeds reads a little low, never high."""
    return (shared_matmul_params(config) * weight_bytes
            + expert_bytes(config, experts_hit, weight_bytes)
            + page_bytes(config, live_pages, page_size))


def forward_flops(config: dict, rows: float) -> float:
    """Operations of one forward over ``rows`` window rows, without the
    attention scores: 2 per matmul parameter a row multiplies, of the
    experts its k alone."""
    L, *_, K = _dims(config)
    return 2.0 * rows * (shared_matmul_params(config)
                         + L * K * expert_params(config))
