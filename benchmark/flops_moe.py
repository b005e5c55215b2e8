"""What a sparse-expert decoder's work costs, from shapes alone: the bytes
a decode step must read and the operations a token needs. ``flops.py``
counts a dense feed-forward and would read an MoE configuration's
``intermediate_size`` (ONE expert's width) as a dense width.

``config`` is a configuration file of ``benchmark/configs/`` as a dict with
the published MoE keys (``num_experts``, ``num_experts_per_tok``,
``intermediate_size`` = one expert's width)."""

from __future__ import annotations


def _dims(config: dict) -> tuple[int, int, int, int, int, int, int, int, int]:
    h = config["hidden_size"]
    nq = config["num_attention_heads"]
    return (config["num_hidden_layers"], h, config["intermediate_size"], nq,
            config["num_key_value_heads"], config.get("head_dim", h // nq),
            config["vocab_size"], config["num_experts"],
            config["num_experts_per_tok"])


def expert_params(config: dict) -> int:
    """One expert's gate, up and down kernels."""
    _, H, F, *_ = _dims(config)
    return 3 * H * F


def shared_matmul_params(config: dict) -> int:
    """Matrix-multiplication parameters every token uses whatever its
    routing: the blocks' attention projections and routers, and the output
    head. (The embedding is a lookup; the norms are vectors.)"""
    L, H, _, Nq, Nkv, D, V, E, _ = _dims(config)
    attn = H * Nq * D + 2 * H * Nkv * D + Nq * D * H
    return L * (attn + H * E) + H * V


def total_params(config: dict) -> int:
    L, H, _, Nq, Nkv, D, V, E, _ = _dims(config)
    head = 0 if config.get("tie_word_embeddings") else H * V
    norms = L * (2 * H + ((Nq + Nkv) * D if config.get("qk_norm")
                          == "projection" else 0)) + H
    return (shared_matmul_params(config) - H * V + head + V * H + norms
            + L * E * expert_params(config))


def forward_flops_per_token(config: dict) -> float:
    """Operations of one token's forward pass without the attention scores:
    2 per matmul parameter it multiplies, of the experts its k alone."""
    L, *_, K = _dims(config)
    return 2.0 * (shared_matmul_params(config)
                  + L * K * expert_params(config))


def kv_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    L, _, _, _, Nkv, D, *_ = _dims(config)
    return 2 * L * Nkv * D * dtype_bytes


def expert_bytes(config: dict, experts_hit: float,
                 weight_bytes: int = 2) -> float:
    """Bytes of the experts' kernels a grouped matmul must stream for
    ``experts_hit`` (layer, expert) pairs: each HIT expert's gate, up and
    down once; an expert no live token chose is not read."""
    return experts_hit * expert_params(config) * weight_bytes


def decode_step_bytes(config: dict, live_kv_tokens: float,
                      experts_hit_per_step: float,
                      weight_bytes: int = 2) -> float:
    """Bytes one decode step must read from HBM: the attention, router and
    head weights once, the experts HIT in the step once (summed over the
    layers: ``experts_hit_per_step`` is at most L x E), and the keys and
    values of the tokens live in the batch. Embedding rows, activations,
    norms and the written K/V are left out (under 1 %), so the roofline
    share this feeds reads a little low, never high."""
    return (shared_matmul_params(config) * weight_bytes
            + expert_bytes(config, experts_hit_per_step, weight_bytes)
            + kv_bytes_per_token(config) * live_kv_tokens)
