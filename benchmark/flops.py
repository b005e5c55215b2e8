"""What the work costs, from shapes alone: operations per trained token,
bytes a decode step must read, and the chip's published peaks.

``config`` everywhere is a configuration file of ``benchmark/configs/`` as a
dict (the model's own ``config.json`` keys at the top level)."""

from __future__ import annotations

import json
from pathlib import Path

_PEAKS = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip. An unknown kind is an error, never a
    default."""
    table = json.loads(_PEAKS.read_text())["device_kinds"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}: "
                       f"add it to {_PEAKS.name} with its source")
    return table[device_kind]


def _dims(config: dict) -> tuple[int, int, int, int, int, int, int]:
    h = config["hidden_size"]
    nq = config["num_attention_heads"]
    return (config["num_hidden_layers"], h, config["intermediate_size"], nq,
            config["num_key_value_heads"], config.get("head_dim", h // nq),
            config["vocab_size"])


def matmul_params(config: dict) -> int:
    """Parameters that take part in a matrix multiplication for every token:
    the blocks' projections and gated FFN, and the output head. The
    embedding is a lookup and is not counted."""
    L, H, F, Nq, Nkv, D, V = _dims(config)
    attn = H * Nq * D + 2 * H * Nkv * D + Nq * D * H
    return L * (attn + 3 * H * F) + H * V


def total_params(config: dict) -> int:
    L, H, F, Nq, Nkv, D, V = _dims(config)
    head = 0 if config.get("tie_word_embeddings") else H * V
    return matmul_params(config) - H * V + head + V * H + (2 * L + 1) * H


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward operations per trained token: 6 per matmul
    parameter, plus attention scores and values at 12*L*Nq*D*S. That is the
    NON-CAUSAL convention (every query against every key, as PaLM's MFU
    counts it); a causal kernel does half of the attention term. Recomputed
    operations are not counted. Copied from models/gpt.py flops_per_token."""
    L, _, _, Nq, _, D, _ = _dims(config)
    return 6.0 * matmul_params(config) + 12.0 * L * Nq * D * seq_len


def kv_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    L, _, _, _, Nkv, D, _ = _dims(config)
    return 2 * L * Nkv * D * dtype_bytes


def decode_step_bytes(config: dict, live_kv_tokens: float,
                      weight_bytes: int = 2) -> float:
    """Bytes one decode step must read from HBM whatever the batch: every
    block weight and the output head once, and the keys and values of the
    tokens that are live in the batch. The embedding rows, the activations
    and the written K/V are left out (under 1 % at these sizes), so the
    roofline share this feeds is a little low, never high."""
    return (matmul_params(config) * weight_bytes
            + kv_bytes_per_token(config) * live_kv_tokens)
