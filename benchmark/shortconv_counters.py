"""What the short-conv cell's metric readers share: the runner's by-scope
device seconds of the decode program (``run["trace"]["decode_scope_s"]``,
``runners/shortconv.py``) and deltas of the engine's ``shortconv``, ``moe``
and ``kv`` counters over the traced stretch. A program or a trace without
them (the parent of PR 55 has no ``shortconv`` group) gives None, and the
reader leaves its metric out."""

from __future__ import annotations

from benchmark import (flops_shortconv, hybrid_counters, moe_counters,
                       parallel_counters)

traced_decode_steps = hybrid_counters.traced_decode_steps
decode_scope_ms_per_step = parallel_counters.decode_scope_ms_per_step
live_kv_tokens = parallel_counters.live_kv_tokens


def is_shortconv(run: dict) -> bool:
    """Did the program under test serve a ``C`` model (its stats have the
    kind's own group)?"""
    after = (run.get("stats") or {}).get("after", {})
    return "shortconv" in after


def slots(run: dict) -> int:
    return run["serve_cfg"]["max_batch_size"]


def decode_experts_hit_per_step(run: dict) -> float | None:
    """(expert layer, expert) pairs hit in a decode step of the traced
    stretch, summed over the expert layers: at most 14 x 32."""
    d = moe_counters.traced(run)
    if not d or not d["decode_layer_steps"]:
        return None
    steps = d["decode_layer_steps"] / flops_shortconv.layers(
        run["config"], "experts")
    return d["decode_experts_hit"] / steps


def decode_step_bytes(run: dict) -> float | None:
    """Bytes a decode step of the traced stretch must move
    (``flops_shortconv.decode_step_bytes``)."""
    rows, hit = live_kv_tokens(run), decode_experts_hit_per_step(run)
    if not is_shortconv(run) or rows is None or hit is None:
        return None
    return flops_shortconv.decode_step_bytes(run["config"], rows, hit,
                                             slots(run))

