"""What the parallel cell's metric readers share: the runner's by-scope
device seconds (``run["trace"]["scope_s"]`` over every program,
``run["trace"]["decode_scope_s"]`` over the decode program alone;
``runners/parallel.py``) and deltas of the engine's ``ssm`` and ``kv``
counters over the traced stretch. A program or a trace without them gives
None, and the reader leaves its metric out."""

from __future__ import annotations

from benchmark import flops_parallel, hybrid_counters, latent_counters

scope_seconds = hybrid_counters.scope_seconds
traced_decode_steps = hybrid_counters.traced_decode_steps
live_slots_per_step = hybrid_counters.live_slots_per_step


def decode_scope_ms_per_step(run: dict, *scopes: str) -> float | None:
    """Device ms a decode step of the traced stretch spent under ``scopes``
    in the DECODE program (those the trace has; None where it has none)."""
    by_scope = (run.get("trace") or {}).get("decode_scope_s")
    steps = traced_decode_steps(run)
    if not by_scope or not steps or not any(s in by_scope for s in scopes):
        return None
    return 1e3 * sum(by_scope[s][1] for s in scopes if s in by_scope) / steps


def live_kv_tokens(run: dict) -> float | None:
    """K/V rows a decode step of the traced stretch reads in a layer: the
    pages the slots' lengths cover (the engine's own count), as the kernel
    copies them."""
    pages = latent_counters.live_pages_per_step(run)
    ps = latent_counters.page_size(run)
    return None if pages is None or ps is None else pages * ps


def decode_step_bytes(run: dict) -> float | None:
    """Bytes a decode step of the traced stretch must move
    (``flops_parallel.decode_step_bytes``)."""
    slots, rows = live_slots_per_step(run), live_kv_tokens(run)
    if slots is None or rows is None:
        return None
    return flops_parallel.decode_step_bytes(run["config"], rows, slots)
