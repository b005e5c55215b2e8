"""What the sessions cell's metric readers share: the runner's by-scope
device seconds (``run["trace"]["scope_s"]``, ``runners/sessions.py``) and
deltas of the engine's ``kda`` (the snapshot counters among them), ``kv``
and ``moe`` counters over the traced stretch and the window. A program or a
trace without them (a commit from before the snapshot pool) gives None, and
the reader leaves its metric out."""

from __future__ import annotations

from benchmark import (flops_sessions, hybrid_counters, latent_counters,
                       linear_counters, moe_counters)

scope_seconds = hybrid_counters.scope_seconds
traced_decode_steps = hybrid_counters.traced_decode_steps
live_slots_per_step = linear_counters.live_slots_per_step
kda_delta = linear_counters._group


def scope_ms_per_step(run: dict, *scopes: str) -> float | None:
    """Device ms a decode step of the traced stretch spent under
    ``scopes`` (those the trace has; None where it has none of them)."""
    steps = traced_decode_steps(run)
    seconds = [scope_seconds(run, s) for s in scopes]
    if not steps or all(s is None for s in seconds):
        return None
    return 1e3 * sum(s or 0.0 for s in seconds) / steps


def decode_experts_hit_per_step(run: dict) -> float | None:
    """(expert layer, held expert) pairs hit in a decode step of the traced
    stretch, summed over the expert layers."""
    d = moe_counters.traced(run)
    if not d or not d["decode_layer_steps"]:
        return None
    steps = d["decode_layer_steps"] / flops_sessions.layers(run["config"],
                                                            "E")
    return d["decode_experts_hit"] / steps


def live_kv_tokens(run: dict) -> float | None:
    """K/V rows a decode step of the traced stretch reads in a ``*`` layer:
    the pages the slots' lengths cover (the engine's own count), as the
    kernel copies them."""
    pages = latent_counters.live_pages_per_step(run)
    ps = latent_counters.page_size(run)
    return None if pages is None or ps is None else pages * ps


def decode_step_bytes(run: dict) -> float | None:
    """Bytes a decode step of the traced stretch must move
    (``flops_sessions.decode_step_bytes``)."""
    slots, hit = live_slots_per_step(run), decode_experts_hit_per_step(run)
    rows = live_kv_tokens(run)
    if slots is None or hit is None or rows is None:
        return None
    return flops_sessions.decode_step_bytes(run["config"], rows, hit, slots)


def snapshot_token_share(run: dict) -> float | None:
    """Prompt tokens skipped through a snapshot, of the window's prompt
    tokens (skipped and prefilled) (%)."""
    skipped = kda_delta(run, "stats", "snapshot_tokens_skipped")
    s = run.get("stats") or {}
    if skipped is None or "prefill_tokens" not in s.get("after", {}):
        return None
    computed = s["after"]["prefill_tokens"] - s["before"]["prefill_tokens"]
    return (100.0 * skipped / (skipped + computed)
            if skipped + computed > 0 else None)


def snapshot_miss_share(run: dict) -> float | None:
    """Admissions whose hashed page chain had no snapshot on it, of the
    window's admissions that found a hashed chain (%)."""
    hits = kda_delta(run, "stats", "snapshot_hits")
    misses = kda_delta(run, "stats", "snapshot_misses")
    if hits is None or misses is None or hits + misses <= 0:
        return None
    return 100.0 * misses / (hits + misses)
