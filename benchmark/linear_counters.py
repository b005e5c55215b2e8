"""What the linear cell's metric readers share: the runner's by-scope device
seconds (``run["trace"]["scope_s"]``, ``runners/linear.py``) and deltas of
the engine's ``kda``, ``kv`` and ``moe`` counters over the traced stretch
and the window. A program or a trace without them gives None, and the
reader leaves its metric out."""

from __future__ import annotations

from benchmark import (flops_linear, hybrid_counters, latent_counters,
                       moe_counters)

scope_seconds = hybrid_counters.scope_seconds
traced_decode_steps = hybrid_counters.traced_decode_steps


def _group(run: dict, which: str, key: str) -> float | None:
    """Delta of ``stats()["kda"][key]`` over ``which`` (a pair of
    snapshots: ``trace_stats`` or ``stats``)."""
    s = run.get(which) or {}
    a = (s.get("before") or {}).get("kda")
    b = (s.get("after") or {}).get("kda")
    if not a or not b or key not in a or key not in b:
        return None
    return b[key] - a[key]


def live_slots_per_step(run: dict) -> float | None:
    """State updates of live slots a decode step, over the traced stretch."""
    slot_steps = _group(run, "trace_stats", "slot_steps")
    s = run.get("trace_stats") or {}
    if slot_steps is None or "decode_steps" not in s.get("after", {}):
        return None
    steps = s["after"]["decode_steps"] - s["before"]["decode_steps"]
    return slot_steps / steps if steps else None


def decode_experts_hit_per_step(run: dict) -> float | None:
    """(expert layer, held expert) pairs hit in a decode step of the traced
    stretch, summed over the expert layers."""
    d = moe_counters.traced(run)
    if not d or not d["decode_layer_steps"]:
        return None
    steps = d["decode_layer_steps"] / flops_linear.layers(run["config"], "E")
    return d["decode_experts_hit"] / steps


def held_experts_hit_share(run: dict) -> float | None:
    """(expert layer, step, held expert) triples in which some live token
    chose the expert, of all such triples over the window (%)."""
    d = moe_counters.window(run)
    if not d or not d["layer_steps"]:
        return None
    return 100.0 * d["experts_hit"] / (
        run["config"]["num_experts"] * d["layer_steps"])


def live_latent_tokens(run: dict) -> float | None:
    """Latent rows a decode step of the traced stretch reads a ``*`` layer:
    the pages the slots' lengths cover (the engine's own count), as the
    kernel copies them."""
    pages = latent_counters.live_pages_per_step(run)
    ps = latent_counters.page_size(run)
    return None if pages is None or ps is None else pages * ps


def decode_step_bytes(run: dict) -> tuple[float, float] | None:
    """(state bytes, all bytes) a decode step of the traced stretch must
    move (``flops_linear.decode_step_bytes``)."""
    slots, hit = live_slots_per_step(run), decode_experts_hit_per_step(run)
    rows = live_latent_tokens(run)
    if slots is None or hit is None or rows is None:
        return None
    cfg = run["config"]
    return (flops_linear.state_step_bytes(cfg, slots),
            flops_linear.decode_step_bytes(cfg, rows, hit, slots))


def state_carry_token_share(run: dict) -> float | None:
    """Prompt tokens prefilled by chunk programs that read a slot's state,
    of all prompt tokens prefilled, over the window (%)."""
    carried = _group(run, "stats", "state_carry_tokens")
    s = run.get("stats") or {}
    if carried is None or "prefill_tokens" not in s.get("after", {}):
        return None
    tokens = s["after"]["prefill_tokens"] - s["before"]["prefill_tokens"]
    return 100.0 * carried / tokens if tokens else None
