"""What the hybrid cell's metric readers share: the runner's by-scope
device seconds (``run["trace"]["scope_s"]``, ``runners/hybrid.py``) and
deltas of the engine's ``ssm`` and ``moe`` counters over the traced
stretch. A program or a trace without them gives None, and the reader
leaves its metric out."""

from __future__ import annotations

from benchmark import flops_hybrid, moe_counters


def scope_seconds(run: dict, scope: str) -> float | None:
    """Device seconds of the leaf operations under ``scope`` in the traced
    stretch (exact scope: ``moe_gmm`` does not count ``moe_gmm_prefill``)."""
    scopes = (run.get("trace") or {}).get("scope_s")
    if not scopes or scope not in scopes:
        return None
    return float(scopes[scope][1])


def traced_decode_steps(run: dict) -> float | None:
    """Decode steps the device ran in the traced stretch: the decode
    program's executions x steps per dispatch."""
    n, _ = (run.get("trace") or {}).get("programs", {}).get("decode",
                                                            (0, 0.0))
    return n * run["serve_cfg"]["decode_steps_per_dispatch"] or None


def _traced(run: dict, group: str, key: str) -> float | None:
    s = run.get("trace_stats") or {}
    a, b = s.get("before", {}).get(group), s.get("after", {}).get(group)
    if not a or not b or key not in a or key not in b:
        return None
    return b[key] - a[key]


def live_slots_per_step(run: dict) -> float | None:
    """State updates of live slots a decode step, over the traced stretch."""
    slot_steps = _traced(run, "ssm", "slot_steps")
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
    steps = d["decode_layer_steps"] / flops_hybrid.layers(run["config"], "E")
    return d["decode_experts_hit"] / steps
