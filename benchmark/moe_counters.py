"""What the MoE metric readers share: deltas of the engine's routing
counters (``engine.stats()["moe"]``: ``choices`` [E], ``experts_hit``,
``layer_steps`` and the decode programs' part of the last two) between two
snapshots. A program without the counters (a dense model, a parent commit
from before them) gives None, and the reader leaves its metric out."""

from __future__ import annotations


def delta(before: dict, after: dict) -> dict | None:
    a, b = before.get("moe"), after.get("moe")
    if not a or not b:
        return None
    out = {k: b[k] - a[k] for k in b if k != "choices"}
    out["choices"] = [y - x for x, y in zip(a["choices"], b["choices"])]
    return out


def traced(run: dict) -> dict | None:
    """Over the traced stretch."""
    s = run.get("trace_stats") or {}
    return delta(s.get("before", {}), s.get("after", {}))


def window(run: dict) -> dict | None:
    """Over the measured window."""
    return delta(run["stats"]["before"], run["stats"]["after"])


def decode_experts_hit_per_step(run: dict) -> float | None:
    """(layer, expert) pairs hit in a decode step of the traced stretch,
    summed over the layers: at most L x E."""
    d = traced(run)
    if not d or not d["decode_layer_steps"]:
        return None
    steps = d["decode_layer_steps"] / run["config"]["num_hidden_layers"]
    return d["decode_experts_hit"] / steps
