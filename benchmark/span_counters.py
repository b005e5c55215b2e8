"""What the readers of the program's own spans and counters share: deltas of
``engine.stats()`` over the traced stretch (``run["trace_stats"]``). The keys
are the program's (``metrics/spans.py``: ``clock_s``, ``phases``,
``starved_s``; the scheduler's ``queue_wait_ms``); a program that has none of
them, as every commit before PR 25, gives ``None`` and the metric is left out
of the line."""

from __future__ import annotations

# the spans in which the engine thread works on the host: not idle, and not
# waiting for the device (decode.wait, prefill.wait, and prefill.key_wait,
# the fetch of a slot's key, which queues behind a dispatch in flight)
HOST_PHASES = ("llmctl.engine.admit", "llmctl.engine.prefill.host",
               "llmctl.engine.capacity", "llmctl.engine.decode.submit",
               "llmctl.engine.apply", "llmctl.engine.deliver")
PREFILL_WAIT = "llmctl.engine.prefill.wait"


def delta(pair: dict, key: str) -> float | None:
    """after[key] - before[key], or None where the program has no such key."""
    if key not in pair["before"] or key not in pair["after"]:
        return None
    return pair["after"][key] - pair["before"][key]


def phase_seconds(pair: dict, names) -> float | None:
    """Self seconds the named spans gained between the two snapshots."""
    if "phases" not in pair["before"] or "phases" not in pair["after"]:
        return None
    a, b = pair["before"]["phases"], pair["after"]["phases"]
    return sum(b.get(n, {"s": 0.0})["s"] - a.get(n, {"s": 0.0})["s"]
               for n in names)


def ms_per_decode_step(run: dict, names) -> float | None:
    pair = run["trace_stats"]
    seconds, steps = phase_seconds(pair, names), delta(pair, "decode_steps")
    if seconds is None or not steps:
        return None
    return 1e3 * seconds / steps
