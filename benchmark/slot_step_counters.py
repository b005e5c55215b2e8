"""What the readers of the engine's two ledgers share (PR 51).

The slot-step ledger, ``engine.stats()["slot_steps"]``: every slot of every
decode step (a diffusion model's forward, a verify window) in one class,
``useful`` (a request was credited with the step's result), ``overrun`` (the
slot was live and the result was nobody's), ``prompt_wait`` (a seated
request was not live yet), ``empty``; beside them ``first_tokens`` (tokens
no decode slot-step made) and ``tokens_credited``. Read over the WHOLE
window (``run["stats"]``), where the tokens per second are counted too.

``starved_by_phase``: the seconds of ``starved_s`` by the span the engine
thread was in, read over the traced stretch (``run["trace_stats"]``), as
``engine.device_starved_share`` reads ``starved_s``.

A program without the keys, as every commit before PR 51, gives ``None``
and the metric is left out of the line."""

from __future__ import annotations

import sys

from benchmark import span_counters

CLASSES = ("useful", "overrun", "prompt_wait", "empty")
# the spans in which the engine thread hands results on, and those in which
# it gets the next program ready
DELIVER_SPANS = ("llmctl.engine.apply", "llmctl.engine.deliver",
                 "llmctl.engine.snapshot.take", "llmctl.engine.snapshot.arm")
DISPATCH_SPANS = ("llmctl.engine.admit", "llmctl.engine.capacity",
                  "llmctl.engine.prefill.host", "llmctl.engine.decode.submit")


def window(run: dict) -> dict | None:
    """Window deltas of the ledger's six counts, ``decode_steps`` and
    ``clock_s``, and the slots; None where the program has no ledger or the
    window no decode step."""
    a, b = run["stats"]["before"], run["stats"]["after"]
    if "slot_steps" not in a or "slot_steps" not in b:
        return None
    out = {k: b["slot_steps"][k] - a["slot_steps"][k] for k in b["slot_steps"]}
    out["decode_steps"] = b["decode_steps"] - a["decode_steps"]
    out["clock_s"] = b["clock_s"] - a["clock_s"]
    out["slots"] = run["serve_cfg"]["max_batch_size"]
    return out if out["decode_steps"] > 0 else None


def share(run: dict, name: str) -> float | None:
    """Class ``name``'s share of the window's slot-steps, in %. The
    denominator is ``decode_steps`` x slots and not the classes' sum, so
    that four shares which do not add to 100 show a ledger that leaks."""
    w = window(run)
    if w is None:
        return None
    return 100.0 * w[name] / (w["decode_steps"] * w["slots"])


def wall_ms_per_decode_step(run: dict) -> float | None:
    w = window(run)
    return None if w is None else 1e3 * w["clock_s"] / w["decode_steps"]


def starved_ms_per_decode_step(run: dict, names, metric: str) -> float | None:
    """Starved seconds under the spans ``names`` over the traced stretch /
    decode steps in it; the whole table goes to stderr."""
    pair = run["trace_stats"]
    if not pair or "starved_by_phase" not in pair["before"] \
            or "starved_by_phase" not in pair["after"]:
        return None
    steps = span_counters.delta(pair, "decode_steps")
    if not steps:
        return None
    a, b = pair["before"]["starved_by_phase"], pair["after"]["starved_by_phase"]
    table = {k: v - a.get(k, 0.0) for k, v in b.items()}
    print(f"[bench] {metric}: starved seconds by span over {steps} steps "
          f"(they add to {sum(table.values()):.6f} of starved_s "
          f"{span_counters.delta(pair, 'starved_s'):.6f}): "
          + ", ".join(f"{k} {v:.6f}" for k, v in sorted(
              table.items(), key=lambda kv: -kv[1])), file=sys.stderr)
    return 1e3 * sum(table.get(n, 0.0) for n in names) / steps
