"""What the self-drafting cell's metric readers share: the runner's
by-scope device seconds of the decode program alone
(``run["trace"]["decode_scope_s"]``, ``runners/selfdraft.py``; every scope
an operation lies in counted) and deltas of the engine's ``mtp_*``, ``kv``
and ``moe`` counters. A program or a trace without them (the parent commit:
no prediction module, no ``mtp`` group in its stats) gives None, and the reader
leaves its metric out."""

from __future__ import annotations

from benchmark import (flops_selfdraft, latent_counters, layer_metrics,
                       moe_counters)

traced_steps = latent_counters.traced_decode_steps
live_pages_per_step = latent_counters.live_pages_per_step
page_size = latent_counters.page_size


KEYS = ("drafts", "accepted", "slot_steps", "tokens")


def mtp_delta(run: dict) -> dict | None:
    """The window's delta of ``engine.stats()["mtp_*"]``: drafts verified,
    drafts that stood, slot-steps credited, tokens they made."""
    a, b = run["stats"]["before"], run["stats"]["after"]
    if not is_selfdraft(run) or any(
            f"mtp_{k}" not in s for k in KEYS for s in (a, b)):
        return None
    return {k: b[f"mtp_{k}"] - a[f"mtp_{k}"] for k in KEYS}


def is_selfdraft(run: dict) -> bool:
    return bool(run["stats"]["after"].get("mtp"))


def step_scope_ms(run: dict, *scopes: str) -> float | None:
    """Device ms a step of the traced stretch spent under ``scopes`` in the
    DECODE program (those the trace has; None where it has none)."""
    by_scope = (run.get("trace") or {}).get("decode_scope_s")
    steps = traced_steps(run)
    if (not is_selfdraft(run) or not by_scope or not steps
            or not any(s in by_scope for s in scopes)):
        return None
    return 1e3 * sum(by_scope[s][1] for s in scopes if s in by_scope) / steps


def step_ms(run: dict) -> float | None:
    """Device ms of a draft-and-verify step: the decode program's
    executions in the traced stretch / (executions x steps a dispatch), as
    the accepted ``serve_programs.decode_step_device_ms`` reads it."""
    if not is_selfdraft(run) or not run.get("trace"):
        return None
    return layer_metrics.load("serve_programs.decode_step_device_ms").read(run)


def experts_hit_per_step(run: dict) -> float | None:
    """(expert layer, held expert) pairs hit in a step of the traced
    stretch, summed over the main stack's expert layers and the module's."""
    d = moe_counters.traced(run)
    if not d or not d["decode_layer_steps"]:
        return None
    steps = d["decode_layer_steps"] / flops_selfdraft.expert_layers(
        run["config"])
    return d["decode_experts_hit"] / steps


def step_bytes(run: dict) -> float | None:
    """Bytes a step of the traced stretch must move
    (``flops_selfdraft.step_bytes``)."""
    pages, ps = live_pages_per_step(run), page_size(run)
    hit = experts_hit_per_step(run)
    if pages is None or ps is None or hit is None or not is_selfdraft(run):
        return None
    return flops_selfdraft.step_bytes(run["config"], pages * ps, hit)
