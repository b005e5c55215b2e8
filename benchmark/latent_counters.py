"""What the latent cell's metric readers share: the runner's by-scope device
seconds (``run["trace"]["scope_s"]``, ``runners/latent.py``) and deltas of
the engine's ``kv``, ``moe`` and token counters over the traced stretch and
the window. A program or a trace without them gives None, and the reader
leaves its metric out."""

from __future__ import annotations

from benchmark import flops_latent, hybrid_counters, moe_counters

scope_seconds = hybrid_counters.scope_seconds
traced_decode_steps = hybrid_counters.traced_decode_steps


def _kv(run: dict, which: str) -> tuple[dict, dict] | None:
    s = run.get(which) or {}
    a, b = s.get("before", {}).get("kv"), s.get("after", {}).get("kv")
    if not a or not b or "live_pages" not in a or "live_pages" not in b:
        return None
    return a, b


def live_pages_per_step(run: dict) -> float | None:
    """Latent pages the slots' lengths cover at a decode step of the traced
    stretch: the engine counts them once a dispatch (at its first step), so
    the counter's delta over the dispatches."""
    kv = _kv(run, "trace_stats")
    s = run.get("trace_stats") or {}
    if kv is None or "decode_steps" not in s.get("after", {}):
        return None
    steps = s["after"]["decode_steps"] - s["before"]["decode_steps"]
    dispatches = steps / run["serve_cfg"]["decode_steps_per_dispatch"]
    return (kv[1]["live_pages"] - kv[0]["live_pages"]) / dispatches \
        if dispatches else None


def page_size(run: dict) -> int | None:
    kv = _kv(run, "trace_stats")
    return kv[1]["page_size"] if kv else None


def is_latent(run: dict) -> bool:
    kv = _kv(run, "stats")
    return bool(kv and kv[1].get("kind") == "latent")


def decode_experts_hit_per_step(run: dict) -> float | None:
    """(expert layer, expert) pairs hit in a decode step of the traced
    stretch, summed over the expert layers."""
    d = moe_counters.traced(run)
    if not d or not d["decode_layer_steps"]:
        return None
    steps = d["decode_layer_steps"] / flops_latent.expert_layers(
        run["config"])
    return d["decode_experts_hit"] / steps


def decode_step_bytes(run: dict) -> tuple[float, float] | None:
    """(latent bytes, all bytes) a decode step of the traced stretch must
    move: the live pages' rows in every layer, and with them the weights
    read once and the hit experts."""
    pages, ps = live_pages_per_step(run), page_size(run)
    hit = decode_experts_hit_per_step(run)
    if pages is None or ps is None or hit is None:
        return None
    cfg = run["config"]
    latent = flops_latent.latent_bytes_per_token(cfg) * pages * ps
    return latent, (flops_latent.once_a_step_weight_bytes(cfg)
                    + flops_latent.expert_bytes(cfg, hit) + latent)
