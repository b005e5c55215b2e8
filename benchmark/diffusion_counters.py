"""What the diffusion cell's metric readers share: deltas of the engine's
``stats()["diffusion"]`` (``serve/decode.py DENOISE_COUNTS``, counted on the
device over live slots) and ``["moe"]`` counters over the window and the
traced stretch, and the runner's device seconds by program and kernel
(``run["trace"]["program_scope_s"]``, ``runners/diffusion.py``). A program
or a trace without them (a parent commit from before the mechanism) gives
None, and the reader leaves its metric out."""

from __future__ import annotations

from benchmark import flops_diffusion, moe_counters


def _delta(run: dict, which: str, key: str) -> float | None:
    """Delta of ``stats()["diffusion"][key]`` over ``which`` (a pair of
    snapshots: ``trace_stats`` or ``stats``)."""
    s = run.get(which) or {}
    a = (s.get("before") or {}).get("diffusion")
    b = (s.get("after") or {}).get("diffusion")
    if not a or not b or key not in a or key not in b:
        return None
    return b[key] - a[key]


def ratio(run: dict, above: str, below: str, which: str = "stats",
          scale: float = 1.0) -> float | None:
    """``above`` / (``scale`` x ``below``) of the counters' deltas."""
    a, b = _delta(run, which, above), _delta(run, which, below)
    return a / (scale * b) if a is not None and b else None


def block_length(run: dict) -> int | None:
    return ((run.get("stats") or {}).get("after") or {}).get(
        "diffusion", {}).get("block_length")


def page_size(run: dict) -> int | None:
    return ((run.get("stats") or {}).get("after") or {}).get(
        "kv", {}).get("page_size")


def traced_forwards(run: dict) -> float | None:
    """Forwards the device ran in the traced stretch: the decode program's
    executions x forwards a dispatch."""
    n, _ = (run.get("trace") or {}).get("programs", {}).get("decode",
                                                            (0, 0.0))
    return n * run["serve_cfg"]["decode_steps_per_dispatch"] or None


def forward_device_ms(run: dict) -> float | None:
    """Device time of the decode program's executions in the traced
    stretch / forwards in it."""
    n, seconds = (run.get("trace") or {}).get("programs", {}).get(
        "decode", (0, 0.0))
    forwards = traced_forwards(run)
    return 1e3 * seconds / forwards if n and forwards else None


def kernel_ms_per_forward(run: dict, scope: str) -> float | None:
    """Device time of the DECODE program's operations named ``scope`` in
    the traced stretch / forwards in it (a prefill program's kernels of
    the same name are another program's)."""
    scopes = ((run.get("trace") or {}).get("program_scope_s") or {}).get(
        "decode")
    forwards = traced_forwards(run)
    if not scopes or scope not in scopes or not forwards:
        return None
    return 1e3 * float(scopes[scope][1]) / forwards


def live_pages_per_forward(run: dict) -> float | None:
    """Pages the live slots' windows reach in a forward of the traced
    stretch, summed over the slots (the device's own count)."""
    return ratio(run, "live_pages", "forwards", "trace_stats")


def experts_hit_per_forward(run: dict) -> float | None:
    """(layer, expert) pairs hit in a forward of the traced stretch,
    summed over the layers: at most L x E."""
    d = moe_counters.traced(run)
    if not d or not d["decode_layer_steps"]:
        return None
    forwards = d["decode_layer_steps"] / run["config"]["num_hidden_layers"]
    return d["decode_experts_hit"] / forwards


def forward_bytes(run: dict) -> float | None:
    pages, hit, ps = (live_pages_per_forward(run),
                      experts_hit_per_forward(run), page_size(run))
    if pages is None or hit is None or not ps:
        return None
    return flops_diffusion.forward_bytes(run["config"], pages, ps, hit)

