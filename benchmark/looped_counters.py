"""What the looped stack's metric readers share: the engine's ``loop``
group (``passes``, ``pool_planes``, ``decode_tokens``,
``decode_token_passes``; present for a looped model ALONE), the ``kv``
group's pages, the load generator's stamps (the live rows), and the runner's by-scope device seconds of the decode
program (``run["trace"]["decode_scope_s"]``: ``loop_pass``, ``exit_gate``).
A program or a trace without them (a parent that knows no ``total_ut_steps``
has no ``loop`` group) gives None, and the reader leaves its metric out."""

from __future__ import annotations

from benchmark import facts, flops_looped, parallel_counters

decode_scope_ms_per_step = parallel_counters.decode_scope_ms_per_step


def live_kv_tokens(run: dict) -> float | None:
    """K/V rows a decode step of the traced stretch must read in ONE plane:
    the tokens live in the decoding slots, from the benchmark's own stamps
    (``facts.live_kv_tokens``: a request's prompt and what it has streamed
    so far, between its first and last chunk). ROWS, not pages: the kernel
    copies whole pages, and what a slot's last page holds beyond its length
    (half a page a slot: 11 % here), like a riding prompt's pages, is not
    what the step must move; counted by pages the kernel's share read 98 %
    of the HBM peak on the chip (PERF.md 6, PR 60)."""
    trace = run.get("trace") or {}
    if "t0" not in trace or "t1" not in trace:
        return None
    return facts.live_kv_tokens(run, trace["t0"], trace["t1"])


def loop_group(run: dict) -> tuple[dict, dict] | None:
    """The ``loop`` group (before, after) of the window."""
    s = run.get("stats") or {}
    a, b = s.get("before", {}).get("loop"), s.get("after", {}).get("loop")
    return (a, b) if a and b else None


def passes(run: dict) -> int | None:
    loop = loop_group(run)
    return loop[1]["passes"] if loop else None


def slots(run: dict) -> int:
    return run["serve_cfg"]["max_batch_size"]


def passes_per_decode_token(run: dict) -> float | None:
    """Passes a token of a decode step ran, over the window."""
    loop = loop_group(run)
    if not loop:
        return None
    tokens = loop[1]["decode_tokens"] - loop[0]["decode_tokens"]
    ran = loop[1]["decode_token_passes"] - loop[0]["decode_token_passes"]
    return ran / tokens if tokens else None


def pass_ms_per_decode_step(run: dict) -> float | None:
    """Device ms of ONE pass of the stack in a decode step of the traced
    stretch: the decode program's seconds under ``loop_pass`` / (steps x
    passes)."""
    n = passes(run)
    under = decode_scope_ms_per_step(run, "loop_pass")
    return None if not n or under is None else under / n


def exit_gate_ms_per_decode_step(run: dict) -> float | None:
    if passes(run) is None:
        return None
    return decode_scope_ms_per_step(run, "exit_gate")


def live_kv_bytes(run: dict) -> float | None:
    """Live K and V bytes of every plane a decode step of the traced
    stretch reads."""
    rows = live_kv_tokens(run)
    return (None if rows is None
            else flops_looped.kv_bytes_per_token(run["config"]) * rows)


def decode_step_bytes(run: dict) -> float | None:
    """Bytes a decode step of the traced stretch must move
    (``flops_looped.decode_step_bytes``)."""
    rows = live_kv_tokens(run)
    if rows is None:
        return None
    return flops_looped.decode_step_bytes(run["config"], rows, slots(run))


def loop_share_of_decode_bytes(run: dict) -> float | None:
    kv, moved = live_kv_bytes(run), decode_step_bytes(run)
    if passes(run) is None or kv is None or not moved:
        return None
    return 100.0 * kv / moved


def pool_live_page_share(run: dict) -> float | None:
    """Pages in use (held by a slot or kept for a prefix hit; the scratch
    page is nobody's) over the pool's pages, the mean of the traced
    stretch's two ends."""
    if passes(run) is None:
        return None
    s = run.get("trace_stats") or {}
    ends = [s.get(k, {}).get("kv") for k in ("before", "after")]
    if not all(e and "free_pages" in e and "num_pages" in e for e in ends):
        return None
    return 100.0 * sum((e["num_pages"] - 1 - e["free_pages"])
                       / (e["num_pages"] - 1) for e in ends) / len(ends)
