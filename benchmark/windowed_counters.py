"""What the windowed cell's metric readers share: the engine's ``window``
group (``window``, ``ring_pages``, the two pools' bytes and, counted once a
decode dispatch as ``live_pages`` is, ``window_rows``,
``window_rows_unwindowed``, ``full_rows``, ``ring_wraps``; present for a
model with window layers ALONE), the routing counters, the load generator's
stamps (the live rows) and the runner's by-scope device seconds of the decode
program (``run["trace"]["decode_scope_s"]``: ``window_attention``,
``paged_attention``). A program or a trace without them (a parent that knows
no ``layer_types`` has no ``window`` group) gives None, and the reader leaves
its metric out."""

from __future__ import annotations

from benchmark import facts, flops_windowed, moe_counters, parallel_counters

decode_scope_ms_per_step = parallel_counters.decode_scope_ms_per_step


def window_group(run: dict, which: str = "stats") -> tuple[dict, dict] | None:
    """The ``window`` group (before, after) of the window (``stats``) or of
    the traced stretch (``trace_stats``)."""
    s = run.get(which) or {}
    a, b = s.get("before", {}).get("window"), s.get("after", {}).get("window")
    return (a, b) if a and b else None


def delta(run: dict, name: str, which: str = "stats") -> float | None:
    group = window_group(run, which)
    return None if group is None else group[1][name] - group[0][name]


def slots(run: dict) -> int:
    return run["serve_cfg"]["max_batch_size"]


def live_tokens(run: dict) -> float | None:
    """Tokens live in the decoding slots over the traced stretch, from the
    benchmark's own stamps (``facts.live_kv_tokens``: a request's prompt and
    what it has streamed so far, between its first and last chunk). ROWS,
    not pages (PERF.md 6, PR 60: counted by pages a share read 98 %)."""
    trace = run.get("trace") or {}
    if "t0" not in trace or "t1" not in trace:
        return None
    return facts.live_kv_tokens(run, trace["t0"], trace["t1"])


def live_slots(run: dict) -> float | None:
    """Requests between their first and last chunk, the traced stretch's
    time average."""
    trace = run.get("trace") or {}
    if "t0" not in trace or "t1" not in trace:
        return None
    t0, t1 = trace["t0"], trace["t1"]
    area = sum(max(min(r["chunks"][-1], t1) - max(r["chunks"][0], t0), 0.0)
               for r in run["stamps"]["records"] if r["chunks"])
    return area / max(t1 - t0, 1e-9)


def window_rows_per_step(run: dict) -> float | None:
    """Rows x window layers the window kernel must read in a decode step of
    the traced stretch: for every live request min(length, window), from
    the stamps (the engine's ``window_rows`` counts the same at every
    dispatch's first step; the stamps give the stretch's time average and
    leave a riding prompt's slot out, as ``live_tokens`` does)."""
    trace = run.get("trace") or {}
    if window_group(run) is None or "t0" not in trace:
        return None
    t0, t1 = trace["t0"], trace["t1"]
    window = run["config"]["sliding_window"]
    area = 0.0
    for r in run["stamps"]["records"]:
        if not r["chunks"] or not r["tokens"]:
            continue
        a, b = max(r["chunks"][0], t0), min(r["chunks"][-1], t1)
        if b <= a:
            continue
        life = max(r["chunks"][-1] - r["chunks"][0], 1e-9)
        mid = ((a + b) / 2 - r["chunks"][0]) / life
        area += (b - a) * min(r["prompt_tokens"] + mid * r["tokens"], window)
    return (area / max(t1 - t0, 1e-9)
            * flops_windowed.layers(run["config"], "sliding_attention"))


def full_rows_per_step(run: dict) -> float | None:
    rows = live_tokens(run)
    return None if rows is None else rows * flops_windowed.layers(
        run["config"], "full_attention")


def window_live_row_share(run: dict) -> float | None:
    """Rows the window layers' kernel calls saw over the rows they would
    have seen as full layers, over the window (the engine's counters)."""
    seen = delta(run, "window_rows")
    whole = delta(run, "window_rows_unwindowed")
    return 100.0 * seen / whole if seen is not None and whole else None


def window_attention_ms_per_decode_step(run: dict) -> float | None:
    if window_group(run) is None:
        return None
    return decode_scope_ms_per_step(run, "window_attention")


def window_attention_bytes(run: dict) -> float | None:
    rows = window_rows_per_step(run)
    return (None if rows is None
            else flops_windowed.window_attention_bytes(run["config"], rows))


def decode_step_bytes(run: dict) -> float | None:
    """Bytes a decode step of the traced stretch must move
    (``flops_windowed.decode_step_bytes``)."""
    full, seen = full_rows_per_step(run), window_rows_per_step(run)
    hit = moe_counters.decode_experts_hit_per_step(run)
    live = live_slots(run)
    if None in (full, seen, hit, live):
        return None
    return flops_windowed.decode_step_bytes(run["config"], full, seen, hit,
                                            live)


def window_share_of_decode_bytes(run: dict) -> float | None:
    kv, moved = window_attention_bytes(run), decode_step_bytes(run)
    return 100.0 * kv / moved if kv is not None and moved else None
