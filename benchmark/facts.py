"""Reductions from a run's raw stamps to the samples the metric readers
take their numbers from. A reader (``end_to_end/<metric>.py``,
``layer_metrics/<metric>.py``) is ``read(run) -> float | None`` over the
dict a runner returns; what several readers share lives here."""

from __future__ import annotations

import sys

from benchmark.stats import percentile


def failed(rec: dict) -> bool:
    return (rec["error"] is not None or rec["status"] != 200
            or rec["done"] is None or not rec["chunks"])


def window_requests(run: dict) -> list:
    """The requests a serving run is judged on. Open loop: those DUE inside
    the window. Closed loop: those sent inside it that also ended inside it
    (the callers stop at the window's close; what is in flight then is not
    in the sample)."""
    w0, w1 = run["window"]
    recs = run["stamps"]["records"]
    if run["stamps"]["kind"] == "serve-open":
        return [r for r in recs if w0 <= r["due"] < w1]
    return [r for r in recs if r["sent"] is not None and r["sent"] >= w0
            and not r.get("in_flight") and (failed(r) or r["done"] <= w1)]


def failed_requests(run: dict) -> list:
    return [r for r in window_requests(run) if failed(r)]


def _worst_ms(run: dict, rec: dict) -> float:
    """What a failed request counts as: the time from when it was due (or
    sent) to the end of the drain."""
    end = run["window"][1] + float(run["traffic"].get("drain_s", 20.0))
    return (end - (rec["due"] or rec["sent"] or run["window"][0])) * 1e3


def ttft_ms(run: dict) -> list:
    """First streamed chunk minus the time the request was DUE (sent, in a
    closed loop), failed requests at their worst."""
    return [_worst_ms(run, r) if failed(r)
            else (r["chunks"][0] - (r["due"] or r["sent"])) * 1e3
            for r in window_requests(run)]


def tpot_ms(run: dict) -> list:
    """(last chunk - first chunk) / (output tokens - 1) of every request
    with at least two tokens, failed requests at their worst."""
    out = []
    for r in window_requests(run):
        if failed(r):
            out.append(_worst_ms(run, r))
        elif r["tokens"] and r["tokens"] > 1 and len(r["chunks"]) > 1:
            out.append((r["chunks"][-1] - r["chunks"][0]) * 1e3
                       / (r["tokens"] - 1))
    return out


def p95(values: list, name: str) -> float | None:
    if not values:
        return None
    print(f"[bench] {name}: p95 over {len(values)} samples, "
          f"p50 {percentile(values, 50):.3f}", file=sys.stderr)
    return percentile(values, 95)


def tokens_in_window(run: dict) -> int:
    """Output tokens whose chunk reached the client inside the window. The
    i-th chunk of a request carries the i-th token batch the engine handed
    to its stream (sizes from the benchmark's hook on ``on_token``)."""
    w0, w1 = run["window"]
    total = 0
    for r in run["stamps"]["records"]:
        for t, n in zip(r["chunks"], r["batch_sizes"]):
            if w0 <= t < w1:
                total += n
    return total


def live_kv_tokens(run: dict, t0: float, t1: float) -> float:
    """Time-average over [t0, t1] of the tokens whose keys and values are
    live in the batch: for every request between its first and last chunk,
    its prompt plus the tokens streamed so far (linear in between)."""
    area = 0.0
    for r in run["stamps"]["records"]:
        if not r["chunks"] or not r["tokens"]:
            continue
        a, b = max(r["chunks"][0], t0), min(r["chunks"][-1], t1)
        if b <= a:
            continue
        life = max(r["chunks"][-1] - r["chunks"][0], 1e-9)
        mid = ((a + b) / 2 - r["chunks"][0]) / life
        area += (b - a) * (r["prompt_tokens"] + mid * r["tokens"])
    return area / max(t1 - t0, 1e-9)


def stopped_early(run: dict) -> list:
    return [r for r in window_requests(run)
            if not failed(r) and r["tokens"] is not None
            and r["tokens"] != r["max_tokens"]]


def serve_correct(run: dict) -> bool:
    """The reference check passed, and no request returned another token
    count than asked for without the engine's own stop reason."""
    unexplained = [r for r in stopped_early(run)
                   if r["engine_finish_reason"] != "stop"]
    return bool(run["check"]["ok"]) and not unexplained


def train_rate(run: dict) -> tuple[float, float]:
    """(tokens, seconds) of the fenced blocks of a training window."""
    steps = sum(n for _, _, n in run["blocks"])
    seconds = sum(b - a for a, b, _ in run["blocks"])
    return steps * run["tokens_per_step"], seconds


def traced_counter(run: dict, key: str) -> float:
    s = run["trace_stats"]
    return s["after"][key] - s["before"][key]
