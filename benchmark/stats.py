"""Arithmetic shared by the metric readers: percentiles, spreads, interval
unions. No JAX, no numpy: the load generator's child imports this too."""

from __future__ import annotations

import math
import statistics
from typing import Iterable, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    order statistics (numpy's default). ``inf`` entries (failed requests
    counted as the worst latency) sort last and are returned as such."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    pos = (len(s) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or math.isinf(s[hi]):
        return s[hi] if pos > lo else s[lo]
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median — the contract's spread (``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def merge_intervals(intervals: Iterable[tuple[float, float]]
                    ) -> list[tuple[float, float]]:
    """Sorted, non-overlapping union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_length(intervals: Iterable[tuple[float, float]]) -> float:
    return sum(b - a for a, b in merge_intervals(intervals))


def subtract_length(intervals: Iterable[tuple[float, float]],
                    others: Iterable[tuple[float, float]]) -> float:
    """Length of the union of *intervals* not covered by any of *others*."""
    a, b = merge_intervals(intervals), merge_intervals(others)
    covered, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return sum(e - s for s, e in a) - covered
