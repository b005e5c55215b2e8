"""95th percentile of the time a request waited in the queue for a slot,
over the requests admitted inside the window: the scheduler's fixed-bound
histogram (``queue_wait_ms`` of ``engine.stats()``), after - before, by
linear interpolation inside the bucket the percentile falls in. The last
bucket has no upper bound: a percentile in it reads as its lower bound."""
import sys


def read(run):
    a = run["stats"]["before"].get("queue_wait_ms")
    b = run["stats"]["after"].get("queue_wait_ms")
    if not a or not b:
        return None
    counts = [y - x for x, y in zip(a["counts"], b["counts"])]
    n = sum(counts)
    if n <= 0:
        return None
    print(f"[bench] scheduler.queue_wait_p95_ms: p95 over {n} admissions, "
          f"mean {(b['sum'] - a['sum']) / n:.3f}", file=sys.stderr)
    rank, seen, lower = 0.95 * n, 0, 0.0
    for upper, c in zip(b["le"], counts):
        if c and seen + c >= rank:
            if not isinstance(upper, (int, float)):
                return lower
            return lower + (upper - lower) * (rank - seen) / c
        seen += c
        if isinstance(upper, (int, float)):
            lower = float(upper)
    return lower
