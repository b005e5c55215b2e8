"""How late the load generator sent: sent - due, 95th percentile."""
from benchmark import facts


def read(run):
    late = [(r["sent"] - r["due"]) * 1e3 for r in facts.window_requests(run)
            if r["sent"] is not None and r["due"] is not None]
    return facts.p95(late, "loadgen.lateness_p95_ms")
