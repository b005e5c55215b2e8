"""95th percentile of first streamed chunk minus the time the request was
due, over the requests due inside the window. Most of it is the wait behind
the decode dispatch in flight, which the scheduler decides. Per layer and
not end to end: at ~150 requests a window it repeats too loosely to carry a
bound (PERF.md, section 2)."""
from benchmark import facts


def read(run):
    return facts.p95(facts.ttft_ms(run), "engine.ttft_p95_ms")
