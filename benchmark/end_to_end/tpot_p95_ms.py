"""95th percentile over the window's requests of (last chunk - first chunk)
/ (output tokens - 1)."""
from benchmark import facts


def read(run):
    return facts.p95(facts.tpot_ms(run), "tpot_p95_ms")
