"""Busiest expert's choices over the mean expert's, from the window's
``choices`` (live tokens' choices per expert, summed over the layers): 1.0
is an even router; the grouped matmul's longest group, and with ep > 1 the
busiest chip, scale with it."""
from benchmark import moe_counters


def read(run):
    d = moe_counters.window(run)
    if not d or not sum(d["choices"]):
        return None
    return max(d["choices"]) / (sum(d["choices"]) / len(d["choices"]))
