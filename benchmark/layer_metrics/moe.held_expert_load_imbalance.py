"""Busiest HELD expert's choices over the mean held expert's, from the
window's ``choices`` (live tokens' choices per held expert, summed over the
expert layers): 1.0 is an even router. A selection bias that concentrates
the load reads high here and low in ``moe.held_experts_hit_share``."""
from benchmark import moe_counters


def read(run):
    d = moe_counters.window(run)
    if not d or not sum(d["choices"]):
        return None
    return max(d["choices"]) / (sum(d["choices"]) / len(d["choices"]))
