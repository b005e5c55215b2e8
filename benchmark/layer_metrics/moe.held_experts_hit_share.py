"""Share of the (expert layer, step, held expert) triples in which the
expert was chosen by at least one live token, over the window: the
engine's ``experts_hit`` / (experts held x ``layer_steps``)."""
from benchmark import moe_counters


def read(run):
    d = moe_counters.window(run)
    if not d or not d["layer_steps"]:
        return None
    return 100.0 * d["experts_hit"] / (
        run["config"]["n_routed_experts"] * d["layer_steps"])
