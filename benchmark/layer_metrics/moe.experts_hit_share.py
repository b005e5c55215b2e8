"""Share of the (layer, step, expert) triples in which the expert was
chosen by at least one live token, over the window: the engine's
``experts_hit`` / (E x ``layer_steps``), decode and prefill programs alike.
What the decode step's bandwidth floor scales with."""
from benchmark import moe_counters


def read(run):
    d = moe_counters.window(run)
    if not d or not d["layer_steps"]:
        return None
    return 100.0 * d["experts_hit"] / (
        run["config"]["num_experts"] * d["layer_steps"])
