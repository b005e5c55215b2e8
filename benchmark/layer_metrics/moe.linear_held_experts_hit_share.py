"""Share of the (expert layer, step, held expert) triples in which the
expert was chosen by at least one live token, over the window: the engine's
``experts_hit`` / (``num_experts`` held here x ``layer_steps``). (The
accepted ``moe.held_experts_hit_share`` reads the hybrid file's key for the
experts held.)"""
from benchmark import linear_counters


def read(run):
    return linear_counters.held_experts_hit_share(run)
