"""Share of the (layer, step, expert) triples in which the expert was
chosen by at least one live token, over the window: the engine's
``experts_hit`` / (E x ``layer_steps``), decode and prefill programs alike.
What the decode step's bandwidth floor scales with. Through the run's
family (``benchmark/families/<runner>.py experts_hit_share``)."""
from benchmark import families


def read(run):
    return families.read(run, "experts_hit_share")
