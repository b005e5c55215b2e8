"""Share of the (expert layer, step, held expert) triples in which the
expert was chosen by at least one live token, over the window: the
engine's ``experts_hit`` / (experts held x ``layer_steps``). Which key of
the configuration counts the experts held is the run's family's
(``benchmark/families/<runner>.py held_experts_hit_share``)."""
from benchmark import families


def read(run):
    return families.read(run, "held_experts_hit_share")
