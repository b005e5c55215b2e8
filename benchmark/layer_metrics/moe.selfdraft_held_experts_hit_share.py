"""Share of the (expert layer, step, held expert) triples in which the
expert was chosen by at least one live row, over the window, the module's
expert layer among them: the accepted ``moe.held_experts_hit_share`` (the
engine's ``experts_hit`` / (experts held x ``layer_steps``)) for an engine
that drafts."""
from benchmark import selfdraft_counters
from benchmark.layer_metrics import load

_share = load("moe.held_experts_hit_share")


def read(run):
    return _share.read(run) if selfdraft_counters.is_selfdraft(run) else None
