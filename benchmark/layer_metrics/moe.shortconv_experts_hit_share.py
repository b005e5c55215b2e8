"""``moe.experts_hit_share`` in the short-conv cell: the share of the
(layer, step, expert) triples in which the expert was chosen by at least
one live token, over the window (32 experts, all held; 256 slots x 4
choices a step: near 100). An entry of its own because the accepted entry's
list of cells is pinned by its tests. The same reader, for a program that
serves the model."""
from benchmark import layer_metrics, shortconv_counters

_read = layer_metrics.load("moe.experts_hit_share").read


def read(run):
    return _read(run) if shortconv_counters.is_shortconv(run) else None
