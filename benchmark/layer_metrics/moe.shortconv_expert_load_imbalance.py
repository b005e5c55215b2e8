"""``moe.expert_load_imbalance`` in the short-conv cell: the busiest
expert's choices over the mean expert's, from the window's ``choices``
(1.0 is an even router; the grouped matmul's longest group scales with
it). An entry of its own because the accepted entry's list of cells is
pinned by its tests. The same reader, for a program that serves the
model."""
from benchmark import layer_metrics, shortconv_counters

_read = layer_metrics.load("moe.expert_load_imbalance").read


def read(run):
    return _read(run) if shortconv_counters.is_shortconv(run) else None
