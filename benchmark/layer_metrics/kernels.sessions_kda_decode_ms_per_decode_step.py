"""``kernels.kda_decode_ms_per_decode_step`` in the sessions cell: device
time of the 3 ``K`` layers' one-step state update at 64 heads
(``kda_decode`` in the runner's by-scope seconds) a decode step. An entry
of its own because an accepted entry's list of cells is not a later PR's to
lengthen. The same reader."""
from benchmark import layer_metrics

read = layer_metrics.load("kernels.kda_decode_ms_per_decode_step").read
