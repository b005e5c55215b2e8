"""``kernels.paged_attention_live_page_share`` in the short-conv cell: the
share of the block tables' pages (8 a slot of 256 tokens) that the kernel
walks, over the window. An entry of its own because the accepted entry's
list of cells is pinned by its test. The same reader, for a program that
serves the model."""
from benchmark import layer_metrics, shortconv_counters

_read = layer_metrics.load("kernels.paged_attention_live_page_share").read


def read(run):
    return _read(run) if shortconv_counters.is_shortconv(run) else None
