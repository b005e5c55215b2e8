"""Share of the block tables' pages that the linear cell's latent
paged-attention kernel walks, over the window: the engine's ``live_pages``
/ ``table_pages`` on a pool of kind ``latent``, as the accepted
``kernels.mla_live_page_share`` reads them (its reader, under a name whose
list this cell may join)."""
from benchmark.layer_metrics import load

read = load("kernels.mla_live_page_share").read
