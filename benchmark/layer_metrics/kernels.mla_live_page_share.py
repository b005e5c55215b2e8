"""Share of the block tables' pages that the latent paged-attention kernel
walks, over the window: the engine's ``live_pages`` / ``table_pages``
(``engine.stats()["kv"]``, counted as
``kernels.paged_attention_live_page_share`` reads them) on a pool of kind
``latent``. A program without the counters or without latent pages gives
None. Through the run's family (``benchmark/families/<runner>.py
mla_live_page_share``)."""
from benchmark import families


def read(run):
    return families.read(run, "mla_live_page_share")
