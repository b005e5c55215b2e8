"""Share of the block tables' pages that the latent paged-attention kernel
walks, over the window: the engine's ``live_pages`` / ``table_pages``
(``engine.stats()["kv"]``, counted as
``kernels.paged_attention_live_page_share`` reads them) on a pool of kind
``latent``. A program without the counters or without latent pages gives
None."""
from benchmark import latent_counters


def read(run):
    if not latent_counters.is_latent(run):
        return None
    a, b = run["stats"]["before"]["kv"], run["stats"]["after"]["kv"]
    table = b["table_pages"] - a["table_pages"]
    if table <= 0:
        return None
    return 100.0 * (b["live_pages"] - a["live_pages"]) / table
