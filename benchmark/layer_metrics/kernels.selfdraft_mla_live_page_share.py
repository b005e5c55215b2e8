"""Share of the block tables' pages that the window kernel walks, over the
window: the accepted ``kernels.mla_live_page_share`` (the engine's
``live_pages`` / ``table_pages`` on a latent pool) for an engine that
drafts."""
from benchmark import selfdraft_counters
from benchmark.layer_metrics import load

_share = load("kernels.mla_live_page_share")


def read(run):
    return _share.read(run) if selfdraft_counters.is_selfdraft(run) else None
