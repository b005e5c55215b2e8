"""Pages of the pool in use (held by a slot or kept for a prefix hit) over
its pages, the mean of the traced stretch's two ends: how near the batch
stands to the pool's limit, where a token costs passes x layers planes.
None for a program without a ``loop`` group."""
from benchmark import looped_counters


def read(run):
    return looped_counters.pool_live_page_share(run)
