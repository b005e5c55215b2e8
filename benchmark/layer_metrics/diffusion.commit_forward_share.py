"""Share of the live slots' forwards that were COMMITS (a finished block
forwarded once more to store its K/V, fixing no token), over the window:
``commit_slot_forwards`` / ``slot_forwards``. 1 in ``denoising_steps + 1``
under the schedule (20 % at 4) before PR 47; since then a block's commit
rides the next block's first forward and no forward is a commit alone: the
share reads 0 (ledger, PR 58), and anything above it is a forward lost."""
from benchmark import diffusion_counters


def read(run):
    share = diffusion_counters.ratio(run, "commit_slot_forwards",
                                     "slot_forwards")
    return None if share is None else 100.0 * share
