"""Share of the window rows the live slots' forwards computed that were
still masks, over the window: ``masked_rows`` / (``block_length`` x
``slot_forwards``). 50 % under the schedule at 4 and 4 (4 + 3 + 2 + 1 + 0
of 20): the rest are rows already fixed, forwarded again for their block's
sake."""
from benchmark import diffusion_counters


def read(run):
    bd = diffusion_counters.block_length(run)
    if not bd:
        return None
    share = diffusion_counters.ratio(run, "masked_rows", "slot_forwards",
                                     scale=bd)
    return None if share is None else 100.0 * share
