"""K/V rows the window layers' kernel calls saw over the rows they would
have seen as full layers, over the window: the engine's ``window_rows`` /
``window_rows_unwindowed`` (``engine.stats()["window"]``, counted once a
decode dispatch: min(length, window) against length, summed over live slots
and window layers). What the window saves the kernel, and the ring the pool.
None for a program without the group."""
from benchmark import families


def read(run):
    return families.read(run, "window_live_row_share")
