"""Share of a decode step's byte floor that is live K and V rows of the
passes x layers planes (over weights once a pass + the head + those rows +
the rows written), traced stretch: how much of the step the loop's cache
is. None for a program without a ``loop`` group."""
from benchmark import looped_counters


def read(run):
    return looped_counters.loop_share_of_decode_bytes(run)
