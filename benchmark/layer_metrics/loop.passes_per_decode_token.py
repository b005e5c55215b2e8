"""Passes of the stack a decode step's token ran: the window's delta of the
engine's ``loop.decode_token_passes`` over that of ``loop.decode_tokens``.
``total_ut_steps`` (4.0) at the published exit threshold 1, which is all
that loads; what early exit would move. None for a program without a
``loop`` group."""
from benchmark import looped_counters


def read(run):
    return looped_counters.passes_per_decode_token(run)
