"""Share of the window's decode slot-steps (``decode_steps`` x slots) of a
slot that was live, or armed in flight, whose result was credited to nobody:
``overrun`` of the engine's slot-step ledger (window deltas): the steps
after a stop inside a dispatch, and every step of the dispatch chained
behind it."""
from benchmark import slot_step_counters


def read(run):
    return slot_step_counters.share(run, "overrun")
