"""Share of the window's decode slot-steps (``decode_steps`` x slots) of a
slot in which no request sat: ``empty`` of the engine's slot-step ledger
(window deltas)."""
from benchmark import slot_step_counters


def read(run):
    return slot_step_counters.share(run, "empty")
