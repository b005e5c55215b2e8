"""Share of the window's decode slot-steps (``decode_steps`` x slots) of a
slot whose seated request was not live yet: ``prompt_wait`` of the engine's
slot-step ledger (window deltas): its prompt's pieces riding, or waiting for
room among them or for a prefill program."""
from benchmark import slot_step_counters


def read(run):
    return slot_step_counters.share(run, "prompt_wait")
