"""Share of the window's decode slot-steps (``decode_steps`` x slots) whose
result a request was credited with: ``useful`` of the engine's slot-step
ledger (``engine.stats()["slot_steps"]``, window deltas)."""
from benchmark import slot_step_counters


def read(run):
    return slot_step_counters.share(run, "useful")
