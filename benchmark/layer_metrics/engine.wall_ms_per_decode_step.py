"""The engine's own wall clock a decode step over the WHOLE window:
``clock_s`` delta / ``decode_steps`` delta of ``engine.stats()``, the window
in which the tokens per second are counted (the device's step is a traced
stretch's)."""
from benchmark import slot_step_counters


def read(run):
    return slot_step_counters.wall_ms_per_decode_step(run)
