"""Seconds the engine thread worked before the window, its programs' first
calls apart (``startup_counters.engine_work_seconds``): the reference
check's and the warm-up's requests, a document cell's loading."""
from benchmark import startup_counters


def read(run):
    return startup_counters.engine_work_seconds(run)
