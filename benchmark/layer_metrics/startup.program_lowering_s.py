"""Seconds of tracing and of jaxpr -> MLIR lowering (``trace_s + lower_s``
of the compile ledger) over the programs first called before the window:
what no compile cache skips."""
from benchmark import startup_counters


def read(run):
    return startup_counters.ledger_seconds(run, ("trace_s", "lower_s"))
