"""Seconds of backend compiles and of executables read from the persistent
compile cache (``compile_s + cache_read_s`` of the compile ledger) over the
programs first called before the window."""
from benchmark import startup_counters


def read(run):
    return startup_counters.ledger_seconds(run, ("compile_s", "cache_read_s"))
