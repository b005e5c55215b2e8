"""Share of the traced stretch in which a slot held a request and the engine
had no program in flight (``starved_s`` / ``clock_s``, ``engine.stats()``
deltas): the program's own twin of ``device_idle.serve``."""
from benchmark import span_counters


def read(run):
    pair = run["trace_stats"]
    starved, clock = (span_counters.delta(pair, "starved_s"),
                      span_counters.delta(pair, "clock_s"))
    if starved is None or not clock:
        return None
    return 100.0 * starved / clock
