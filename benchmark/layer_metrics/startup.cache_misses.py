"""How many of the programs first called before the window the backend
COMPILED where it could have read them from the persistent cache
(``cache_hit`` false): 0 on a warm machine."""
from benchmark import startup_counters


def read(run):
    entries = startup_counters.programs(run)
    if entries is None:
        return None
    return sum(p["cache_hit"] is False for p in entries)
