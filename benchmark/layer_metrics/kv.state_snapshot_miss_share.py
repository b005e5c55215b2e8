"""Share of the window's admissions that found their page chain hashed and
NO snapshot on it (prefilled from zero), of those that found a hashed chain
at all: ``snapshot_misses`` / (``snapshot_hits`` + ``snapshot_misses``) of
``stats()["kda"]``. Near 0 while the snapshot pool holds a snapshot a
session."""
from benchmark import sessions_counters


def read(run):
    return sessions_counters.snapshot_miss_share(run)
