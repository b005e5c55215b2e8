"""How many programs were first called before the window: one ledger entry a
``llmctl.startup.program`` span and one a compile under none
(``(unscoped)``)."""
from benchmark import startup_counters


def read(run):
    entries = startup_counters.programs(run)
    return None if entries is None else len(entries)
