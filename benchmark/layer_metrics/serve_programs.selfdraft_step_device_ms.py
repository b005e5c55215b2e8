"""Device time of one draft-and-verify step (main window, acceptance,
module, both head passes, all slots): the decode program's executions in
the traced stretch / (executions x steps a dispatch), for an engine that
drafts (``engine.stats()["mtp"]``)."""
from benchmark import selfdraft_counters


def read(run):
    return selfdraft_counters.step_ms(run)
