"""Device time of the two copies of a snapshot pool (``kda_snapshot_take``:
a slot's rows to an entry at a prompt's last page boundary;
``kda_snapshot_arm``: an entry's rows to a slot at a hit), programs of their
own between two dispatches, in the traced stretch / decode steps on the
device."""
from benchmark import sessions_counters


def read(run):
    return sessions_counters.scope_ms_per_step(
        run, "kda_snapshot_take", "kda_snapshot_arm")
