"""Device time of the ``K`` layers' one-step state update (``kda_decode`` in
the runner's by-scope seconds) in the traced stretch / decode steps on the
device (executions x steps per dispatch)."""
from benchmark import linear_counters


def read(run):
    s = linear_counters.scope_seconds(run, "kda_decode")
    steps = linear_counters.traced_decode_steps(run)
    if not s or not steps:
        return None
    return 1e3 * s / steps
