"""Device time of the state-space layers' one-step state update
(``ssm_decode*`` in the runner's by-scope seconds) in the traced stretch /
decode steps on the device (executions x steps per dispatch)."""
from benchmark import hybrid_counters


def read(run):
    s = hybrid_counters.scope_seconds(run, "ssm_decode")
    steps = hybrid_counters.traced_decode_steps(run)
    if not s or not steps:
        return None
    return 1e3 * s / steps
