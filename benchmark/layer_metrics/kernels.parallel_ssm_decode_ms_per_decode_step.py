"""Device time of the state-space branch's one-step state update
(``ssm_decode`` in the runner's by-scope seconds of the decode program:
the recurrence alone, the projections apart) in the traced stretch / decode
steps on the device (executions x steps per dispatch)."""
from benchmark import parallel_counters


def read(run):
    return parallel_counters.decode_scope_ms_per_step(run, "ssm_decode")
