"""Device time of the state-space layers' one-step state update
(``ssm_decode`` in the runner's by-scope seconds) in the traced stretch /
decode steps on the device (executions x steps per dispatch). Through the
run's family (``benchmark/families/<runner>.py
ssm_decode_ms_per_decode_step``)."""
from benchmark import families


def read(run):
    return families.read(run, "ssm_decode_ms_per_decode_step")
