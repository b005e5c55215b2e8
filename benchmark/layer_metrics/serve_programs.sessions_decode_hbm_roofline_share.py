"""Least time a decode step of the sessions cell's model could take on this
chip, as a share of the step's measured device time. The bytes it must move
(``benchmark/flops_sessions.py``): mixer, router, shared-expert and head
weights once, the held experts some LIVE token chose once (the engine's
counter over the traced stretch), the live slots' recurrent state read and
written (``stats()["kda"]["slot_steps"]`` a step) and the live K/V rows of
the one softmax layer; over the HBM peak. A riding piece's rows are in the
step's time and not in the floor, as in the accepted riding cells."""
from benchmark import flops, sessions_counters
from benchmark.layer_metrics import load

_step = load("serve_programs.decode_step_device_ms")


def read(run):
    step_ms = _step.read(run)
    moved = sessions_counters.decode_step_bytes(run)
    if not step_ms or moved is None:
        return None
    floor_s = moved / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / (step_ms * 1e-3)
