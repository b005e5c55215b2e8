"""Least time a decode step of the linear-attention model could take on this
chip, as a share of the step's measured device time. The bytes it must move
(``benchmark/flops_linear.py``): mixer, dense, router, shared-expert and
head weights once, the held experts some LIVE token chose once (the engine's
counter over the traced stretch), the live slots' recurrent state read and
written (``stats()["kda"]["slot_steps"]`` a step) and the live latent rows;
over the HBM peak."""
from benchmark import flops, linear_counters
from benchmark.layer_metrics import load

_step = load("serve_programs.decode_step_device_ms")


def read(run):
    step_ms = _step.read(run)
    moved = linear_counters.decode_step_bytes(run)
    if not step_ms or moved is None:
        return None
    floor_s = moved[1] / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / (step_ms * 1e-3)
