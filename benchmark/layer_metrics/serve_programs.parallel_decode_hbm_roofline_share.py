"""Least time a decode step of the parallel cell could take on this chip,
as a share of the step's measured device time. The bytes it must move
(``benchmark/flops_parallel.py``): every layer's weights and the head once,
the live slots' recurrent state read and written in EVERY layer
(``stats()["ssm"]["slot_steps"]`` a step) and the live keys and values of
every layer (the pages the slots' lengths cover); over the HBM peak. The
step's time holds the rows of prompts that ride it, its bytes do not."""
from benchmark import flops, parallel_counters
from benchmark.layer_metrics import load

_step = load("serve_programs.decode_step_device_ms")


def read(run):
    step_ms = _step.read(run)
    moved = parallel_counters.decode_step_bytes(run)
    if not step_ms or moved is None:
        return None
    floor_s = moved / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / (step_ms * 1e-3)
