"""Least time a decode step of the short-conv cell could take on this chip,
as a share of the step's measured device time: the share of the WHOLE step.
The bytes it must move (``benchmark/flops_shortconv.py``): mixer, attention,
dense-MLP, router and head weights once, the experts some LIVE token chose
once (the engine's counter over the traced stretch), the conv windows of
every slot read and written in every ``conv`` layer, and the live keys and
values of the four attention layers (the pages the slots' lengths cover, at
the bytes a true head_dim 64 stores); over the HBM peak. The step's time
holds the rows of prompts that ride it, its bytes do not."""
from benchmark import flops, shortconv_counters
from benchmark.layer_metrics import load

_step = load("serve_programs.decode_step_device_ms")


def read(run):
    step_ms = _step.read(run)
    moved = shortconv_counters.decode_step_bytes(run)
    if not step_ms or moved is None:
        return None
    floor_s = moved / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / (step_ms * 1e-3)
