"""Least time a decode step of the latent-attention model could take on this
chip, as a share of the step's measured device time. The bytes it must move
(``benchmark/flops_latent.py``): attention, dense feed-forward, router,
shared-expert, head and map weights once, the experts some LIVE token chose
once (the engine's counter over the traced stretch) and every live latent
page's rows in every layer (the engine's live-page count); over the HBM
peak."""
from benchmark import flops, latent_counters
from benchmark.layer_metrics import load

_step = load("serve_programs.decode_step_device_ms")


def read(run):
    step_ms = _step.read(run)
    moved = latent_counters.decode_step_bytes(run)
    if not step_ms or moved is None:
        return None
    floor_s = moved[1] / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / (step_ms * 1e-3)
