"""Least time the short-conv cell's mixers could take in a decode step (12
x 33.6 MB of weights read once and every slot's two rows a layer read and
written, over the HBM peak) as a share of ``shortconv_mixer``'s decode-step
device time. The time holds the rows of a riding piece, the bytes do
not."""
from benchmark import flops, flops_shortconv, shortconv_counters
from benchmark.layer_metrics import load

_mixer = load("kernels.shortconv_mixer_ms_per_decode_step")


def read(run):
    mixer_ms = _mixer.read(run)
    if not mixer_ms:
        return None
    floor_s = (flops_shortconv.mixer_step_bytes(
        run["config"], shortconv_counters.slots(run))
        / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (mixer_ms * 1e-3)
