"""Least time a denoise forward could take on this chip, as a share of
its measured device time: the share of the WHOLE step. The bytes it must
move (``benchmark/flops_diffusion.py forward_bytes``): attention, router
and head weights once, the experts some LIVE row chose once (the engine's
counter over the traced stretch) and the live K/V pages (the device's own
count of the pages the windows reach); over the HBM peak."""
from benchmark import diffusion_counters, flops


def read(run):
    ms, moved = (diffusion_counters.forward_device_ms(run),
                 diffusion_counters.forward_bytes(run))
    if not ms or moved is None:
        return None
    floor_s = moved / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms * 1e-3)
