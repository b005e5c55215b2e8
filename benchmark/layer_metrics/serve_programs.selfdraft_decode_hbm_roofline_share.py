"""Least time a draft-and-verify step could take on this chip as a share
of its measured device time: the bytes it must move
(``benchmark/flops_selfdraft.py``: every attention's, the dense layer's,
the routers', the shared experts' and the module's weights once, the head
once a pass, the held experts some live row chose once, every live latent
page's rows in every cached layer once a window) over the HBM peak."""
from benchmark import flops, selfdraft_counters


def read(run):
    step_ms = selfdraft_counters.step_ms(run)
    moved = selfdraft_counters.step_bytes(run)
    if not step_ms or moved is None:
        return None
    floor_s = moved / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / (step_ms * 1e-3)
