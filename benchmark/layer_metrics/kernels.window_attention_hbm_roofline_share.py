"""Least time the window layers' page kernel could take in a decode step
(the K and V rows its queries see, min(length, window) a live slot and
window layer, read once over the HBM peak:
``flops_windowed.window_attention_bytes`` over the benchmark's own stamps)
as a share of its measured time a step. The kernel copies whole pages: the
first page's rows behind the window and the last page's beyond the length
are moved and not counted, so the share reads a little low, never high.
None for a program without a ``window`` group."""
from benchmark import families, flops


def read(run):
    kernel_ms = families.read(run, "window_attention_ms_per_decode_step")
    moved = families.read(run, "window_attention_bytes")
    if not kernel_ms or moved is None:
        return None
    floor_s = moved / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / (kernel_ms * 1e-3)
