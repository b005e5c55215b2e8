"""Least time the short-conv cell's decode-step paged-attention kernel
could take (the live K and V rows of the four attention layers read once a
slot, at the bytes a true head_dim 64 stores them: a layout that wasted
lanes in HBM would read low; over the HBM peak) as a share of its measured
time a step. The small-head route: the kernel's dots do twice the work of
heads of 128 at the same bytes."""
from benchmark import flops, flops_shortconv, shortconv_counters
from benchmark.layer_metrics import load

_kernel = load("kernels.shortconv_paged_attention_ms_per_decode_step")


def read(run):
    kernel_ms = _kernel.read(run)
    rows = shortconv_counters.live_kv_tokens(run)
    if not kernel_ms or rows is None:
        return None
    floor_s = (flops_shortconv.kv_bytes_per_token(run["config"]) * rows
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)
