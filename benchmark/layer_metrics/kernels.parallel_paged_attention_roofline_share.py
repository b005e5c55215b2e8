"""Least time the parallel cell's decode-step paged-attention kernel could
take (the live K and V rows of every layer read once a slot, over the HBM
peak: the pages the slots' lengths cover, the engine's own count over the
traced stretch) as a share of its measured time a step."""
from benchmark import flops, flops_parallel, parallel_counters
from benchmark.layer_metrics import load

_kernel = load("kernels.parallel_paged_attention_ms_per_decode_step")


def read(run):
    kernel_ms = _kernel.read(run)
    rows = parallel_counters.live_kv_tokens(run)
    if not kernel_ms or rows is None:
        return None
    floor_s = (flops_parallel.kv_bytes_per_token(run["config"]) * rows
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)
