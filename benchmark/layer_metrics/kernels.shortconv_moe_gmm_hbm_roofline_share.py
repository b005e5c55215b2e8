"""Least time the short-conv cell's decode-step grouped expert matmuls
could take (the HIT experts' three kernels streamed once, over the HBM
peak; 256 slots x 4 choices hit nearly all 14 x 32) as a share of
``moe_gmm``'s decode-step device time."""
from benchmark import flops, flops_shortconv, shortconv_counters
from benchmark.layer_metrics import load

_kernel = load("kernels.shortconv_moe_gmm_ms_per_decode_step")


def read(run):
    kernel_ms = _kernel.read(run)
    hit = shortconv_counters.decode_experts_hit_per_step(run)
    if not kernel_ms or hit is None:
        return None
    floor_s = (flops_shortconv.expert_bytes(run["config"], hit)
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)
