"""Least time the decode step's grouped expert matmuls could take (the hit
experts' gate, up and down kernels streamed once, over the HBM peak) as a
share of the kernels' measured time per step. Bytes, not FLOPs, are the
measure: at decode a hit expert multiplies ~4 rows (32 tokens x 8 choices
over 64 experts) by 6.3 M parameters, 0.05 GFLOP against 12.6 MB: 4
operations a byte where the chip's ridge is 240. Only experts the engine's
counter says were hit are counted, so the share cannot read high."""
from benchmark import flops, flops_moe, moe_counters
from benchmark.layer_metrics import load

_kernel = load("kernels.moe_gmm_ms_per_decode_step")


def read(run):
    kernel_ms = _kernel.read(run)
    hit = moe_counters.decode_experts_hit_per_step(run)
    if not kernel_ms or hit is None:
        return None
    floor_s = (flops_moe.expert_bytes(run["config"], hit)
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)
