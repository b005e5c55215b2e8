"""Least time the linear cell's decode-step grouped expert matmuls could
take (the HELD experts some live token chose, gate, up and down streamed
once, over the HBM peak: ``flops_linear.expert_bytes`` over the engine's
count of the traced stretch) as a share of ``moe_gmm*``'s time a step."""
from benchmark import flops, flops_linear, linear_counters
from benchmark.layer_metrics import load

_kernel = load("kernels.linear_moe_gmm_ms_per_decode_step")


def read(run):
    kernel_ms = _kernel.read(run)
    hit = linear_counters.decode_experts_hit_per_step(run)
    if not kernel_ms or hit is None:
        return None
    floor_s = (flops_linear.expert_bytes(run["config"], hit)
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)
