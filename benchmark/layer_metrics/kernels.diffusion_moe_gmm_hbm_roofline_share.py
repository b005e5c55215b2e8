"""Least time the denoise forward's grouped expert matmuls could take
(the experts some live row chose, gate, up and down streamed once, over the
HBM peak: ``flops_diffusion.expert_bytes`` of the engine's count over the
traced stretch) as a share of their measured time a forward."""
from benchmark import diffusion_counters, flops, flops_diffusion


def read(run):
    ms = diffusion_counters.kernel_ms_per_forward(run, "moe_gmm_prefill")
    hit = diffusion_counters.experts_hit_per_forward(run)
    if not ms or hit is None:
        return None
    floor_s = (flops_diffusion.expert_bytes(run["config"], hit)
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (ms * 1e-3)
