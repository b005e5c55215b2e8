"""Least time the decode step's grouped expert matmuls could take (the hit
HELD experts' up and down kernels streamed once, over the HBM peak) as a
share of ``moe_gmm*``'s decode-step device time (the prefill's kernels are
``moe_gmm_prefill*`` and are not counted)."""
from benchmark import flops, flops_hybrid, hybrid_counters


def read(run):
    s = hybrid_counters.scope_seconds(run, "moe_gmm")
    steps = hybrid_counters.traced_decode_steps(run)
    hit = hybrid_counters.decode_experts_hit_per_step(run)
    if not s or not steps or hit is None:
        return None
    floor_s = (flops_hybrid.expert_bytes(run["config"], hit)
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (s / steps)
