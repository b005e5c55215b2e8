"""Least time the sessions cell's decode-step grouped expert matmuls could
take (the HELD experts some live token chose, gate, up and down streamed
once, over the HBM peak: ``flops_sessions.expert_bytes`` over the engine's
count of the traced stretch) as a share of ``moe_gmm``'s time a step. A
riding piece's rows go through the same calls, and the experts they alone
hit are streamed too: the share reads low then."""
from benchmark import flops, flops_sessions, sessions_counters


def read(run):
    kernel_ms = sessions_counters.scope_ms_per_step(run, "moe_gmm")
    hit = sessions_counters.decode_experts_hit_per_step(run)
    if not kernel_ms or hit is None:
        return None
    floor_s = (flops_sessions.expert_bytes(run["config"], hit)
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)
