"""Least time the decode step's grouped expert matmuls could take (the hit
experts' gate, up and down kernels streamed once, over the HBM peak) as a
share of the ``moe_gmm`` kernels' measured time a step, for a model whose
expert layers are fewer than its decoder layers (the accepted
``kernels.moe_gmm_hbm_roofline_share`` divides the counter by
``num_hidden_layers``, one too many here, and reads the ten longest
operations where this reads every one by name)."""
from benchmark import flops, flops_latent, latent_counters


def read(run):
    s = latent_counters.scope_seconds(run, "moe_gmm")
    steps = latent_counters.traced_decode_steps(run)
    hit = latent_counters.decode_experts_hit_per_step(run)
    if not s or not steps or hit is None:
        return None
    floor_s = (flops_latent.expert_bytes(run["config"], hit)
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (s / steps)
