"""Least time the step's grouped expert matmuls could take (the held
experts some live row of the window chose: gate, up and down streamed once,
over the HBM peak) as a share of the grouped-matmul kernels' measured time
a step in the decode program (``moe_gmm`` and ``moe_gmm_prefill``:
whichever the window takes)."""
from benchmark import flops, flops_selfdraft, selfdraft_counters


def read(run):
    gmm_ms = selfdraft_counters.step_scope_ms(
        run, "moe_gmm", "moe_gmm_prefill")
    hit = selfdraft_counters.experts_hit_per_step(run)
    if not gmm_ms or hit is None:
        return None
    floor_s = (flops_selfdraft.expert_bytes(run["config"], hit)
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (gmm_ms * 1e-3)
