"""Least time the block-rule page kernel could take in a forward (the
live pages' K and V copied once in every layer:
``flops_diffusion.page_bytes`` of the device's own page count, over the
HBM peak) as a share of ``paged_attention_blk``'s time a forward."""
from benchmark import diffusion_counters, flops, flops_diffusion


def read(run):
    ms = diffusion_counters.kernel_ms_per_forward(run, "paged_attention_blk")
    pages = diffusion_counters.live_pages_per_forward(run)
    ps = diffusion_counters.page_size(run)
    if not ms or pages is None or not ps:
        return None
    floor_s = (flops_diffusion.page_bytes(run["config"], pages, ps)
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (ms * 1e-3)
