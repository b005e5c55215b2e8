"""Least time the step's window kernel could take as a share of its
measured time: over the cached layers (the module's too) the greater of the
byte floor (each live latent page read once a slot a layer, ONCE for the
window's two rows, as stored, over the HBM peak) and the FLOP floor (two
rows' absorbed scores and values over the bf16 peak),
``benchmark/flops_selfdraft.py``. Live pages are the engine's own count
over the traced stretch."""
from benchmark import flops, flops_selfdraft, selfdraft_counters


def read(run):
    kernel_ms = selfdraft_counters.step_scope_ms(
        run, "mla_paged_attention_mq")
    pages = selfdraft_counters.live_pages_per_step(run)
    ps = selfdraft_counters.page_size(run)
    if not kernel_ms or pages is None or ps is None:
        return None
    cfg, peaks = run["config"], flops.peaks(run["device"]["kind"])
    floor_s = flops_selfdraft.cached_layers(cfg) * max(
        flops_selfdraft.kernel_bytes(cfg, pages, ps)
        / peaks["hbm_bytes_per_s"],
        flops_selfdraft.kernel_flops(cfg, pages * ps)
        / peaks["bf16_flops_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)
