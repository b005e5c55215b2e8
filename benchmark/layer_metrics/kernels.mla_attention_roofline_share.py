"""Least time the decode step's latent paged-attention kernel could take as
a share of its measured time: the greater of the byte floor (each live
latent page read once a slot a layer, as stored, over the HBM peak) and the
FLOP floor (absorbed scores and values over the bf16 peak),
``benchmark/flops_latent.py``. Live pages are the engine's own count over
the traced stretch (a page a slot's length covers), so the bytes cannot be
counted high: a page's padding rows past the length are in them, as they
are in the copy."""
from benchmark import flops, flops_latent, latent_counters
from benchmark.layer_metrics import load

_kernel = load("kernels.mla_attention_ms_per_decode_step")


def read(run):
    kernel_ms = _kernel.read(run)
    pages = latent_counters.live_pages_per_step(run)
    ps = latent_counters.page_size(run)
    if not kernel_ms or pages is None or ps is None:
        return None
    cfg, peaks = run["config"], flops.peaks(run["device"]["kind"])
    layers = cfg["num_hidden_layers"]
    floor_s = layers * max(
        flops_latent.kernel_bytes(cfg, pages, ps) / peaks["hbm_bytes_per_s"],
        flops_latent.kernel_flops(cfg, pages * ps)
        / peaks["bf16_flops_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)
