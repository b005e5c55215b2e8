"""Least time the linear cell's decode-step latent paged-attention kernel
could take as a share of its measured time: in each of the ``*`` layers
(3, not every layer) the greater of the byte floor (each live latent page
read once a slot, as stored, over the HBM peak) and the FLOP floor
(absorbed scores and values over the bf16 peak). Live pages are the
engine's own count over the traced stretch."""
from benchmark import flops, flops_linear, linear_counters
from benchmark.layer_metrics import load

_kernel = load("kernels.linear_mla_attention_ms_per_decode_step")


def read(run):
    kernel_ms = _kernel.read(run)
    rows = linear_counters.live_latent_tokens(run)
    if not kernel_ms or rows is None:
        return None
    cfg, peaks = run["config"], flops.peaks(run["device"]["kind"])
    floor_s = flops_linear.layers(cfg, "*") * max(
        flops_linear.mla_kernel_bytes(cfg, rows) / peaks["hbm_bytes_per_s"],
        flops_linear.mla_kernel_flops(cfg, rows) / peaks["bf16_flops_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)
