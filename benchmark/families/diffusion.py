"""Runs of ``runners/diffusion.py`` (generation by diffusion over blocks: a
"step" is a denoise forward): bytes from ``flops_diffusion.py``, the decode
program's by-kernel seconds and the counters from
``diffusion_counters.py``; the window's routing shares as ``moe.py`` reads
them."""
from benchmark import diffusion_counters, families, flops_diffusion

_moe = families.load("moe")
experts_hit_share = _moe.experts_hit_share
expert_load_imbalance = _moe.expert_load_imbalance


def moe_gmm_step_s(run):
    """A window of ``block_length`` rows a slot takes the kernel's windowed
    form, ``moe_gmm_prefill``: the decode program's alone, a forward."""
    ms = diffusion_counters.kernel_ms_per_forward(run, "moe_gmm_prefill")
    return ms * 1e-3 if ms else None


def expert_bytes(run):
    """The experts some live row chose, a forward."""
    hit = diffusion_counters.experts_hit_per_forward(run)
    return (None if hit is None
            else flops_diffusion.expert_bytes(run["config"], hit))
