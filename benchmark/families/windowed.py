"""Runs of ``runners/windowed.py`` (window layers beside full ones, every
layer sparse experts, two page pools): bytes from ``flops_windowed.py``,
rows from the benchmark's own stamps and the engine's ``window`` group
(``windowed_counters.py``), routing counters from ``moe_counters.py``, the
kernels' times from the runner's by-scope seconds of the decode program. The
``paged_attention`` names are the FULL layers' kernel and rows; the window
layers' run under ``window_attention`` and have readers of their own."""
from benchmark import families, flops_windowed, moe_counters, windowed_counters

_serve, _moe = families.load("serve"), families.load("moe")
decode_step_ms = _serve.decode_step_ms
paged_attention_live_page_share = _serve.paged_attention_live_page_share
decode_step_bytes = windowed_counters.decode_step_bytes
live_kv_tokens = windowed_counters.live_tokens
experts_hit_share = _moe.experts_hit_share
expert_load_imbalance = _moe.expert_load_imbalance
window_attention_ms_per_decode_step = (
    windowed_counters.window_attention_ms_per_decode_step)
window_attention_bytes = windowed_counters.window_attention_bytes
window_live_row_share = windowed_counters.window_live_row_share
window_share_of_decode_bytes = windowed_counters.window_share_of_decode_bytes


def paged_attention_ms_per_decode_step(run):
    """``paged_attention`` in the runner's by-scope seconds of the decode
    program: the full layers' calls, one query a slot."""
    return windowed_counters.decode_scope_ms_per_step(run, "paged_attention")


def kv_bytes_per_token(run):
    """Of the full layers: what ``live_kv_tokens`` rows cost their kernel."""
    return flops_windowed.kv_bytes_per_token(run["config"], "full_attention")


def moe_gmm_ms_per_decode_step(run):
    return windowed_counters.decode_scope_ms_per_step(run, "moe_gmm")


def moe_gmm_step_s(run):
    kernel_ms = moe_gmm_ms_per_decode_step(run)
    return kernel_ms * 1e-3 if kernel_ms else None


def expert_bytes(run):
    hit = moe_counters.decode_experts_hit_per_step(run)
    return (None if hit is None
            else flops_windowed.expert_bytes(run["config"], hit))
