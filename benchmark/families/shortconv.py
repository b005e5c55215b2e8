"""Runs of ``runners/shortconv.py`` (gated short-convolution ``C`` layers,
four GQA layers of head_dim 64, 32 experts all held): bytes from
``flops_shortconv.py``, the decode program's by-scope seconds and the
``shortconv`` / ``kv`` / ``moe`` counters from ``shortconv_counters.py``. A
program that serves no ``C`` model (the parent of PR 55) gives None in the
readings that would otherwise read another model's counters."""
from benchmark import families, flops_shortconv, shortconv_counters

_moe, _serve = families.load("moe"), families.load("serve")
decode_step_ms = _serve.decode_step_ms
decode_step_bytes = shortconv_counters.decode_step_bytes
live_kv_tokens = shortconv_counters.live_kv_tokens


def _serves_it(read):
    return lambda run: (read(run) if shortconv_counters.is_shortconv(run)
                        else None)


experts_hit_share = _serves_it(_moe.experts_hit_share)
expert_load_imbalance = _serves_it(_moe.expert_load_imbalance)
paged_attention_live_page_share = _serves_it(
    _serve.paged_attention_live_page_share)


def moe_gmm_ms_per_decode_step(run):
    """``moe_gmm`` in the runner's by-scope seconds of the decode program:
    two kernels a layer, gate/up and down, over 32 experts at ~32 rows each
    plus a riding piece's rows."""
    if not shortconv_counters.is_shortconv(run):
        return None
    return shortconv_counters.decode_scope_ms_per_step(run, "moe_gmm")


def moe_gmm_step_s(run):
    kernel_ms = moe_gmm_ms_per_decode_step(run)
    return kernel_ms * 1e-3 if kernel_ms else None


def expert_bytes(run):
    """The HIT experts' three kernels; 256 slots x 4 choices hit nearly all
    14 x 32."""
    hit = shortconv_counters.decode_experts_hit_per_step(run)
    return (None if hit is None
            else flops_shortconv.expert_bytes(run["config"], hit))


def paged_attention_ms_per_decode_step(run):
    """``paged_attention`` in the runner's by-scope seconds of the decode
    program: the four attention layers', one query a slot, heads of 64 in
    PAIRS on the pool's 128 lanes."""
    if not shortconv_counters.is_shortconv(run):
        return None
    return shortconv_counters.decode_scope_ms_per_step(run, "paged_attention")


def kv_bytes_per_token(run):
    """Of the four attention layers, at the bytes a true head_dim 64 stores
    them: a layout that wasted lanes in HBM would read low."""
    return flops_shortconv.kv_bytes_per_token(run["config"])
