"""Runs of ``runners/linear.py`` (delta-rule ``K`` layers, 3 latent ``*``
layers, held experts): bytes from ``flops_linear.py``, by-scope seconds and
the ``kda`` / ``kv`` / ``moe`` counters from ``linear_counters.py``."""
from benchmark import families, flops, flops_linear, linear_counters

decode_step_ms = families.load("serve").decode_step_ms
_scope_ms = families.load("hybrid").scope_ms_per_decode_step
mla_live_page_share = families.load("latent").mla_live_page_share
held_experts_hit_share = linear_counters.held_experts_hit_share


def decode_step_bytes(run):
    """Mixer, dense, router, shared-expert and head weights once, the held
    experts some LIVE token chose once, the live slots' recurrent state
    read and written (``stats()["kda"]["slot_steps"]`` a step) and the
    live latent rows."""
    moved = linear_counters.decode_step_bytes(run)
    return None if moved is None else moved[1]


def moe_gmm_ms_per_decode_step(run):
    """``moe_gmm*`` in the runner's by-scope seconds: two a layer over the
    held experts, 11 ``E`` layers."""
    return _scope_ms(run, "moe_gmm")


def moe_gmm_step_s(run):
    kernel_ms = moe_gmm_ms_per_decode_step(run)
    return kernel_ms * 1e-3 if kernel_ms else None


def expert_bytes(run):
    hit = linear_counters.decode_experts_hit_per_step(run)
    return (None if hit is None
            else flops_linear.expert_bytes(run["config"], hit))


def mla_attention_ms_per_decode_step(run):
    """3 ``*`` layers."""
    return _scope_ms(run, "mla_paged_attention")


def mla_attention_roofline_share(run):
    """In each of the ``*`` layers (3, not every layer)."""
    kernel_ms = mla_attention_ms_per_decode_step(run)
    rows = linear_counters.live_latent_tokens(run)
    if not kernel_ms or rows is None:
        return None
    cfg, peaks = run["config"], flops.peaks(run["device"]["kind"])
    floor_s = flops_linear.layers(cfg, "*") * max(
        flops_linear.mla_kernel_bytes(cfg, rows) / peaks["hbm_bytes_per_s"],
        flops_linear.mla_kernel_flops(cfg, rows) / peaks["bf16_flops_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)


def kda_decode_ms_per_decode_step(run):
    return _scope_ms(run, "kda_decode")


def kda_decode_hbm_roofline_share(run):
    """Every live slot's state read once and written once in every ``K``
    layer: a handful of operations an element of a 2 MB state."""
    kernel_ms = kda_decode_ms_per_decode_step(run)
    slots = linear_counters.live_slots_per_step(run)
    if not kernel_ms or slots is None:
        return None
    floor_s = (flops_linear.state_step_bytes(run["config"], slots)
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)
