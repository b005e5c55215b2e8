"""Runs of ``runners/hybrid.py`` (state-space, attention and expert layers
by a table): bytes from ``flops_hybrid.py``, by-scope seconds and the
``ssm`` / ``moe`` counters from ``hybrid_counters.py``."""
from benchmark import facts, families, flops, flops_hybrid, hybrid_counters
from benchmark import moe_counters

decode_step_ms = families.load("serve").decode_step_ms


def decode_step_bytes(run):
    """Mixer, attention, router, shared-expert and head weights once, the
    held experts some LIVE token chose once, the live slots' recurrent
    state read and written (``stats()["ssm"]["slot_steps"]`` a step) and
    the live keys and values."""
    hit = hybrid_counters.decode_experts_hit_per_step(run)
    slots = hybrid_counters.live_slots_per_step(run)
    if hit is None or slots is None:
        return None
    live = facts.live_kv_tokens(run, run["trace"]["t0"], run["trace"]["t1"])
    return flops_hybrid.decode_step_bytes(run["config"], live, hit, slots)


def scope_ms_per_decode_step(run, scope):
    """Device ms a decode step of the traced stretch spent under ``scope``
    in the runner's by-scope seconds (``run["trace"]["scope_s"]``; exact
    scope: ``moe_gmm`` does not count ``moe_gmm_prefill``), not the ten-line
    ``device_ops``. The latent and linear runners keep the same table."""
    s = hybrid_counters.scope_seconds(run, scope)
    steps = hybrid_counters.traced_decode_steps(run)
    if not s or not steps:
        return None
    return 1e3 * s / steps


def moe_gmm_ms_per_decode_step(run):
    """Two kernels a layer, up and down, over the held experts."""
    return scope_ms_per_decode_step(run, "moe_gmm")


def moe_gmm_step_s(run):
    s = hybrid_counters.scope_seconds(run, "moe_gmm")
    steps = hybrid_counters.traced_decode_steps(run)
    if not s or not steps:
        return None
    return s / steps


def expert_bytes(run):
    """The hit HELD experts' up and down kernels."""
    hit = hybrid_counters.decode_experts_hit_per_step(run)
    return (None if hit is None
            else flops_hybrid.expert_bytes(run["config"], hit))


def held_experts_hit_share(run):
    """The experts held are the file's ``n_routed_experts``."""
    d = moe_counters.window(run)
    if not d or not d["layer_steps"]:
        return None
    return 100.0 * d["experts_hit"] / (
        run["config"]["n_routed_experts"] * d["layer_steps"])


def ssm_decode_ms_per_decode_step(run):
    return scope_ms_per_decode_step(run, "ssm_decode")


def ssm_decode_hbm_roofline_share(run):
    """Every live slot's state read once and written once in every
    state-space layer: 4 operations an element of a 2 MB state."""
    kernel_ms = ssm_decode_ms_per_decode_step(run)
    slots = hybrid_counters.live_slots_per_step(run)
    if not kernel_ms or slots is None:
        return None
    floor_s = (flops_hybrid.state_step_bytes(run["config"], slots)
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)


def ssm_prefill_roofline_share(run):
    """Floors are for the rows the prefill programs computed (the bucket's
    rows: the scan runs over padding too), in every state-space layer."""
    s = hybrid_counters.scope_seconds(run, "ssm_scan_prefill")
    if not s:
        return None
    rows = facts.traced_counter(run, "prefill_padded_tokens")
    if not rows:
        return None
    peaks = flops.peaks(run["device"]["kind"])
    cfg = run["config"]
    per_row = max(
        flops_hybrid.scan_flops_per_token(cfg) / peaks["bf16_flops_per_s"],
        flops_hybrid.scan_bytes_per_token(cfg) / peaks["hbm_bytes_per_s"])
    return 100.0 * rows * flops_hybrid.layers(cfg, "M") * per_row / s


def ssm_state_share_of_decode_bytes(run):
    hit = hybrid_counters.decode_experts_hit_per_step(run)
    slots = hybrid_counters.live_slots_per_step(run)
    if hit is None or slots is None or not run.get("trace"):
        return None
    live = facts.live_kv_tokens(run, run["trace"]["t0"], run["trace"]["t1"])
    cfg = run["config"]
    return (100.0 * flops_hybrid.state_step_bytes(cfg, slots)
            / flops_hybrid.decode_step_bytes(cfg, live, hit, slots))
