"""Runs of ``runners/parallel.py`` (attention AND a state-space mixer in
every layer): bytes from ``flops_parallel.py``, the decode program's
by-scope seconds and the ``ssm`` / ``kv`` counters from
``parallel_counters.py``."""
from benchmark import (facts, families, flops, flops_parallel,
                       parallel_counters)

decode_step_ms = families.load("serve").decode_step_ms
live_kv_tokens = parallel_counters.live_kv_tokens


def decode_step_bytes(run):
    """Every layer's weights and the head once, the live slots' recurrent
    state read and written in EVERY layer and the live keys and values of
    every layer. The step's time holds the rows of prompts that ride it,
    its bytes do not."""
    return parallel_counters.decode_step_bytes(run)


def paged_attention_ms_per_decode_step(run):
    """``paged_attention`` in the runner's by-scope seconds of the decode
    program: every layer's, one query a slot, FIVE query heads a K/V head.
    (The ten longest operations need not hold this kernel.)"""
    return parallel_counters.decode_scope_ms_per_step(run, "paged_attention")


def kv_bytes_per_token(run):
    """Of every layer."""
    return flops_parallel.kv_bytes_per_token(run["config"])


def ssm_decode_ms_per_decode_step(run):
    """``ssm_decode`` of the decode program: the recurrence alone, the
    projections apart."""
    return parallel_counters.decode_scope_ms_per_step(run, "ssm_decode")


def ssm_decode_hbm_roofline_share(run):
    """In every layer: 4 operations an element of a 4.19 MB state."""
    kernel_ms = ssm_decode_ms_per_decode_step(run)
    slots = parallel_counters.live_slots_per_step(run)
    if not kernel_ms or slots is None:
        return None
    floor_s = (flops_parallel.state_step_bytes(run["config"], slots)
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)


def ssm_prefill_roofline_share(run):
    """Over every program (a riding piece's scan in the decode program, a
    cold prompt's in a prefill program); the bytes are the activations'
    alone (``flops_parallel.py scan_bytes_per_token`` says why), so the
    nearer roof is the operations'."""
    s = parallel_counters.scope_seconds(run, "ssm_scan_prefill")
    if not s:
        return None
    rows = facts.traced_counter(run, "prefill_padded_tokens")
    if not rows:
        return None
    peaks = flops.peaks(run["device"]["kind"])
    cfg = run["config"]
    per_row = max(
        flops_parallel.scan_flops_per_token(cfg) / peaks["bf16_flops_per_s"],
        flops_parallel.scan_bytes_per_token(cfg) / peaks["hbm_bytes_per_s"])
    return 100.0 * rows * flops_parallel.layers(cfg) * per_row / s


def ssm_state_share_of_decode_bytes(run):
    slots = parallel_counters.live_slots_per_step(run)
    total = parallel_counters.decode_step_bytes(run)
    if slots is None or not total:
        return None
    return (100.0 * flops_parallel.state_step_bytes(run["config"], slots)
            / total)
