"""Runs of ``runners/sessions.py`` (delta-rule ``K`` layers, ONE softmax
layer over GQA pages, held experts, snapshots): bytes from
``flops_sessions.py``, by-scope seconds and the ``kda`` / ``kv`` / ``moe``
counters from ``sessions_counters.py``."""
from benchmark import families, flops, flops_sessions, sessions_counters

decode_step_ms = families.load("serve").decode_step_ms
# the experts held are ``n_routed_experts`` in both files
held_experts_hit_share = families.load("hybrid").held_experts_hit_share
kda_decode_ms_per_decode_step = families.load(
    "linear").kda_decode_ms_per_decode_step
live_kv_tokens = sessions_counters.live_kv_tokens


def decode_step_bytes(run):
    """Mixer, router, shared-expert and head weights once, the held experts
    some LIVE token chose once, the live slots' recurrent state read and
    written and the live K/V rows of the one softmax layer. A riding
    piece's rows are in the step's time and not in the floor."""
    return sessions_counters.decode_step_bytes(run)


def moe_gmm_step_s(run):
    """A riding piece's rows go through the same calls, and the experts
    they alone hit are streamed too: the share reads low then."""
    kernel_ms = sessions_counters.scope_ms_per_step(run, "moe_gmm")
    return kernel_ms * 1e-3 if kernel_ms else None


def expert_bytes(run):
    hit = sessions_counters.decode_experts_hit_per_step(run)
    return (None if hit is None
            else flops_sessions.expert_bytes(run["config"], hit))


def paged_attention_ms_per_decode_step(run):
    """``paged_attention`` in the runner's by-scope seconds: the ONE
    softmax layer, one query a slot, 8 query heads a K/V head. (The ten
    longest operations need not hold this kernel.)"""
    return sessions_counters.scope_ms_per_step(run, "paged_attention")


def kv_bytes_per_token(run):
    """Of the one ``*`` layer."""
    return flops_sessions.kv_bytes_per_token(run["config"])


def kda_decode_hbm_roofline_share(run):
    """Every live slot's state, 64 heads of 128 x 128 float32, read once
    and written once in each of the 3 ``K`` layers, AND the kernel's
    operands. The kernel runs over every slot, idle ones too: the share
    reads low then."""
    kernel_ms = kda_decode_ms_per_decode_step(run)
    slots = sessions_counters.live_slots_per_step(run)
    if not kernel_ms or slots is None:
        return None
    cfg = run["config"]
    floor_s = ((flops_sessions.state_step_bytes(cfg, slots)
                + flops_sessions.kda_operand_bytes(cfg, slots))
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)
