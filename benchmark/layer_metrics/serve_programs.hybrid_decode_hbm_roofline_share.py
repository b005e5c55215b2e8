"""Least time a decode step of the hybrid model could take on this chip, as
a share of the step's measured device time. The bytes it must move
(``benchmark/flops_hybrid.py``): mixer, attention, router, shared-expert
and head weights once, the held experts some LIVE token chose once (the
engine's counter over the traced stretch), the live slots' recurrent state
read and written (``stats()["ssm"]["slot_steps"]`` a step) and the live
keys and values; over the HBM peak."""
from benchmark import facts, flops, flops_hybrid, hybrid_counters
from benchmark.layer_metrics import load

_step = load("serve_programs.decode_step_device_ms")


def read(run):
    step_ms = _step.read(run)
    hit = hybrid_counters.decode_experts_hit_per_step(run)
    slots = hybrid_counters.live_slots_per_step(run)
    if not step_ms or hit is None or slots is None:
        return None
    live = facts.live_kv_tokens(run, run["trace"]["t0"], run["trace"]["t1"])
    floor_s = (flops_hybrid.decode_step_bytes(run["config"], live, hit, slots)
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (step_ms * 1e-3)
