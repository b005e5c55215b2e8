"""Recurrent-state bytes a decode step moves (live slots' state read and
written in every state-space layer) as a share of the step's byte floor
(``flops_hybrid.decode_step_bytes``): how much of the step is the second
kind of cache state."""
from benchmark import facts, flops_hybrid, hybrid_counters


def read(run):
    hit = hybrid_counters.decode_experts_hit_per_step(run)
    slots = hybrid_counters.live_slots_per_step(run)
    if hit is None or slots is None or not run.get("trace"):
        return None
    live = facts.live_kv_tokens(run, run["trace"]["t0"], run["trace"]["t1"])
    cfg = run["config"]
    return (100.0 * flops_hybrid.state_step_bytes(cfg, slots)
            / flops_hybrid.decode_step_bytes(cfg, live, hit, slots))
