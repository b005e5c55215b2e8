"""Least time a decode step of a sparse-expert model could take on this
chip, as a share of the step's measured device time. The bytes it must
read (``benchmark/flops_moe.py``): attention, router and head weights
once, the experts some LIVE token chose once (the engine's counter of
(layer, expert) hits in the decode steps of the traced stretch; an expert
nobody chose is not read, so counting all L x E would read high), and the
live keys and values; over the HBM peak. Bound by bandwidth: at 32 tokens
a step each hit expert multiplies ~4 rows."""
from benchmark import facts, flops, flops_moe, moe_counters
from benchmark.layer_metrics import load

_step = load("serve_programs.decode_step_device_ms")


def read(run):
    step_ms = _step.read(run)
    hit = moe_counters.decode_experts_hit_per_step(run)
    if not step_ms or hit is None:
        return None
    live = facts.live_kv_tokens(run, run["trace"]["t0"], run["trace"]["t1"])
    floor_s = (flops_moe.decode_step_bytes(run["config"], live, hit)
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (step_ms * 1e-3)
