"""Least time a decode step could take on this chip (the bytes it must read:
weights once and the live keys and values, over the HBM peak) as a share of
the decode step's measured device time. Bound by bandwidth, not by FLOPs."""
from benchmark import facts, flops
from benchmark.layer_metrics import load

_step = load("serve_programs.decode_step_device_ms")


def read(run):
    step_ms = _step.read(run)
    if not step_ms:
        return None
    live = facts.live_kv_tokens(run, run["trace"]["t0"], run["trace"]["t1"])
    floor_s = (flops.decode_step_bytes(run["config"], live)
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (step_ms * 1e-3)
