"""Least time the one-step state update could take (every live slot's state
read once and written once in every layer, over the HBM peak) as a share
of ``ssm_decode``'s measured time a step. Bytes are the measure: a slot's
update is 4 operations an element of a 4.19 MB state."""
from benchmark import flops, flops_parallel, parallel_counters
from benchmark.layer_metrics import load

_kernel = load("kernels.parallel_ssm_decode_ms_per_decode_step")


def read(run):
    kernel_ms = _kernel.read(run)
    slots = parallel_counters.live_slots_per_step(run)
    if not kernel_ms or slots is None:
        return None
    floor_s = (flops_parallel.state_step_bytes(run["config"], slots)
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)
