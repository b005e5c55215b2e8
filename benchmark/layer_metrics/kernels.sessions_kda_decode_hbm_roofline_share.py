"""Least time the sessions cell's one-step delta-rule update could take
(every live slot's state, 64 heads of 128 x 128 float32, read once and
written once in each of the 3 ``K`` layers, and the kernel's operands, over
the HBM peak) as a share of ``kda_decode``'s measured time a step. The
kernel runs over every slot, idle ones too: the share reads low then."""
from benchmark import flops, flops_sessions, sessions_counters
from benchmark.layer_metrics import load

_kernel = load("kernels.sessions_kda_decode_ms_per_decode_step")


def read(run):
    kernel_ms = _kernel.read(run)
    slots = sessions_counters.live_slots_per_step(run)
    if not kernel_ms or slots is None:
        return None
    cfg = run["config"]
    floor_s = ((flops_sessions.state_step_bytes(cfg, slots)
                + flops_sessions.kda_operand_bytes(cfg, slots))
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)
