"""Least time a step's grouped expert matmuls could take (the experts some
live row chose, their kernels streamed once, over the HBM peak) as a share
of the kernels' measured time a step. Bytes, not FLOPs, are the measure: at
decode a hit expert multiplies ~4 rows (32 tokens x 8 choices over 64
experts) by 6.3 M parameters, 0.05 GFLOP against 12.6 MB: 4 operations a
byte where the chip's ridge is 240. Only experts the engine's counter says
were hit are counted, so the share cannot read high. Bytes and time are the
run's family's (``benchmark/families/<runner>.py``): ``expert_bytes`` from
its ``flops_*`` module over its own count of hits a step, and
``moe_gmm_step_s``, the time of ITS step (a decode step; a denoise forward
in the diffusion family; a draft-and-verify step where the engine
drafts)."""
from benchmark import families, flops


def read(run):
    step_s = families.read(run, "moe_gmm_step_s")
    moved = families.read(run, "expert_bytes")
    if not step_s or moved is None:
        return None
    floor_s = moved / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / step_s
