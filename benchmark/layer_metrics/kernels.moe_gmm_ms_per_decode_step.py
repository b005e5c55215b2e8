"""Device time of the decode step's grouped expert matmuls (``moe_gmm``; a
prefill's kernels are ``moe_gmm_prefill`` and are not counted) in the
traced stretch / decode steps on the device (executions x steps per
dispatch). Where it is read from is the run's family's
(``benchmark/families/<runner>.py moe_gmm_ms_per_decode_step``): the
ten-line ``device_ops`` in the MoE family, the runner's by-scope seconds in
the others."""
from benchmark import families


def read(run):
    return families.read(run, "moe_gmm_ms_per_decode_step")
