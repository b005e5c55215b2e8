"""Device time of the linear cell's decode-step grouped expert matmuls
(``moe_gmm*`` in the runner's by-scope seconds: two a layer over the held
experts, 11 ``E`` layers; the prefill's kernels are ``moe_gmm_prefill*`` and
are not counted) in the traced stretch / decode steps on the device."""
from benchmark import linear_counters


def read(run):
    s = linear_counters.scope_seconds(run, "moe_gmm")
    steps = linear_counters.traced_decode_steps(run)
    if not s or not steps:
        return None
    return 1e3 * s / steps
