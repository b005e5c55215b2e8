"""Device time of the decode step's grouped expert matmuls (``moe_gmm*``:
two a layer, up and down, over the held experts) in the traced stretch /
decode steps on the device. Read from the runner's by-scope seconds, not
from the ten-line ``device_ops``; the prefill's kernels are
``moe_gmm_prefill*`` and are not counted."""
from benchmark import hybrid_counters


def read(run):
    s = hybrid_counters.scope_seconds(run, "moe_gmm")
    steps = hybrid_counters.traced_decode_steps(run)
    if not s or not steps:
        return None
    return 1e3 * s / steps
