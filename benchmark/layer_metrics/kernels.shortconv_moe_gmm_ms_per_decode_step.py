"""Device time of the short-conv cell's decode-step grouped expert matmuls
(``moe_gmm`` in the runner's by-scope seconds of the decode program: two
kernels a layer, gate/up and down, over 32 experts at ~32 rows each plus a
riding piece's rows; a prefill program's are ``moe_gmm_prefill``) in the
traced stretch / decode steps on the device."""
from benchmark import shortconv_counters


def read(run):
    if not shortconv_counters.is_shortconv(run):
        return None
    return shortconv_counters.decode_scope_ms_per_step(run, "moe_gmm")
