"""Device time of the grouped expert matmuls in the DECODE program (the
main stack's expert layers and the module's, two rows a slot; a window of
128 rows takes the ragged kernel, ``moe_gmm_prefill``, where a step of 64
rows takes ``moe_gmm``: both names are read, the decode program's alone)
over the traced stretch / steps."""
from benchmark import selfdraft_counters


def read(run):
    return selfdraft_counters.step_scope_ms(
        run, "moe_gmm", "moe_gmm_prefill")
