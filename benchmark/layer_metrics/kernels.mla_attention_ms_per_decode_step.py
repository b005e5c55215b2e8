"""Device time of the decode step's latent paged-attention kernel
(``mla_paged_attention`` in the runner's by-name seconds; a window's kernel
is ``mla_paged_attention_mq`` and is not counted) in the traced stretch /
decode steps on the device (executions x steps per dispatch)."""
from benchmark import latent_counters


def read(run):
    s = latent_counters.scope_seconds(run, "mla_paged_attention")
    steps = latent_counters.traced_decode_steps(run)
    if not s or not steps:
        return None
    return 1e3 * s / steps
