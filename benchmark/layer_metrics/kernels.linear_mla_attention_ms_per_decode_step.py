"""Device time of the linear cell's decode-step latent paged-attention
kernel (``mla_paged_attention`` in the runner's by-scope seconds, 3 ``*``
layers; a chunk's kernel is ``mla_paged_attention_mq`` and is not counted)
in the traced stretch / decode steps on the device."""
from benchmark import linear_counters


def read(run):
    s = linear_counters.scope_seconds(run, "mla_paged_attention")
    steps = linear_counters.traced_decode_steps(run)
    if not s or not steps:
        return None
    return 1e3 * s / steps
