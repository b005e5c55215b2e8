"""Device time of the decode step's latent paged-attention kernel
(``mla_paged_attention`` in the runner's by-name seconds; a window's kernel
is ``mla_paged_attention_mq`` and is not counted) in the traced stretch /
decode steps on the device (executions x steps per dispatch). Through the
run's family (``benchmark/families/<runner>.py
mla_attention_ms_per_decode_step``)."""
from benchmark import families


def read(run):
    return families.read(run, "mla_attention_ms_per_decode_step")
