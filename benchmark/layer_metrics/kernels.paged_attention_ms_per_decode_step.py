"""Device time of the decode step's paged-attention kernel
(``paged_attention``; the multi-query kernel of a suffix prefill or a
riding piece is ``paged_attention_mq`` and is not counted) in the traced
stretch / decode steps on the device (executions x steps per dispatch).
Where it is read from is the run's family's
(``benchmark/families/<runner>.py paged_attention_ms_per_decode_step``): the
ten-line ``device_ops`` in the dense and MoE families (a run in which the
kernel is not among the ten has no such line, which is said on stderr), the
runner's by-scope seconds in the others."""
from benchmark import families


def read(run):
    return families.read(run, "paged_attention_ms_per_decode_step")
