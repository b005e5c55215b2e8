"""Device time of the sessions cell's decode-step paged-attention kernel
(``paged_attention`` in the runner's by-scope seconds: the ONE softmax
layer, one query a slot, 8 query heads a K/V head; a riding piece's window
is ``paged_attention_mq`` and is not counted) in the traced stretch /
decode steps on the device. (The accepted
``kernels.paged_attention_ms_per_decode_step`` reads the ten longest
operations, among which this kernel need not be.)"""
from benchmark import sessions_counters


def read(run):
    return sessions_counters.scope_ms_per_step(run, "paged_attention")
