"""Device time of the parallel cell's decode-step paged-attention kernel
(``paged_attention`` in the runner's by-scope seconds of the decode
program: every layer's, one query a slot, FIVE query heads a K/V head; a
riding piece's window is ``paged_attention_mq`` and is not counted) in the
traced stretch / decode steps on the device. (The accepted
``kernels.paged_attention_ms_per_decode_step`` reads the ten longest
operations, among which this kernel need not be.)"""
from benchmark import parallel_counters


def read(run):
    return parallel_counters.decode_scope_ms_per_step(run, "paged_attention")
