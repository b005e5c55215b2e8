"""Device time of the short-conv cell's decode-step paged-attention kernel
(``paged_attention`` in the runner's by-scope seconds of the decode
program: the four attention layers', one query a slot, heads of 64 in
PAIRS on the pool's 128 lanes; a riding piece's window is
``paged_attention_mq`` and is not counted) in the traced stretch / decode
steps on the device."""
from benchmark import shortconv_counters


def read(run):
    if not shortconv_counters.is_shortconv(run):
        return None
    return shortconv_counters.decode_scope_ms_per_step(run, "paged_attention")
