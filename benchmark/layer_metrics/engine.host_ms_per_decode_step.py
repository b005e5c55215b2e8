"""Self time of the engine thread's host spans (admit, prefill.host,
capacity, decode.submit, apply, deliver) over the traced stretch / decode
steps in it: what the host adds to a step, where no dispatch hides it."""
from benchmark import span_counters


def read(run):
    return span_counters.ms_per_decode_step(run, span_counters.HOST_PHASES)
