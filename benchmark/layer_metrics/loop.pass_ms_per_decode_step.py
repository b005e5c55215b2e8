"""Device ms of ONE pass of the stack in a decode step: the decode program's
seconds under the named scope ``loop_pass`` in the traced stretch / (decode
steps x passes). None for a program without a ``loop`` group or a trace
without the scope."""
from benchmark import looped_counters


def read(run):
    return looped_counters.pass_ms_per_decode_step(run)
