"""Device ms a decode step spends under the named scope ``exit_gate`` (the
gate on every pass's normed state and the exit rule's bookkeeping), traced
stretch, all passes of the step. None for a program without a ``loop``
group or a trace without the scope."""
from benchmark import looped_counters


def read(run):
    return looped_counters.exit_gate_ms_per_decode_step(run)
