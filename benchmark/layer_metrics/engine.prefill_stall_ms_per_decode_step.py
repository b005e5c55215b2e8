"""Seconds the engine thread waited for a prefill's first token
(``llmctl.engine.prefill.wait``) over the traced stretch / decode steps in
it: every running request's next token is held that long."""
from benchmark import span_counters


def read(run):
    return span_counters.ms_per_decode_step(run,
                                            (span_counters.PREFILL_WAIT,))
