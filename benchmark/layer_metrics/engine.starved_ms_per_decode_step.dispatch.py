"""Seconds a slot held a request, nothing was in flight and the engine
thread was getting the next program ready (``starved_by_phase`` under
``llmctl.engine.admit``, ``.capacity``, ``.prefill.host``,
``.decode.submit``) over the traced stretch / decode steps in it."""
from benchmark import slot_step_counters


def read(run):
    return slot_step_counters.starved_ms_per_decode_step(
        run, slot_step_counters.DISPATCH_SPANS,
        "engine.starved_ms_per_decode_step.dispatch")
