"""Device time under the two mixers' scopes (``parallel_attention`` and
``parallel_ssm``: projections, page writes, the paged-attention kernel, the
conv, the recurrence, the gated norm; a riding piece's rows among them) as
a share of the decode program's device time in the traced stretch: whether
the mechanism (two kinds of state a layer) does most of the work, or the
MLP and the head do."""
from benchmark import parallel_counters
from benchmark.layer_metrics import load

_step = load("serve_programs.decode_step_device_ms")


def read(run):
    step_ms = _step.read(run)
    mixers_ms = parallel_counters.decode_scope_ms_per_step(
        run, "parallel_attention", "parallel_ssm")
    if not step_ms or mixers_ms is None:
        return None
    return 100.0 * mixers_ms / step_ms
