"""Device time of the short-conv cell's twelve gated short-convolution
mixers in a decode step (``shortconv_mixer`` in the runner's by-scope
seconds of the decode program: the in-projection, the gates, the window
step ``shortconv_step`` over the conv pool, a riding piece's conv, the
out-projection) in the traced stretch / decode steps on the device."""
from benchmark import shortconv_counters


def read(run):
    if not shortconv_counters.is_shortconv(run):
        return None
    return shortconv_counters.decode_scope_ms_per_step(run, "shortconv_mixer")
