"""Device ms of the WINDOW layers' page kernel in a decode step: the decode
program's seconds under the kernel's name ``window_attention`` (one query a
slot, once a window layer; a riding piece's and a chunk's multi-query calls
are ``window_attention_mq`` and not counted) in the traced stretch / decode
steps. None for a program without a ``window`` group or a trace without the
kernel. Through the run's family (``benchmark/families/windowed.py``)."""
from benchmark import families


def read(run):
    return families.read(run, "window_attention_ms_per_decode_step")
