"""Tokens a credited slot-step made over the window (1 where the draft
fell, 2 where it stood): ``mtp_tokens`` / ``mtp_slot_steps`` of
``engine.stats()``. A deployment's rate is this cell's tokens/s
times its own value of this over the cell's."""
from benchmark import selfdraft_counters


def read(run):
    d = selfdraft_counters.mtp_delta(run)
    if not d or not d["slot_steps"]:
        return None
    return d["tokens"] / d["slot_steps"]
