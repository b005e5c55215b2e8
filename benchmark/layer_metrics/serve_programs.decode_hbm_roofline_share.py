"""Least time a decode step could take on this chip (the bytes it must move
over the HBM peak) as a share of the step's measured device time: the share
of the WHOLE step, which bounds a claim in every serving cell. Bound by
bandwidth, not by FLOPs. The bytes are the run's family's
(``benchmark/families/<runner>.py decode_step_bytes``: weights once, the
experts some LIVE token chose, the live slots' recurrent state read and
written, the live keys and values or latent rows, each from the family's
``flops_*`` module over the engine's counters of the traced stretch), and
so is the step's time (``decode_step_ms``: a draft-and-verify step where
the engine drafts). A riding piece's rows are in the step's time and not in
its bytes."""
from benchmark import families, flops


def read(run):
    step_ms = families.read(run, "decode_step_ms")
    if not step_ms:
        return None
    moved = families.read(run, "decode_step_bytes")
    if moved is None:
        return None
    floor_s = moved / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / (step_ms * 1e-3)
