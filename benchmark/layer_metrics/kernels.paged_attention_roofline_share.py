"""Least time the decode step's paged-attention kernel could take (the live
K and V rows of the attention layers read once a slot, over the HBM peak:
the pages the slots' lengths cover, the engine's own count over the traced
stretch) as a share of its measured time a step. Rows, bytes a row and time
are the run's family's (``benchmark/families/<runner>.py live_kv_tokens``,
``kv_bytes_per_token`` from its ``flops_*`` module,
``paged_attention_ms_per_decode_step``)."""
from benchmark import families, flops


def read(run):
    kernel_ms = families.read(run, "paged_attention_ms_per_decode_step")
    rows = families.read(run, "live_kv_tokens")
    per_row = families.read(run, "kv_bytes_per_token")
    if not kernel_ms or rows is None or per_row is None:
        return None
    floor_s = (per_row * rows
               / flops.peaks(run["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * floor_s / (kernel_ms * 1e-3)
