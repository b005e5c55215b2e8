"""The greater of the chunked delta rule's FLOP and byte floors as a share
of ``kda_chunk_prefill``'s device time in the traced stretch. Floors are for
the rows the prefill programs computed (a bucket's or a chunk's rows: the
chunked form runs over padding too), in every ``K`` layer."""
from benchmark import facts, flops, flops_linear, linear_counters


def read(run):
    s = linear_counters.scope_seconds(run, "kda_chunk_prefill")
    if not s:
        return None
    rows = facts.traced_counter(run, "prefill_padded_tokens")
    if not rows:
        return None
    peaks = flops.peaks(run["device"]["kind"])
    cfg = run["config"]
    per_row = max(
        flops_linear.chunk_flops_per_token(cfg) / peaks["bf16_flops_per_s"],
        flops_linear.chunk_bytes_per_token(cfg) / peaks["hbm_bytes_per_s"])
    return 100.0 * rows * flops_linear.layers(cfg, "K") * per_row / s
