"""The greater of the chunked scan's FLOP and byte floors as a share of
``ssm_scan_prefill``'s device time in the traced stretch, over every
program (a riding piece's scan in the decode program, a cold prompt's in a
prefill program). Floors are for the rows the programs computed (a
bucket's or a piece's rows: the scan runs over padding too), in every
layer; the bytes are the activations' alone (``flops_parallel.py
scan_bytes_per_token`` says why), so the nearer roof is the operations'."""
from benchmark import facts, flops, flops_parallel, parallel_counters


def read(run):
    s = parallel_counters.scope_seconds(run, "ssm_scan_prefill")
    if not s:
        return None
    rows = facts.traced_counter(run, "prefill_padded_tokens")
    if not rows:
        return None
    peaks = flops.peaks(run["device"]["kind"])
    cfg = run["config"]
    per_row = max(
        flops_parallel.scan_flops_per_token(cfg) / peaks["bf16_flops_per_s"],
        flops_parallel.scan_bytes_per_token(cfg) / peaks["hbm_bytes_per_s"])
    return 100.0 * rows * flops_parallel.layers(cfg) * per_row / s
