"""The greater of the chunked scan's FLOP and byte floors as a share of
``ssm_scan_prefill*``'s device time in the traced stretch. Floors are for
the rows the prefill programs computed (the bucket's rows: the scan runs
over padding too), in every state-space layer."""
from benchmark import facts, flops, flops_hybrid, hybrid_counters


def read(run):
    s = hybrid_counters.scope_seconds(run, "ssm_scan_prefill")
    if not s:
        return None
    rows = facts.traced_counter(run, "prefill_padded_tokens")
    if not rows:
        return None
    peaks = flops.peaks(run["device"]["kind"])
    cfg = run["config"]
    per_row = max(
        flops_hybrid.scan_flops_per_token(cfg) / peaks["bf16_flops_per_s"],
        flops_hybrid.scan_bytes_per_token(cfg) / peaks["hbm_bytes_per_s"])
    return 100.0 * rows * flops_hybrid.layers(cfg, "M") * per_row / s
