"""Model FLOP/s utilisation of the window: tokens/s x operations per token
(``flops.train_flops_per_token``, the non-causal convention, recomputation
not counted) over chips x the chip's published bf16 peak."""
from benchmark import facts, flops


def read(run):
    tokens, seconds = facts.train_rate(run)
    per_token = flops.train_flops_per_token(run["config"], run["seq_len"])
    peak = flops.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * tokens / seconds * per_token / (run["chips"] * peak)
