"""Device time of the prefill programs in the traced stretch per thousand
prompt tokens prefilled in it (engine.stats() prefill_tokens delta)."""
from benchmark import facts


def read(run):
    progs = run["trace"].get("programs", {})
    seconds = sum(progs.get(p, (0, 0.0))[1]
                  for p in ("prefill", "suffix_prefill"))
    tokens = facts.traced_counter(run, "prefill_tokens") if progs else 0
    if not tokens or not seconds:
        return None
    return 1e3 * seconds / (tokens / 1e3)
