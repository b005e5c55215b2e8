"""Device time of the prefill programs in the traced stretch per thousand
prompt tokens prefilled in it (engine.stats() prefill_tokens delta). Listed
where a prefill program runs inside the window: the open-loop chat cell
(under half the slots resident, a prompt takes the cold program at once)
and the diffusion cell. In the saturated cells whose prompts ride the
decode dispatches (since PRs 36-44) no prefill program runs and there is
nothing to read: what their prompts cost is read by
``engine.prefill_ride_token_share`` and by the decode step's time."""
from benchmark import facts


def read(run):
    progs = run["trace"].get("programs", {})
    seconds = sum(progs.get(p, (0, 0.0))[1]
                  for p in ("prefill", "suffix_prefill"))
    tokens = facts.traced_counter(run, "prefill_tokens") if progs else 0
    if not tokens or not seconds:
        return None
    return 1e3 * seconds / (tokens / 1e3)
