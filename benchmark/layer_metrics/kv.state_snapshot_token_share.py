"""Share of the window's prompt tokens that were SKIPPED through a snapshot
of the recurrent state: the engine's ``stats()["kda"]
["snapshot_tokens_skipped"]`` over that and ``prefill_tokens`` (window
deltas). Near 88 where every turn is armed from its session's last
snapshot; lower means the cell is not measuring what it says (a snapshot or
the page under it was evicted, and a turn re-read its whole history)."""
from benchmark import sessions_counters


def read(run):
    return sessions_counters.snapshot_token_share(run)
