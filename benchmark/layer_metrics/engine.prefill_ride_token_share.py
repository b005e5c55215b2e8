"""Share of the window's prefilled prompt tokens that were prefilled INSIDE
the decode dispatches, as rows of the decode steps' matmuls, and not by a
prefill program between two dispatches: the engine's ``prefill_ride_tokens``
/ ``prefill_tokens`` (``engine.stats()`` deltas; the second counts both).
Near 0 where fewer than half the slots are resident (a prompt then takes
the cold program at once), near 100 at saturation. A program without the
counter (a commit from before PR 36) gives None, and so does a window that
prefilled nothing. One entry for every saturated cell whose layer table
rides, whatever its family (a piece attends over its slot's pages and runs
from its slot's own recurrent state, conv rows or snapshot); 0 on a program
whose engine has the counter and never rides. The cell under the knee is
judged the other way round and has ``engine.prefill_ride_token_share.chat``."""


def read(run):
    a, b = run["stats"]["before"], run["stats"]["after"]
    if "prefill_ride_tokens" not in a or "prefill_ride_tokens" not in b:
        return None
    tokens = b["prefill_tokens"] - a["prefill_tokens"]
    if tokens <= 0:
        return None
    return 100.0 * (b["prefill_ride_tokens"] - a["prefill_ride_tokens"]) / tokens
