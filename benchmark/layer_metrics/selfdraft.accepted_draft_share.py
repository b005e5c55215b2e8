"""Share of the drafts the window's credited draft-and-verify steps
verified that STOOD (the main stack's own greedy token equalled the
module's draft): ``mtp_accepted`` / ``mtp_drafts`` of ``engine.stats()``.
At chance (1 in the vocabulary) over seeded weights; 80-90 % is what
trained modules of this family reach."""
from benchmark import selfdraft_counters


def read(run):
    d = selfdraft_counters.mtp_delta(run)
    if not d or not d["drafts"]:
        return None
    return 100.0 * d["accepted"] / d["drafts"]
