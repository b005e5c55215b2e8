"""Readings for the sessions cell's correctness check (PR 46): what the
check of ``benchmark/runners/sessions.py`` reads, on snapshot hits that a
window of the cell's own traffic finished, for the RIGHT model and for
wrong references, on one seed (one server a process), each by the three
limits (the window's tokens, the probes' tokens, the probes' snapshot
entries). Two of the faults are wrong SERVERS' (``zero_at_hit``: a slot
armed from nothing; ``stale_at_hit``: armed from the snapshot of the page
before), read through the reference with the same fault at each turn's
and each probe's own hit.

    chiprun --timeout 3000 -- python experiments/sessions_check_readings.py \
        --seed 4000000101 --seconds 51

Writes one JSON line a (seed, reference) to
``chiprun_out/pr46/window_check_readings.jsonl`` with every sampled token's
gap and routing margin; prints each line's summary."""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())

WRONG = (None, "bf16_state", "zero_at_hit", "stale_at_hit", "beta_unscaled",
         "no_gate", "rope", "no_renorm", "float8")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--only", default="")
    ap.add_argument("--out",
                    default="chiprun_out/pr46/window_check_readings.jsonl")
    a = ap.parse_args()

    from benchmark import facts, harness, loadgen_sessions
    from benchmark.run import load_cell
    from benchmark.runners import sessions
    spec = load_cell("solar-open2-250b-4l-ep8.sessions-64")
    sessions.require_sessions_support(spec["config"])
    t0 = time.monotonic()
    harness.start(1)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    traffic = loadgen_sessions.load(spec["traffic_path"])
    served = sessions.Served(spec["config"], a.seed, traffic)
    try:
        served.warm(traffic, a.seed)
        harness.mark("programs warm, sessions resident", t0)
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "traffic.json")
            with open(path, "w") as f:
                json.dump(dict(traffic, kind="serve-closed"), f)
            raw = served.drive(path, a.seed, a.seconds, False)
        w0, w1 = raw["window"]
        kda = {k: raw["stats"]["after"]["kda"][k]
               - raw["stats"]["before"]["kda"][k] for k in (
            "snapshots_taken", "snapshot_hits", "snapshot_misses",
            "snapshot_evictions", "snapshot_tokens_skipped")}
        print(json.dumps({
            "serve_tokens_per_s": facts.tokens_in_window(raw) / (w1 - w0),
            "sent_and_ended_in_window": len(facts.window_requests(raw)),
            "window_kda": kda}), flush=True)
        sample = served.window_sample(raw)
        print(f"sampled {len(sample)} turns, slots "
              f"{sorted(s[0] for s in sample)}, prompts "
              f"{[len(s[1]) for s in sample]}, armed at "
              f"{[s[3] for s in sample]}", flush=True)
        probes = served.probe(raw)
        print(f"{len(probes)} probes, cuts {[p[2] for p in probes]}",
              flush=True)
        served.release_pools()
        for wrong in (a.only.split(",") if a.only else WRONG):
            wrong = None if wrong in ("", "right") else wrong
            t1 = time.monotonic()
            c = served.check_served(sample, probes, wrong=wrong, detail=True)
            c.update(seed=a.seed, reference=wrong or "right",
                     seconds=time.monotonic() - t1)
            with open(a.out, "a") as f:
                f.write(json.dumps(c) + "\n")
            print(json.dumps({k: v for k, v in c.items()
                              if k not in ("gaps", "margins", "probe_gaps")}),
                  flush=True)
    finally:
        served.close()


if __name__ == "__main__":
    main()
