"""Readings for the self-drafting cell's correctness check (PR 53): what the
check of ``benchmark/runners/selfdraft.py`` reads, served tokens AND served
drafts, on requests a window of the cell's own traffic finished, for the
RIGHT model and for the wrong references of
``benchmark/reference/selfdraft_decoder.py`` (every matmul operand rounded
to float8; of the module alone: ``RMSNorm_h`` left out, the normed stream,
the swapped concatenation, the token's own embedding), on one seed (one
server a process).

    chiprun --timeout 3000 -- python experiments/selfdraft_check_readings.py \
        --seed 3300005311 --seconds 25

Writes one JSON line a (seed, reference) to
``chiprun_out/pr53/window_check_readings.jsonl`` with every sampled value's
gap and routing margin; prints each line's summary. ``float8`` runs
operation by operation (minutes a request at 10k tokens): it is read on the
first ``--float8-requests`` of the sample."""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())

WRONG = (None, "no_hnorm", "normed_stream", "swapped_concat", "own_token",
         "float8")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--float8-requests", type=int, default=2)
    ap.add_argument("--only", default="")
    ap.add_argument("--out",
                    default="chiprun_out/pr53/window_check_readings.jsonl")
    a = ap.parse_args()

    from benchmark import harness, loadgen_docqa
    from benchmark.run import load_cell
    from benchmark.runners import selfdraft
    spec = load_cell("joyai-llm-flash-8l-ep2.agent-turns-64")
    selfdraft.require_selfdraft_support(spec["config"])
    t0 = time.monotonic()
    harness.start(1)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    selfdraft.CHECK_REQUESTS = a.requests
    traffic = loadgen_docqa.load(spec["traffic_path"])
    served = selfdraft.Served(spec["config"], a.seed, traffic)
    served._t0 = t0
    try:
        served.warm(traffic, a.seed)
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "traffic.json")
            with open(path, "w") as f:
                json.dump(dict(traffic, kind="serve-closed"), f)
            raw = served.drive(path, a.seed, a.seconds, False)
        sample = served.window_sample(raw)
        print(f"sampled {len(sample)} requests, slots "
              f"{sorted(s[0] for s in sample)}, prompts "
              f"{[len(s[1]) for s in sample]}", flush=True)
        served.release_pool()
        for wrong in (a.only.split(",") if a.only else WRONG):
            wrong = wrong or None
            t1 = time.monotonic()
            some = sample[:a.float8_requests] if wrong == "float8" else sample
            selfdraft.CHECK_REQUESTS = len(some)
            c = served.check_served(some, wrong=wrong, detail=True)
            c.update(seed=a.seed, reference=wrong or "right",
                     seconds=time.monotonic() - t1)
            with open(a.out, "a") as f:
                f.write(json.dumps(c) + "\n")
            print(json.dumps({k: v for k, v in c.items()
                              if not k.endswith(("_gaps", "_margins"))}),
                  flush=True)
    finally:
        served.close()


if __name__ == "__main__":
    main()
