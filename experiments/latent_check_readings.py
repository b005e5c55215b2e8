"""Readings for the latent cell's correctness check (PR 33): what the check
of ``benchmark/runners/latent.py`` reads, on requests a window of the cell's
own traffic finished, for the RIGHT model, for nine wrong references and
for a cache hit served from another document's pages, on one seed (one
server a process).

    chiprun --timeout 3000 -- python experiments/latent_check_readings.py \
        --seed 3300000101 --seconds 25

Writes one JSON line a (seed, reference) to
``chiprun_out/pr33/window_check_readings.jsonl`` with every sampled token's
gap and routing margin; prints each line's summary (worst and mean gap in
reference-logit standard deviations, tokens off the reference's argmax)."""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())

WRONG = (None, "float8", "float8_latent", "other_document", "ckv_unnormed",
         "rope_wrong_dims", "scale_without_mscale", "yarn_interpolation",
         "no_sinkhorn", "one_stream", "softmax_scores")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--only", default="")
    ap.add_argument("--out",
                    default="chiprun_out/pr33/window_check_readings.jsonl")
    a = ap.parse_args()

    from benchmark import harness, loadgen_docqa
    from benchmark.run import load_cell
    from benchmark.runners import latent
    spec = load_cell("xing4.0-29b-a4b-7l.doc-qa-64")
    latent.require_latent_support(spec["config"])
    t0 = time.monotonic()
    harness.start(1)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    latent.CHECK_REQUESTS = a.requests
    traffic = loadgen_docqa.load(spec["traffic_path"])
    served = latent.Served(spec["config"], a.seed, traffic)
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
            other = wrong == "other_document"
            c = served.check_served(sample, wrong=None if other else wrong,
                                    other_document=other, detail=True)
            c.update(seed=a.seed, reference=wrong or "right",
                     seconds=time.monotonic() - t1)
            with open(a.out, "a") as f:
                f.write(json.dumps(c) + "\n")
            print(json.dumps({k: v for k, v in c.items()
                              if k not in ("gaps", "margins")}), flush=True)
    finally:
        served.close()


if __name__ == "__main__":
    main()
