"""Readings for the linear cell's correctness check (PR 40): what the check
of ``benchmark/runners/linear.py`` reads, on requests a window of the cell's
own traffic finished, for the RIGHT model and for six wrong references, on
one seed (one server a process). The right model and the two NEAR MISSES
(``bf16_state``, ``rotated_pe``) come first: each is read by both limits,
the second against the reference with one near miss toggled (four passes
in all, kept); a gross fault fails the first limit and is read by it alone.

    chiprun --timeout 3000 -- python experiments/linear_check_readings.py \
        --seed 4000000101 --seconds 51

Writes one JSON line a (seed, reference) to
``chiprun_out/pr40/window_check_readings_r2.jsonl`` with every sampled token's
gap and routing margin; prints each line's summary (worst and mean gap in
reference-logit standard deviations, tokens off the reference's argmax, the
seconds the reference took)."""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())

WRONG = (None, "bf16_state", "rotated_pe", "no_beta", "per_head_decay",
         "no_renorm", "float8")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--only", default="")
    ap.add_argument("--out",
                    default="chiprun_out/pr40/window_check_readings_r2.jsonl")
    a = ap.parse_args()

    import numpy as np

    from benchmark import facts, harness, loadgen_linear
    from benchmark.run import load_cell
    from benchmark.runners import linear
    spec = load_cell("kimi-linear-48b-a3b-12l-ep8.reason-docs-128")
    linear.require_linear_support(spec["config"])
    t0 = time.monotonic()
    harness.start(1)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    traffic = loadgen_linear.load(spec["traffic_path"])
    served = linear.Served(spec["config"], a.seed, traffic)
    try:
        served.warm(traffic, a.seed)
        harness.mark("programs warm", t0)
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "traffic.json")
            with open(path, "w") as f:
                json.dump(dict(traffic, kind="serve-closed"), f)
            raw = served.drive(path, a.seed, a.seconds, False)
        w0, w1 = raw["window"]
        ended = [r for r in raw["stamps"]["records"]
                 if r["done"] is not None and w0 <= r["done"] <= w1]
        print(json.dumps({
            "serve_tokens_per_s": facts.tokens_in_window(raw) / (w1 - w0),
            "replies_ended_in_window": len(ended),
            "documents_among_them": sum(served.is_document(
                [0] * r["prompt_tokens"]) for r in ended),
            "stopped_before_max_tokens": sum(
                r["tokens"] != r["max_tokens"] for r in ended),
            "sent_and_ended_in_window": len(facts.window_requests(raw)),
            "tpot_ms_of_ended_p50_p95": [
                float(np.percentile(t, p)) for t in [[
                    (r["chunks"][-1] - r["chunks"][0]) * 1e3
                    / (r["tokens"] - 1) for r in ended if r["tokens"] > 1]]
                for p in (50, 95)]}), flush=True)
        sample = served.window_sample(raw)
        print(f"sampled {len(sample)} requests, slots "
              f"{sorted(s[0] for s in sample)}, prompts "
              f"{[len(s[1]) for s in sample]}", flush=True)
        served.release_pools()
        for wrong in (a.only.split(",") if a.only else WRONG):
            wrong = None if wrong in ("", "right") else wrong
            t1 = time.monotonic()
            c = served.check_served(sample, wrong=wrong, detail=True)
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
