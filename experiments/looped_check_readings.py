"""Readings for the looped cell's correctness check (PR 60): what the check
of ``benchmark/runners/looped.py`` reads, on requests a window of the cell's
own traffic finished, for the RIGHT model and for every wrong reference
``benchmark/reference/looped_decoder.py`` knows (one pass fewer, the first
pass's K/V plane in every pass, no second norm of a pair, the un-normed
state handed on, float8 operands), on one seed (one server a process: a
second does not fit beside the first; loop over seeds in the shell).

    chiprun --timeout 3000 -- python experiments/looped_check_readings.py \
        --seed 3100000101 --seconds 30

Writes one JSON line a (seed, reference) to
``chiprun_out/pr60/window_check_readings.jsonl``, each with every sampled
token's gap, so that a tolerance and a share of tokens that may miss can be
read off the lines afterwards (``--summarise FILE`` prints, for each
reference, the share of tokens further down than 0.05 / 0.1 / 0.25 / 0.5
std, the worst and the mean gap in std)."""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())


def summarise(path: str) -> None:
    print("seed  reference  share > 0.05 / 0.1 / 0.25 / 0.5 std  worst  "
          "mean (std)  off the argmax")
    for line in map(json.loads, open(path)):
        gaps = [g / line["logit_std"] for g in line["gaps"]]
        shares = [sum(g > t for g in gaps) / len(gaps)
                  for t in (0.05, 0.1, 0.25, 0.5)]
        print(line["seed"], f"{line['reference']:>16}",
              *(f"{100 * s:5.1f}%" for s in shares), f"{max(gaps):.3f}",
              f"{sum(gaps) / len(gaps):.4f}",
              f"{line['tokens_off_the_reference_argmax']}/{len(gaps)}",
              sep="  ")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--only", nargs="*", help="these wrong models alone")
    ap.add_argument("--out",
                    default="chiprun_out/pr60/window_check_readings.jsonl")
    ap.add_argument("--summarise", metavar="FILE")
    a = ap.parse_args()
    if a.summarise:
        return summarise(a.summarise)

    from benchmark import harness, traffic as traffic_mod
    from benchmark.reference import looped_decoder
    from benchmark.run import load_cell
    from benchmark.runners import looped
    spec = load_cell("ouro-2.6b.loop-batch-16")
    harness.start(1)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    looped.hybrid.CHECK_REQUESTS = a.requests
    traffic = traffic_mod.load(spec["traffic_path"])
    traffic["kind"] = "serve-closed"
    served = looped.Served(spec["config"], a.seed)
    try:
        served.warm(traffic, a.seed)
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "traffic.json")
            with open(path, "w") as f:
                json.dump(traffic, f)
            raw = served.drive(path, a.seed, a.seconds, False)
        sample = served.window_sample(raw)
        stats = raw["stats"]
        print(f"preemptions in the window "
              f"{stats['after']['preemptions'] - stats['before']['preemptions']}"
              f", kv {stats['after']['kv']}", flush=True)
        served.release_pools()
        print(f"sampled {len(sample)} requests, slots "
              f"{sorted(s[0] for s in sample)}, prompts "
              f"{[len(s[1]) for s in sample]}", flush=True)
        for wrong in (None, *(a.only if a.only is not None
                              else looped_decoder.WRONG)):
            t0 = time.monotonic()
            c = served.check_served(sample, wrong=wrong, detail=True)
            c.update(seed=a.seed, reference=wrong or "right",
                     seconds=time.monotonic() - t0)
            with open(a.out, "a") as f:
                f.write(json.dumps(c) + "\n")
            print(json.dumps({k: v for k, v in c.items() if k != "gaps"}),
                  flush=True)
    finally:
        served.close()


if __name__ == "__main__":
    main()
