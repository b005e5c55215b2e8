"""Readings for the windowed cell's correctness check (PR 63): what the check
of ``benchmark/runners/windowed.py`` reads, on replies that ENDED inside a
window of the cell's own traffic, for the RIGHT model and for every wrong
variant ``benchmark/reference/windowed_decoder.py`` knows (every layer full, a
window of 1,023 and of 1,025 keys, YaRN on every layer, no attention factor,
raw top-8 weights, the period's full layer first, float8 operands), on one
seed (one server a process: a second does not fit beside the first; loop over
seeds in the shell).

    chiprun --timeout 3000 -- python experiments/windowed_check_readings.py \
        --seed 3100000101 --seconds 51

Writes one JSON line a (seed, reference) to
``chiprun_out/pr63/window_check_readings.jsonl``, each with every held
token's gap, so that the limits can be read off the lines afterwards
(``--summarise FILE`` prints, for each reference, the share of tokens further
down than 0.05 / 0.1 / 0.25 / 0.5 std, the worst and the mean gap in std, and
the near misses' paired readings)."""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

CELL = "mellum2-12b-a2.5b-8l.code-context-48"


def summarise(path: str) -> None:
    print("seed  reference  ok  share > 0.05 / 0.1 / 0.25 / 0.5 std  worst  "
          "mean (std)  off the argmax  near misses further (std)")
    for line in map(json.loads, open(path)):
        gaps = [g / line["logit_std"] for g in line["gaps"]]
        shares = [sum(g > t for g in gaps) / len(gaps)
                  for t in (0.05, 0.1, 0.25, 0.5)]
        print(line["seed"], f"{line['reference']:>20}", line["ok"],
              *(f"{100 * s:5.1f}%" for s in shares), f"{max(gaps):.3f}",
              f"{sum(gaps) / len(gaps):.4f}",
              f"{line['tokens_off_the_reference_argmax']}/{len(gaps)}",
              {k: round(v, 5) for k, v in
               line["near_miss_further_std"].items()}, sep="  ")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--only", nargs="*", help="these wrong models alone")
    ap.add_argument("--out",
                    default="chiprun_out/pr63/window_check_readings.jsonl")
    ap.add_argument("--summarise", metavar="FILE")
    a = ap.parse_args()
    if a.summarise:
        return summarise(a.summarise)

    from benchmark import harness
    from benchmark.reference import windowed_decoder
    from benchmark.run import load_cell
    from benchmark.runners import windowed
    spec = load_cell(CELL)
    t0 = time.monotonic()
    device = harness.start(1)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    served = windowed.Served(spec["config"], a.seed)
    try:
        raw, sample = windowed.window(
            served, spec["cell"], spec["traffic_path"], a.seed, a.seconds,
            False, t0, device)
        stats = raw["stats"]
        print(f"preemptions in the window "
              f"{stats['after']['preemptions'] - stats['before']['preemptions']}"
              f", kv {stats['after']['kv']}, window "
              f"{stats['after']['window']}", flush=True)
        print(f"sampled {len(sample)} requests, slots "
              f"{sorted(s[0] for s in sample)}, prompts "
              f"{[len(s[1]) for s in sample]}, replies "
              f"{[len(s[2]) for s in sample]}", flush=True)
        for wrong in (None, *(a.only if a.only is not None
                              else windowed_decoder.WRONG)):
            t1 = time.monotonic()
            c = served.check_served(sample, wrong=wrong, detail=True)
            c.update(seed=a.seed, reference=wrong or "right",
                     seconds=time.monotonic() - t1)
            with open(a.out, "a") as f:
                f.write(json.dumps(c) + "\n")
            print(json.dumps({k: v for k, v in c.items() if k != "gaps"}),
                  flush=True)
    finally:
        served.close()


if __name__ == "__main__":
    main()
