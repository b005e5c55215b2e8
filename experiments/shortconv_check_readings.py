"""Readings for the short-conv cell's correctness check (PR 55): what the
check of ``benchmark/runners/shortconv.py`` reads, on requests a window of
the cell's own traffic finished, for the RIGHT model and for every wrong
reference ``benchmark/reference/shortconv_decoder.py`` knows, on one seed
(one server a process: a second does not fit beside the first; loop over
seeds in the shell).

    chiprun --timeout 3000 -- python experiments/shortconv_check_readings.py \
        --seed 3100000101 --seconds 30

Writes one JSON line a (seed, reference) to
``chiprun_out/pr55/window_check_readings.jsonl``, each with every sampled
token's gap and routing margin, so that a tolerance, a margin and a share
of tokens that may miss can be read off the lines afterwards (``--summarise
FILE`` prints, for each reference, the share of KEPT tokens further down
than 0.05 / 0.1 / 0.25 std at margins of 0 / 0.001 / 0.002 / 0.005, the
share kept, the worst and the mean gap in std)."""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())


def summarise(path: str) -> None:
    print("seed  reference  margin  kept  share > 0.05 / 0.1 / 0.25 std  "
          "worst  mean (std)")
    for line in map(json.loads, open(path)):
        std = line["logit_std"]
        for margin in (0.0, 0.001, 0.002, 0.005):
            gaps = [g / std for g, m in zip(line["gaps"], line["margins"])
                    if m >= margin]
            if not gaps:
                continue
            shares = [sum(g > t for g in gaps) / len(gaps)
                      for t in (0.05, 0.1, 0.25)]
            print(line["seed"], f"{line['reference']:>16}", margin,
                  f"{100 * len(gaps) / len(line['gaps']):5.1f}%",
                  *(f"{100 * s:5.1f}%" for s in shares),
                  f"{max(gaps):.3f}", f"{sum(gaps) / len(gaps):.4f}",
                  sep="  ")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--only", nargs="*", help="these wrong models alone")
    ap.add_argument("--out",
                    default="chiprun_out/pr55/window_check_readings.jsonl")
    ap.add_argument("--summarise", metavar="FILE")
    a = ap.parse_args()
    if a.summarise:
        return summarise(a.summarise)

    from benchmark import harness, traffic as traffic_mod
    from benchmark.reference import shortconv_decoder
    from benchmark.run import load_cell
    from benchmark.runners import shortconv
    spec = load_cell("lfm2-8b-a1b-16l.assist-batch-256")
    harness.start(1)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    shortconv.hybrid.CHECK_REQUESTS = a.requests
    traffic = traffic_mod.load(spec["traffic_path"])
    traffic["kind"] = "serve-closed"
    served = shortconv.Served(spec["config"], a.seed)
    try:
        served.warm(traffic, a.seed)
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "traffic.json")
            with open(path, "w") as f:
                json.dump(traffic, f)
            raw = served.drive(path, a.seed, a.seconds, False)
        sample = served.window_sample(raw)
        served.release_pools()
        print(f"sampled {len(sample)} requests, slots "
              f"{sorted(s[0] for s in sample)}, prompts "
              f"{[len(s[1]) for s in sample]}", flush=True)
        for wrong in (None, *(a.only if a.only is not None
                              else shortconv_decoder.WRONG)):
            t0 = time.monotonic()
            c = served.check_served(sample, wrong=wrong, detail=True)
            c.update(seed=a.seed, reference=wrong or "right",
                     seconds=time.monotonic() - t0)
            with open(a.out, "a") as f:
                f.write(json.dumps(c) + "\n")
            print(json.dumps({k: v for k, v in c.items()
                              if k not in ("gaps", "margins")}), flush=True)
    finally:
        served.close()


if __name__ == "__main__":
    main()
