"""Readings for the hybrid cell's correctness check (PR 31): what the
check of ``benchmark/runners/hybrid.py`` reads, on requests a window of the
cell's own traffic finished, for the RIGHT model and for seven wrong
references, on one seed (one server a process: a second does not fit
beside the first; loop over seeds in the shell).

    chiprun --timeout 3000 -- python experiments/hybrid_check_readings.py \
        --seed 3100000101 --seconds 30

Writes one JSON line a (seed, reference) to
``chiprun_out/pr31/window_check_readings.jsonl``, each with every sampled
token's gap (in reference-logit standard deviations) and routing margin,
so that a margin and a tolerance can be read off the lines afterwards
(``--summarise FILE`` prints, for each margin, the worst kept token of
each reference)."""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())

WRONG = (None, "float8", "float8_experts", "norm_before_gate",
         "softmax_scores", "bias_as_weight", "rope", "padding_in_state")
MARGINS = (0.0, 0.0005, 0.001, 0.002, 0.004, 0.008)


def summarise(path: str) -> None:
    for line in map(json.loads, open(path)):
        cells = []
        for m in MARGINS:
            kept = [g for g, d in zip(line["gaps"], line["margins"])
                    if d >= m]
            cells.append(f"{max(kept, default=0) / line['logit_std']:.3f}"
                         f" ({len(kept)})")
        print(line["seed"], f"{line['reference']:>17}", *cells, sep="  ")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--out",
                    default="chiprun_out/pr31/window_check_readings.jsonl")
    ap.add_argument("--summarise", metavar="FILE")
    a = ap.parse_args()
    if a.summarise:
        print("margins", *MARGINS, "(worst kept token in std, tokens kept)")
        return summarise(a.summarise)

    from benchmark import harness, traffic as traffic_mod
    from benchmark.run import load_cell
    from benchmark.runners import hybrid
    spec = load_cell("nemotron-3-nano-30b-a3b-14l-ep2.reason-batch-128")
    harness.start(1)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    hybrid.CHECK_REQUESTS = a.requests
    traffic = traffic_mod.load(spec["traffic_path"])
    traffic["kind"] = "serve-closed"
    served = hybrid.Served(spec["config"], a.seed)
    try:
        served.warm(traffic, a.seed)
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "traffic.json")
            with open(path, "w") as f:
                json.dump(traffic, f)
            raw = served.drive(path, a.seed, a.seconds, False)
        sample = served.window_sample(raw)
        print(f"sampled {len(sample)} requests, slots "
              f"{sorted(s[0] for s in sample)}, prompts "
              f"{[len(s[1]) for s in sample]}", flush=True)
        for wrong in WRONG:
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
