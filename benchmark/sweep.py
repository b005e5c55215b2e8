"""Find a serving cell's knee once, on the chip, and keep the sweep as data.

    python benchmark/sweep.py --workload mistral-7b-16l.chat --rates 4,6,8,10,12,14 \
        --seconds 20 --out benchmark/traffic/chat.sweep.json

One process holds the chip and one server; each rate is one stretch of the
cell's own open-loop traffic at that rate. The knee is the highest rate at
which the share of requests inside BOTH limits of the traffic file (TTFT and
TPOT) reaches ``limits.attainment`` and the queue is no longer at the end of
the stretch than in its middle. The cell's rate is 0.8 of it, rounded to
0.5 req/s, written into the traffic file by hand. A later ``benchmark`` PR
runs this again to see whether the knee has moved.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != ROOT / "benchmark"]
sys.path.insert(0, str(ROOT))

from benchmark import facts, harness  # noqa: E402
from benchmark.run import load_cell  # noqa: E402
from benchmark.runners import serve  # noqa: E402
from benchmark.stats import percentile  # noqa: E402


def row(rate: float, raw: dict, limits: dict) -> dict:
    """One rate's line of the sweep."""
    ttft, tpot = facts.ttft_ms(raw), facts.tpot_ms(raw)
    reqs = facts.window_requests(raw)
    good = 0
    for r in reqs:
        if facts.failed(r) or not r["tokens"]:
            continue
        t1 = (r["chunks"][0] - r["due"]) * 1e3
        tp = ((r["chunks"][-1] - r["chunks"][0]) * 1e3
              / max(r["tokens"] - 1, 1))
        good += t1 <= limits["ttft_ms"] and tp <= limits["tpot_ms"]
    s = raw["stats"]
    w0, w1 = raw["window"]

    def queue(st):
        return st["queue_depth"] + st["active"]
    return {"rate_per_s": rate, "requests": len(reqs),
            "failed": len(facts.failed_requests(raw)),
            "share_within_limits": good / max(len(reqs), 1),
            "ttft_ms": {"p50": percentile(ttft, 50), "p95": percentile(ttft, 95)},
            "tpot_ms": {"p50": percentile(tpot, 50), "p95": percentile(tpot, 95)},
            "in_system_mid": queue(s["mid"]), "in_system_end": queue(s["after"]),
            "queue_mid": s["mid"]["queue_depth"],
            "queue_end": s["after"]["queue_depth"],
            "tokens_per_s": facts.tokens_in_window(raw) / (w1 - w0),
            "compiled_in_window": s["after"]["compiled_programs"]["total"]
            - s["before"]["compiled_programs"]["total"]}


def knee(rows: list, attainment: float) -> float | None:
    ok = [r["rate_per_s"] for r in rows
          if r["share_within_limits"] >= attainment
          and r["queue_end"] <= max(r["queue_mid"], 1)]
    return max(ok) if ok else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("benchmark/sweep.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    spec = load_cell(a.workload)
    try:
        device = harness.start(spec["cell"]["chips"])
    except harness.NoAccelerator as e:
        print(f"benchmark/sweep.py: {e}", file=sys.stderr)
        return 2
    served = serve.Served(spec["config"], a.seed)
    rows = []
    try:
        check = served.check_against_reference(a.seed)
        traffic = serve.traffic_mod.load(spec["traffic_path"])
        served.warm(traffic, a.seed)
        for rate in (float(x) for x in a.rates.split(",")):
            raw = served.drive(spec["traffic_path"], a.seed, a.seconds,
                               False, rate=rate)
            rows.append(row(rate, raw, traffic["limits"]))
            print(f"[sweep] {rows[-1]}", file=sys.stderr)
    finally:
        served.close()
    result = {"workload": a.workload, "device": device,
              "seconds_per_rate": a.seconds, "seed": a.seed,
              "limits": traffic["limits"], "reference_check": check,
              "rows": rows,
              "knee_rate_per_s": knee(rows, traffic["limits"]["attainment"])}
    Path(a.out).parent.mkdir(parents=True, exist_ok=True)
    Path(a.out).write_text(json.dumps(result, indent=1))
    print(json.dumps({k: result[k] for k in ("workload", "knee_rate_per_s")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
