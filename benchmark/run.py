"""The benchmark's command: one run of one cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell from ``BENCHMARK.json``, picks the runner by the traffic
file's ``kind`` (``serve-open`` -> ``runners/serve.py``; the same word names
the run's family file, ``families/serve.py``), and prints as its
last line one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` and, traced, ``breakdown``. Fails (exit 2,
no result) without a TPU.
"""

from __future__ import annotations

import time

_T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from importlib import import_module  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# run as a script, sys.path[0] is benchmark/: make it the checkout instead,
# so that ``benchmark`` and the program's package import as packages
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != ROOT / "benchmark"]
sys.path.insert(0, str(ROOT))

from benchmark import end_to_end, facts, harness, layer_metrics  # noqa: E402


def load_cell(workload: str, manifest: dict | None = None) -> dict:
    """The cell with its configuration, its traffic file and the metrics it
    reports, from ``BENCHMARK.json``."""
    m = manifest or json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in m["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in m["configs"] if c["name"] == cell["config"])

    def applies(metric):
        return workload in metric.get("workloads", [workload])
    return {
        "cell": cell,
        "config": json.loads((ROOT / cfg_entry["file"]).read_text()),
        "traffic_path": str(ROOT / "benchmark" / "traffic"
                            / (cell["traffic"] + ".json")),
        "end_to_end": [x for x in m["end_to_end"] if applies(x)],
        "per_layer": [x for x in m["per_layer"] if applies(x)],
    }


def result_line(run: dict, metrics: list, load, traced: bool) -> dict:
    """The contract's last line from a runner's raw run."""
    values = {}
    for metric in metrics:
        v = load(metric["name"]).read(run)
        if v is not None:          # a reader that found nothing to read
            values[metric["name"]] = {"value": float(v),
                                      "unit": metric["unit"]}
    if run["kind"] == "serve":
        attempted = len(facts.window_requests(run))
        failed = len(facts.failed_requests(run))
        correct = facts.serve_correct(run)
        early = len(facts.stopped_early(run))
        print(f"[bench] {early} of {attempted} requests stopped before "
              f"max_tokens (EOS from random weights)", file=sys.stderr)
    else:
        attempted = sum(n for _, _, n in run["blocks"])
        failed = 0
        correct = bool(run["check"]["ok"] and run["all_finite"])
    device = dict(run["device"], memory_peak_bytes=run["memory_peak_bytes"])
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": values, "device": device,
            "compiled_in_window": run["compiled_in_window"],
            "check": run["check"]}
    if traced and run["trace"]:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                             "idle_gaps": run["trace"]["idle_gaps"]}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default=None, metavar="FILE",
                    help="also write the run's raw stamps, counters and "
                         "trace listing as JSON (for reading by hand)")
    a = ap.parse_args(argv)
    spec = load_cell(a.workload)
    kind = json.loads(Path(spec["traffic_path"]).read_text())["kind"]
    family = kind.split('-')[0]
    runner = import_module(f"benchmark.runners.{family}")
    try:
        run = runner.run(spec["cell"], spec["config"], spec["traffic_path"],
                         a.seed, a.seconds, bool(a.trace), _T_PROCESS_START)
    except harness.NoAccelerator as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 2
    # which benchmark/families/<name>.py the shared per-layer readers ask
    # for this run's bytes and counters
    run["runner"] = family
    if a.dump:
        Path(a.dump).parent.mkdir(parents=True, exist_ok=True)
        Path(a.dump).write_text(json.dumps(run, default=str))
    if a.trace:
        line = result_line(run, spec["per_layer"], layer_metrics.load, True)
    else:
        line = result_line(run, spec["end_to_end"], end_to_end.load, False)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
