"""Where one benchmark run's `setup_s` went, by the program's own account.

    chiprun -- python experiments/startup_table.py --workload <cell> --seed <n> \
        [--seconds 51] [--trace 1] [--out FILE]

One run of a cell through the benchmark's own runner (what `benchmark/run.py`
does), then BOTH of its result lines' metrics from that one run (`setup_s`
is end to end and the `startup.*` metrics per layer, so `run.py` prints them
in different runs), the `llmctl.startup.*` phases as they stood at the
window's first instant, and the compile ledger: one row a program's first
call before the window, the compiles under no program span summed in one
`(unscoped)` row. The last line checks the sum: named parts + engine work +
unattributed = `setup_s`.

A cold table against a warm one: point `JAX_COMPILATION_CACHE_DIR` at an
empty directory for the first run and run again with the same directory.
Exit 2 with no TPU, as `benchmark/run.py`.
"""

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from importlib import import_module  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import (end_to_end, harness, layer_metrics,  # noqa: E402
                       startup_counters)
from benchmark.run import load_cell, result_line  # noqa: E402

LEDGER = ("s", "trace_s", "lower_s", "compile_s", "cache_read_s", "run_s")


def table(run: dict) -> dict:
    snap = startup_counters.snapshot(run)
    scoped = [p for p in snap["programs"]
              if p["name"] != startup_counters.UNSCOPED]
    loose = [p for p in snap["programs"]
             if p["name"] == startup_counters.UNSCOPED]
    rows = [dict(p, t0=p["t0"] - snap["import_t0"]) for p in scoped]
    if loose:
        rows.append({"name": f"{startup_counters.UNSCOPED} x{len(loose)}",
                     "cache_hit": all(p["cache_hit"] for p in loose),
                     "misses": sum(p["cache_hit"] is False for p in loose),
                     **{k: sum(p[k] for p in loose) for k in LEDGER}})
    named = startup_counters.named_seconds(run)
    work = (startup_counters.engine_work_seconds(run)
            if run["kind"] == "serve" else None)
    return {"setup_s": run["setup_s"],
            "process_start_to_import_t0": snap["import_t0"] - T_PROCESS_START,
            "phases": snap["phases"], "programs": rows,
            "named_s": named, "engine_work_s": work,
            "unattributed_s": run["setup_s"] - named - (work or 0.0)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    spec = load_cell(a.workload)
    kind = json.loads(Path(spec["traffic_path"]).read_text())["kind"]
    runner = import_module(f"benchmark.runners.{kind.split('-')[0]}")
    try:
        run = runner.run(spec["cell"], spec["config"], spec["traffic_path"],
                         a.seed, a.seconds, bool(a.trace), T_PROCESS_START)
    except harness.NoAccelerator as e:
        print(f"startup_table: {e}", file=sys.stderr)
        return 2
    line = result_line(run, spec["end_to_end"], end_to_end.load, False)
    if a.trace:
        traced = result_line(run, spec["per_layer"], layer_metrics.load, True)
        line["metrics"].update(traced["metrics"])
    else:       # the start-up metrics need no trace
        line["metrics"].update(result_line(
            run, [m for m in spec["per_layer"] if m["moves"] == "setup_s"],
            layer_metrics.load, False)["metrics"])
    out = {"workload": a.workload, "seed": a.seed, "traced": bool(a.trace),
           "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
           "correct": line["correct"], "failed": line["failed"],
           "device": line["device"],
           "metrics": {k: v["value"] for k, v in line["metrics"].items()},
           "startup": table(run)}
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(json.dumps(out, indent=1))
    t = out["startup"]
    print(f"{a.workload} seed {a.seed}: setup_s {t['setup_s']:.2f} = named "
          f"{t['named_s']:.2f} + engine work {t['engine_work_s'] or 0:.2f} + "
          f"unattributed {t['unattributed_s']:.2f}", file=sys.stderr)
    for name, cell in sorted(t["phases"].items(), key=lambda kv: -kv[1]["s"]):
        print(f"  {name:<28} {cell['s']:8.2f} s x{cell['n']}", file=sys.stderr)
    for p in t["programs"]:
        print(f"  {p['name']:<28} {p['s']:8.2f} s  trace {p['trace_s']:.2f} "
              f"lower {p['lower_s']:.2f} compile {p['compile_s']:.2f} cache "
              f"read {p['cache_read_s']:.2f} run {p['run_s']:.2f} "
              f"{'hit' if p['cache_hit'] else 'MISS'}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
