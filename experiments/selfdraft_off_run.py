"""The self-drafting cell's configuration served WITHOUT drafting (PR 53):
one run of ``joyai-llm-flash-8l-ep2.agent-turns-64``'s traffic with
``speculative: off`` (the module's weights stay on the chip, its layer of
the pool is nobody's, every decode step is ``decode_scan``'s one row a
slot), so that the break-even acceptance stands beside the cell:

    break-even a = step with drafting / step without - 1

    chiprun --timeout 1500 -- python experiments/selfdraft_off_run.py \
        --seed 3300005321 --seconds 51 --trace 1

A builder's run, not a cell: it prints the end-to-end metrics, the decode
step's device ms and the engine's wall ms a step, and holds no check (no
draft is served, so ``correct`` is false by construction)."""

import argparse
import json
import os
import sys
import tempfile
import time

T0 = time.monotonic()
sys.path.insert(0, os.getcwd())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, default=1)
    a = ap.parse_args()

    from benchmark import end_to_end, layer_metrics
    from benchmark.run import load_cell, result_line
    from benchmark.runners import selfdraft
    spec = load_cell("joyai-llm-flash-8l-ep2.agent-turns-64")
    config = dict(spec["config"], serve=dict(
        spec["config"]["serve"], speculative="off"))
    traffic = json.loads(open(spec["traffic_path"]).read())
    traffic["sampling"] = {k: v for k, v in traffic["sampling"].items()
                           if k != "return_draft_tokens"}
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "agent-turns-64.json")
        with open(path, "w") as f:
            json.dump(traffic, f)
        run = selfdraft.run(spec["cell"], config, path, a.seed, a.seconds,
                            bool(a.trace), T0)
    line = result_line(run, spec["end_to_end"], end_to_end.load, False)
    out = {k: v["value"] for k, v in line["metrics"].items()}
    out["attempted"] = line["attempted"]
    out["memory_peak_bytes"] = line["device"]["memory_peak_bytes"]
    for name in ("serve_programs.decode_step_device_ms",
                 "engine.wall_ms_per_decode_step",
                 "engine.prefill_stall_ms_per_decode_step",
                 "kernels.mla_attention_ms_per_decode_step"):
        v = layer_metrics.load(name).read(run)
        if v is not None:
            out[name] = v
    out["decode_scope_s"] = run["trace"].get("decode_scope_s")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
