"""One prefill's host path on the chip, from a trace (ROADMAP A2a).

    chiprun -- python experiments/prefill_host_path.py [--tree DIR]

Serves `llmctl trace capture --serve`'s rounds (gpt-1b, 8 slots, 16 requests
a round) under the profiler and reads, for every prefill program on the
device, the milliseconds between the end of the decode or prefill program
before it and its own start: what the engine thread spends on a prefill
while the device stands idle (page bookkeeping, arguments, the dispatch,
and before PR 32 eight one-operation programs and a blocking key fetch).
`--tree DIR` imports the package from another checkout (the parent commit
unpacked by `git archive`), for a before beside the after. Exit 2 with no
TPU: a host gap beside a CPU "device" says nothing.
"""

import argparse
import json
import os
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=ROOT)
    ap.add_argument("--model", default="gpt-1b")
    ap.add_argument("--seconds", type=float, default=5.0)
    a = ap.parse_args()
    sys.path.insert(0, os.path.abspath(a.tree))
    import jax
    from distributed_llm_training_and_inference_system_tpu.cli.commands import (
        trace)
    if jax.default_backend() != "tpu":
        print("prefill_host_path: no TPU", file=sys.stderr)
        return 2
    out = tempfile.mkdtemp(prefix="prefill_host_path_")
    trace.capture_serve(a.model, out, a.seconds)
    profile = trace.load_profile(trace.find_xplane(out))
    programs = sorted(profile["devices"][min(profile["devices"])]["programs"],
                      key=lambda p: p[1])
    gaps, tiny, last_end = [], 0, None
    for name, start, end in programs:
        if "prefill" in name and last_end is not None:
            gaps.append(1e3 * (start - last_end))
        if "prefill" in name or "decode" in name:
            last_end = end
        else:
            tiny += 1              # a one-operation program in between
    host = [1e3 * (e - s) for spans in profile["host_spans"].values()
            for n, s, e in spans if n.endswith("engine.prefill.host")]
    print(json.dumps({
        "tree": os.path.abspath(a.tree), "model": a.model,
        "prefills": len(gaps), "other_programs": tiny,
        "ms_from_previous_program_to_prefill": {
            "median": statistics.median(gaps), "mean": statistics.fmean(gaps),
            "p90": statistics.quantiles(gaps, n=10)[-1]},
        "prefill_host_span_ms": {"median": statistics.median(host),
                                 "mean": statistics.fmean(host)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
