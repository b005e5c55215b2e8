"""Readings for the diffusion cell's correctness check (PR 42): what the
check of ``benchmark/runners/diffusion.py`` reads, both limits, on the
sample of ONE window of the cell's own traffic (one server a process), for
the RIGHT model and for the wrong variants:

- four wrong REFERENCES against the right server's window: the CAUSAL mask
  (the mechanism itself), no per-head q/k norms, logits shifted by one,
  matmul operands rounded to float8 (the nearest precision under bfloat16);
- ``--skip-commit``: a wrong SERVER's window against the right reference
  (``never_storing`` below put in place of the engine's ``denoise_scan``
  before its program is traced): no finished block's K/V are ever stored,
  and the K/V of its last half-masked window stay in the pages. (Since PR
  47 a finished block is stored by the next block's first denoise forward;
  ``runners/diffusion.py commit_skipping`` wraps the form in which the
  commit was a forward of its own, and finds none to skip.)

    chiprun -- python experiments/diffusion_check_readings.py --seed N
    chiprun -- python experiments/diffusion_check_readings.py --seed N \
        --skip-commit

Writes one JSON line a (seed, variant), every token's and step's gap in it,
to ``chiprun_out/pr42/check_readings.jsonl`` and prints it without them."""

import argparse
import json
import os
import sys
import time
from importlib import import_module

sys.path.insert(0, os.getcwd())


def never_storing(denoise_scan):
    """The WRONG server the check is shown to catch (here and in
    tests/test_sdar.py; never by the program): ``denoise_scan`` a forward
    at a time with the window's first half never live, so no finished
    block is stored."""
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    counts = import_module(f"{harness.PKG}.serve.decode").DENOISE_COUNTS

    def scan(params, window, starts, k_pages, v_pages, *rest, **kw):
        *args, cfg, num_steps = rest

        def one(carry, _):
            (*window, pending), starts, kp, vp, *sums = carry
            (window, starts, kp, vp, *new), out = denoise_scan(
                params, (*window, jnp.zeros_like(pending)), starts, kp, vp,
                *args, cfg, 1, **kw)
            return (window, starts, kp, vp,
                    *[a + b for a, b in zip(sums, new)]), out[0]

        zeros = [jnp.zeros((cfg.moe.stats_size,), jnp.int32)] \
            if cfg.is_moe else []
        zeros.append(jnp.zeros((len(counts),), jnp.int32))
        return jax.lax.scan(one, (window, starts, k_pages, v_pages, *zeros),
                            None, length=num_steps)
    return scan


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--skip-commit", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--out", default="chiprun_out/pr42/check_readings.jsonl")
    a = ap.parse_args()

    from benchmark import harness
    from benchmark.run import load_cell
    from benchmark.runners import diffusion
    spec = load_cell("sdar-30b-a3b-7l.diffusion-batch-64")
    diffusion.require_diffusion_support(spec["config"])
    t0 = time.monotonic()
    device = harness.start(1)
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    if a.skip_commit:
        engine = import_module(f"{harness.PKG}.serve.engine")
        engine.denoise_scan = never_storing(engine.denoise_scan)
    served = diffusion.Served(spec["config"], a.seed)
    variants = [None] if a.skip_commit else [None, *diffusion.VARIANTS]
    if a.only:
        variants = [v for v in variants if (v or "right") in a.only.split(",")]
    try:
        raw, sample = diffusion.window(
            served, spec["cell"], spec["traffic_path"], a.seed, a.seconds,
            False, t0, device)
        after, before = raw["stats"]["after"], raw["stats"]["before"]
        print(json.dumps({"window_requests": len(sample), "diffusion": {
            k: v - before["diffusion"][k]
            for k, v in after["diffusion"].items() if isinstance(v, int)}}),
            flush=True)
        with open(a.out, "a") as f:
            for variant in variants:
                t1 = time.monotonic()
                reading = served.check_served(sample, variant, keep_gaps=True)
                reading.update(
                    seed=a.seed, seconds=round(time.monotonic() - t1, 1),
                    server="skip_commit" if a.skip_commit else "right",
                    reference=variant or "right")
                f.write(json.dumps(reading) + "\n")
                f.flush()
                print(json.dumps({k: v for k, v in reading.items() if k not in
                                  ("token_gaps", "row_gaps", "contexts")}),
                      flush=True)
                harness.mark(f"read {variant or 'right'}", t0)
    finally:
        served.close()


if __name__ == "__main__":
    main()
