"""The decode step alone on the chip with a prompt's piece riding it: ms a
step and the longest operations' us a call (PERF.md 6, PR 36: what
``InferenceEngine.RIDE_PAGES`` was chosen from).

    chiprun -- python experiments/ride_step_alone.py [--models dense moe]
    chiprun -- python experiments/ride_step_alone.py --models latent \
        --rows 256 512 --no-dead-rows

The benchmark's riding configurations (``benchmark/configs``) at their
cells' shapes, 8 steps a dispatch, weights made on the device.
``mistral-7b-16l`` and ``olmoe-1b-7b-10l``: 32 slots, 715 pages of 64
tokens a layer; 31 slots are resident at ``batch-64``'s lengths (~7 live
pages each); slot 31 holds the riding prompt. ``xing4.0-29b-a4b-7l`` (PR
41): 64 slots over ONE latent pool of 1,307 pages of 256; 63 slots are
resident behind one of 16 documents of 48 pages each (shared pages, as the
prefix cache leaves them) and a page of their own, ``doc-qa-64``'s ~12.9k
live tokens a slot; slot 63's prompt rides behind its document's pages, so
a piece is a window over ~49 pages. Cases: C = 0 (the program
without ``ride``: the parent's), and C = 64 / 128 / 256 rows with

- ``0 live, branch``: no step carries a piece; each step BRANCHES to the
  plain step (what ``chat`` runs),
- ``0 live, dead rows``: the same through ``ride_branch=False``: every step
  computes its C dead rows (what the branch saves),
- ``C live``: every step carries a full piece of C rows, each at the next
  page-aligned start of a 1,024-token prompt.

Times are the host clock around ``block_until_ready`` over chained
dispatches (the fastest of ``BATCHES`` batches of ``REPS``); the operations
come from one traced batch, reduced by the benchmark's own reader. Fails
(exit 2) without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, trace_reduce

PKG = "distributed_llm_training_and_inference_system_tpu"
CONFIGS = {"dense": "mistral-7b-16l", "moe": "olmoe-1b-7b-10l",
           "latent": "xing4.0-29b-a4b-7l"}
K = 8
REPS, BATCHES = 6, 4
DOC_PAGES, DOCS = 48, 16    # the latent cell's resident documents


class Shape:
    """A cell's slots and pages; the LAST slot's prompt rides."""

    def __init__(self, model: str):
        self.latent = model == "latent"
        self.B, self.MAXP, self.PS, self.NP = (
            (64, 68, 256, 1307) if self.latent else (32, 32, 64, 715))
        self.rider = self.B - 1
        # where the rider's first piece starts: behind its cached document
        self.cached = DOC_PAGES * self.PS if self.latent else 0


def model_config(name: str):
    from importlib import import_module
    schema = import_module(f"{PKG}.config.schema")
    with open(os.path.join("benchmark", "configs", f"{name}.json")) as f:
        config = json.load(f)
    if "kv_lora_rank" in config:      # (YaRN lives in a nested group)
        from benchmark.runners import latent
        return schema.ModelConfig.from_dict(latent.model_dict(config))
    return schema.ModelConfig.from_dict(harness.model_dict(config))


def slots(sh: Shape, rng) -> dict:
    """The resident slots mid-generation and the rider's pages."""
    tables = np.zeros((sh.B, sh.MAXP), np.int32)
    free = iter(rng.permutation(np.arange(1, sh.NP)))
    positions = np.zeros(sh.B, np.int32)

    def take(n):
        return [next(free) for _ in range(n)]
    if sh.latent:
        docs = [take(DOC_PAGES) for _ in range(DOCS)]
        for slot in range(sh.B):
            # a document's shared pages, then the slot's own
            tables[slot, :DOC_PAGES + 4] = docs[slot % DOCS] + take(4)
            positions[slot] = DOC_PAGES * sh.PS + 200 + 9 * slot
    else:
        for slot in range(sh.B - 1):
            n = 6 + (slot % 4 == 0)         # ~232 live pages, as batch-64
            tables[slot, :n + 1] = take(n + 1)
            positions[slot] = n * sh.PS - 17
        tables[sh.rider, :16] = take(16)
    stops = positions + 10_000
    stops[sh.rider] = 0                      # not resident: it rides
    # a token a slot (with the random pools below, the slots then route to
    # different experts, as a batch of different requests does)
    return {"tables": tables, "positions": positions, "stops": stops,
            "tokens": rng.integers(1, 30_000, sh.B).astype(np.int32)}


def pieces(sh: Shape, C: int, live: int, rng, vocab: int) -> np.ndarray:
    from importlib import import_module
    meta = import_module(f"{PKG}.serve.decode").PIECE_META
    rows = np.zeros((K, meta + C), np.int32)
    if live:
        for k in range(K):
            # consecutive page-aligned pieces of one 1,024-token prompt
            # (tail, behind the latent cell's cached document)
            rows[k, :meta] = (sh.rider, sh.cached + (k * C) % 1024, live, 0)
            rows[k, meta:meta + live] = rng.integers(1, vocab, live)
    return rows


def program(cfg, C: int, branch: bool):
    from importlib import import_module
    decode = import_module(f"{PKG}.serve.decode")

    def step(params, kp, vp, tokens, positions, tables, stops, keys, temp,
             top_k, top_p, ride=None):
        (toks, pos, kp, vp, *_), _ = decode.decode_scan(
            params, tokens, positions, kp, vp, tables, stops, keys, temp,
            top_k, top_p, cfg, K, return_moe_stats=True, ride=ride,
            ride_branch=branch)
        return toks, kp, vp
    return jax.jit(step, donate_argnums=(1, 2))


def run_case(fn, params, pools, state, ride, trace: bool):
    """(ms a step, {operation: (calls, us a call)} of the traced batch)."""
    B = len(state["tokens"])
    args = [jnp.asarray(state["tokens"]), jnp.asarray(state["positions"]),
            jnp.asarray(state["tables"]), jnp.asarray(state["stops"]),
            jnp.zeros((B, 2), jnp.uint32), jnp.zeros(B, jnp.float32),
            jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32)]
    if ride is not None:
        args.append(jnp.asarray(ride))
    kp, vp = pools

    def batch(kp, vp):
        for _ in range(REPS):
            toks, kp, vp = fn(params, kp, vp, *args)
        jax.block_until_ready(toks)
        return kp, vp
    kp, vp = batch(kp, vp)                  # compiles
    best = float("inf")
    for _ in range(BATCHES):
        t0 = time.perf_counter()
        kp, vp = batch(kp, vp)
        best = min(best, time.perf_counter() - t0)
    ops = {}
    if trace:
        with harness.scratch_dir("ride_trace_") as tmp:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=opts)
            kp, vp = batch(kp, vp)
            jax.profiler.stop_trace()
            planes = trace_reduce.load(jax.profiler.ProfileData.from_file(
                trace_reduce.find_xplane(tmp)))
        seen: dict = defaultdict(lambda: [0, 0.0])
        for plane in list(planes.values())[:1]:
            for name, s, e in trace_reduce.leaves(plane["ops"]):
                seen[name][0] += 1
                seen[name][1] += e - s
        top = sorted(seen.items(), key=lambda kv: -kv[1][1])[:12]
        ops = {name: [n, round(s / n * 1e6, 1), round(s / (REPS * K) * 1e3, 3)]
               for name, (n, s) in top}
    return best / (REPS * K) * 1e3, ops, (kp, vp)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", nargs="+", default=list(CONFIGS),
                    choices=list(CONFIGS))
    ap.add_argument("--rows", nargs="+", type=int, default=[64, 128, 256])
    ap.add_argument("--no-dead-rows", action="store_true",
                    help="leave the ride_branch=False programs out")
    ap.add_argument("--live", nargs="+", type=int, default=[],
                    help="also pieces of so many live rows (<= C)")
    ap.add_argument("--out", default="chiprun_out/ride_step_alone.json")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print("ride_step_alone: no TPU; a step time comes from the chip",
              file=sys.stderr)
        return 2
    from importlib import import_module
    gpt = import_module(f"{PKG}.models.gpt")
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind},
              "ms_a_step": {}, "ops": {}}
    for model in args.models:
        cfg, sh = model_config(CONFIGS[model]), Shape(model)
        params = jax.jit(lambda key: gpt.init(cfg, key, jnp.bfloat16))(
            jax.random.PRNGKey(0))
        if sh.latent:    # ONE pool of padded latent rows
            pools = (jax.random.normal(
                jax.random.PRNGKey(1),
                (cfg.layers_of("*"), sh.NP, 1, sh.PS, cfg.mla.page_width),
                jnp.bfloat16), None)
        else:
            shape = (cfg.num_layers, sh.NP, cfg.num_kv_heads, sh.PS,
                     cfg.head_dim)
            pools = tuple(jax.random.normal(key, shape, jnp.bfloat16)
                          for key in jax.random.split(jax.random.PRNGKey(1)))
        rng = np.random.default_rng(0)
        state = slots(sh, rng)
        cases = [("C=0", 0, 0, True)]
        for C in args.rows:
            cases += [(f"C={C}, 0 live, branch", C, 0, True),
                      (f"C={C}, 0 live, dead rows", C, 0, False),
                      *[(f"C={C}, {n} live", C, n, True)
                        for n in [*args.live, C] if n <= C]]
        programs = {}
        for label, C, live, branch in cases:
            if args.no_dead_rows and not branch:
                continue
            fn = programs.setdefault((C, branch), program(cfg, C, branch))
            ride = pieces(sh, C, live, rng, cfg.vocab_size) if C else None
            ms, ops, pools = run_case(fn, params, pools, state, ride,
                                      trace=True)
            key = f"{model} | {label}"
            result["ms_a_step"][key] = round(ms, 3)
            result["ops"][key] = ops
            print(f"{key:40s} {ms:7.3f} ms a step", flush=True)
            for name, (n, us, ms_step) in ops.items():
                print(f"    {name:48s} {n:5d} calls {us:8.1f} us a call "
                      f"{ms_step:7.3f} ms a step", flush=True)
        del params, pools
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result["ms_a_step"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
