"""The diffusion cell's forward alone on the chip, at 256 and at 512 window
rows: ms a forward and where they go (PERF.md 6, PR 47: what the commit that
rides the next block's first denoise forward costs a forward).

    chiprun -- python experiments/denoise_forward_alone.py

``sdar-30b-a3b-7l`` (``benchmark/configs``) at its cell's shapes: 64 slots,
2,179 pages of 64 tokens a layer, contexts of 3 to 11 pages, weights made
on the device, 8 forwards a dispatch. A forward is ``extend_step_forward``
with the head's mask-token guard and the sampler behind it, as
``serve/decode.py denoise_scan`` chains them (no transfer rule: the rows'
tokens stay, so every forward of a case does the same work). Cases:

- ``256 rows``: a window of ONE block a slot, every row live and under the
  head: the forward before PR 47, a commit forward included;
- ``512 rows, N stores``: a window of two blocks a slot, the head over the
  second alone; the first half is live in N of the 64 slots (0: no slot
  stores a block; 16: a quarter do, what the cell runs at 4 denoising
  steps; 64: all).

Times are the host clock around ``block_until_ready`` over chained
dispatches (the fastest of ``BATCHES`` batches of ``REPS``); the scopes come
from one traced batch, reduced by the diffusion runner's own reader. Fails
(exit 2) without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import defaultdict
from importlib import import_module

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, trace_reduce
from benchmark.runners import diffusion, hybrid

PKG = "distributed_llm_training_and_inference_system_tpu"
CONFIG = "sdar-30b-a3b-7l"
B, MAXP, PS, NP = 64, 32, 64, 2179
K = 8
REPS, BATCHES = 6, 4


def program(cfg, blocks: int):
    """``K`` forwards of every slot's window of ``blocks`` blocks, the head
    and the sampler over the last block."""
    decode = import_module(f"{PKG}.serve.decode")
    sampling = import_module(f"{PKG}.serve.sampling")
    Bd, mask_id = cfg.diffusion.block_length, cfg.diffusion.mask_token_id

    def forwards(params, kp, vp, tokens, starts, ok, tables, keys, temp,
                 top_k, top_p):
        def one(carry, _):
            kp, vp, drawn = carry
            with jax.named_scope("denoise_step"):
                out = decode.extend_step_forward(
                    params, tokens, starts - (blocks - 1) * Bd, kp, vp,
                    tables, cfg, write_ok=ok, return_moe_stats=True,
                    head_from=(blocks - 1) * Bd)
                logits = out.logits.at[..., mask_id].set(-jnp.inf)
                x0, _ = sampling.sample_tokens_with_prob(
                    logits.reshape(B * Bd, -1),
                    jax.random.split(jax.random.wrap_key_data(keys[0]),
                                     B * Bd),
                    jnp.repeat(temp, Bd), jnp.repeat(top_k, Bd),
                    jnp.repeat(top_p, Bd))
            return (out.k_pages, out.v_pages, drawn + x0.sum()), None
        (kp, vp, drawn), _ = jax.lax.scan(
            one, (kp, vp, jnp.int32(0)), None, length=K)
        return drawn, kp, vp
    return jax.jit(forwards, donate_argnums=(1, 2))


def slots(cfg, rng, blocks: int, stores: int) -> dict:
    """Every slot mid-reply: its window's tokens (the finished block, then
    two fixed rows and two masks) and which of its rows are live."""
    Bd, mask_id = cfg.diffusion.block_length, cfg.diffusion.mask_token_id
    tables = np.zeros((B, MAXP), np.int32)
    free = iter(rng.permutation(np.arange(1, NP)))
    starts = np.zeros(B, np.int32)
    for slot in range(B):
        starts[slot] = PS * (3 + slot % 9) + Bd * (slot % 16)
        n = starts[slot] // PS + 1
        tables[slot, :n] = [next(free) for _ in range(n)]
    tokens = rng.integers(1, 30_000, (B, blocks * Bd)).astype(np.int32)
    tokens[:, -(Bd // 2):] = mask_id
    ok = np.ones((B, blocks * Bd), bool)
    if blocks == 2:
        ok[:, :Bd] = (np.arange(B) % (B // stores) == 0)[:, None] \
            if stores else False
    return {"tokens": tokens, "starts": starts, "ok": ok, "tables": tables}


def run_case(cfg, params, pools, blocks: int, stores: int):
    """(ms a forward, ms a forward by named scope, the pools)."""
    state = slots(cfg, np.random.default_rng(0), blocks, stores)
    args = [jnp.asarray(state[k]) for k in ("tokens", "starts", "ok",
                                            "tables")]
    args += [jnp.zeros((B, 2), jnp.uint32), jnp.zeros(B, jnp.float32),
             jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32)]
    exe = program(cfg, blocks).lower(params, *pools, *args).compile()
    # an XLA operation's scope, as ``diffusion.program_scope_seconds``
    # reads it: its instruction's ``op_name``, else the vocabulary's width
    table, vocab = {}, f",{cfg.vocab_size}]"
    for line in exe.as_text().splitlines():
        m = hybrid._INSTRUCTION.match(line)
        scope = m and (diffusion.scope_of([m.group(2)]) or (
            "vocab_rows" if vocab in line else None))
        if scope:
            table.setdefault(m.group(1), scope)

    def batch(pools):
        for _ in range(REPS):
            drawn, *pools = exe(params, *pools, *args)
        jax.block_until_ready(drawn)
        return pools
    pools = batch(pools)
    best = float("inf")
    for _ in range(BATCHES):
        t0 = time.perf_counter()
        pools = batch(pools)
        best = min(best, time.perf_counter() - t0)
    with harness.scratch_dir("denoise_trace_") as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        pools = batch(pools)
        jax.profiler.stop_trace()
        planes = trace_reduce.load(jax.profiler.ProfileData.from_file(
            trace_reduce.find_xplane(tmp)))
    scopes: dict = defaultdict(float)
    for plane in list(planes.values())[:1]:
        for name, s, e in trace_reduce.leaves(plane["ops"]):
            op = name.split(":", 1)[0]
            scopes[diffusion.scope_of([op]) or table.get(op)
                   or "other"] += e - s
    by_scope = {k: round(v / (REPS * K) * 1e3, 3) for k, v in sorted(
        scopes.items(), key=lambda kv: -kv[1])}
    return best / (REPS * K) * 1e3, by_scope, pools


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--stores", nargs="+", type=int, default=[0, 16, 64])
    ap.add_argument("--out", default="chiprun_out/denoise_forward_alone.json")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print("denoise_forward_alone: no TPU; a forward's time comes from "
              "the chip", file=sys.stderr)
        return 2
    schema = import_module(f"{PKG}.config.schema")
    gpt = import_module(f"{PKG}.models.gpt")
    with open(os.path.join("benchmark", "configs", f"{CONFIG}.json")) as f:
        cfg = schema.ModelConfig.from_dict(harness.model_dict(json.load(f)))
    params = jax.jit(lambda key: gpt.init(cfg, key, jnp.bfloat16))(
        jax.random.PRNGKey(0))
    shape = (cfg.num_layers, NP, cfg.num_kv_heads, PS, cfg.head_dim)
    pools = tuple(jax.random.normal(key, shape, jnp.bfloat16)
                  for key in jax.random.split(jax.random.PRNGKey(1)))
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind},
              "ms_a_forward": {}, "scope_ms_a_forward": {}}
    for label, blocks, stores in [("256 rows", 1, 0)] + [
            (f"512 rows, {n} stores", 2, n) for n in args.stores]:
        ms, by_scope, pools = run_case(cfg, params, pools, blocks, stores)
        result["ms_a_forward"][label] = round(ms, 3)
        result["scope_ms_a_forward"][label] = by_scope
        print(f"{label:24s} {ms:7.3f} ms a forward", flush=True)
        print("    by scope (ms a forward):", json.dumps(by_scope),
              flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result["ms_a_forward"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
