"""The state-space mixer's one-token update alone on the chip: the Pallas
kernel over the state pool against the XLA form, at both cells' pools.

    chiprun -- python experiments/ssm_decode_alone.py [--out FILE]
        [--block-bytes N ...]

Two pools at the published widths: the parallel cell's (falcon-h1-34b-4l:
4 layers x 128 slots x 32 heads of [128, 256] float32, 2 groups: 2.15 GB)
and the hybrid cell's (nemotron-3-nano-30b-a3b-14l-ep2: 6 layers x 64 slots
x 64 heads of [64, 128], 8 groups: 0.8 GB). One jitted program walks the
layers of the donated pool ``ROUNDS`` times, so the host clock around
``block_until_ready`` reads the update and not the dispatch (the fastest of
five batches). For each share of live slots (all, 3 of 4 scattered, half,
one, none) and each form: us a layer, GB/s of the LIVE slots' state moved
once in and once out (what ``flops_parallel.state_step_bytes`` counts) and
the share of 819 GB/s; the kernel's ``y`` and live states against the XLA
form's (1e-5), dead slots' states and the other layers bit for bit.
``--block-bytes`` times the kernel at other block sizes too. Fails (exit 2)
without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

PKG = "distributed_llm_training_and_inference_system_tpu"
# (layers, slots, heads, head_dim, state, groups)
POOLS = {"parallel": (4, 128, 32, 128, 256, 2),
         "hybrid": (6, 64, 64, 64, 128, 8)}
ROUNDS, BATCHES = 4, 5
HBM_GBPS = 819.0


def operands(key, S, nh, P, N, G):
    k = jax.random.split(key, 6)
    return (jax.random.normal(k[0], (S, nh, P), jnp.bfloat16),
            jax.nn.softplus(jax.random.normal(k[1], (S, nh))),
            -jnp.exp(0.5 * jax.random.normal(k[2], (nh,))),
            jax.random.normal(k[3], (S, G, N), jnp.bfloat16),
            jax.random.normal(k[4], (S, G, N), jnp.bfloat16),
            jax.random.uniform(k[5], (nh,), minval=0.5, maxval=1.5))


def live_patterns(S: int) -> dict:
    slots = np.arange(S)
    return {"all": np.ones(S, bool), "3_of_4": slots % 4 != 1,
            "half": slots % 2 == 0, "one": slots == S // 2,
            "none": np.zeros(S, bool)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/pr50/ssm_decode_alone.json")
    ap.add_argument("--block-bytes", type=int, nargs="*", default=[])
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: nothing measured", file=sys.stderr)
        return 2
    from importlib import import_module
    ssm = import_module(f"{PKG}.ops.ssm")

    def xla_step(ops, pool, layer, ok):
        old = pool[layer]
        y, new = ssm.ssm_decode(*ops, old)
        new = jnp.where(ok[:, None, None, None], new, old)
        return jnp.where(ok[:, None, None], y, 0), pool.at[layer].set(new)

    def kernel_step(ops, pool, layer, ok):
        return ssm.ssm_decode_pool(*ops, pool, layer, ok)

    def walk(step, Lm):
        def program(ops, pool, ok):
            def body(carry, layer):
                pool, acc = carry
                y, pool = step(ops, pool, layer, ok)
                return (pool, acc + y.astype(jnp.float32)), None
            (pool, acc), _ = jax.lax.scan(
                body, (pool, jnp.zeros(ops[0].shape, jnp.float32)),
                jnp.tile(jnp.arange(Lm), ROUNDS))
            return pool, acc
        return jax.jit(program, donate_argnums=(1,))

    results = {"device": str(jax.devices()[0].device_kind), "cases": []}
    bad = []
    for name, (Lm, S, nh, P, N, G) in POOLS.items():
        ops = operands(jax.random.PRNGKey(1), S, nh, P, N, G)
        slot_bytes = nh * P * N * 4
        for pattern, ok_np in live_patterns(S).items():
            ok = jnp.asarray(ok_np)
            # -- results: one layer, each form on its own copy of the pool
            fresh = lambda: 0.3 * jax.random.normal(
                jax.random.PRNGKey(2), (Lm, S, nh, P, N), jnp.float32)
            once = lambda step: jax.jit(
                lambda ops, pool, ok: step(ops, pool, jnp.int32(1), ok),
                donate_argnums=(1,))
            y_x, pool_x = once(xla_step)(ops, fresh(), ok)
            y_k, pool_k = once(kernel_step)(ops, fresh(), ok)
            diff = lambda a, b: float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32))))
            same = bool(jnp.array_equal(
                jnp.where(ok[:, None, None, None], 0, pool_k[1]),
                jnp.where(ok[:, None, None, None], 0, pool_x[1]))
                and jnp.array_equal(pool_k[jnp.asarray([0, 2])],
                                    pool_x[jnp.asarray([0, 2])]))
            y_d, s_d = diff(y_k, y_x), diff(pool_k[1], pool_x[1])
            y_max = float(jnp.max(jnp.abs(y_x.astype(jnp.float32))))
            del pool_x, pool_k
            if not same or s_d > 1e-5 or y_d > 1e-2 * max(y_max, 1.0):
                bad.append(f"{name} {pattern}: y {y_d:.2e} of {y_max:.1f}, "
                           f"state {s_d:.2e}, untouched {same}")
            # -- times
            row = {"pool": name, "live": pattern, "live_slots": int(ok_np.sum()),
                   "y_diff": y_d, "state_diff": s_d, "untouched_same": same}
            forms = {"xla": (xla_step, None), "kernel": (kernel_step, None)}
            for bb in a.block_bytes:
                forms[f"kernel_{bb >> 10}k"] = (kernel_step, bb)
            for form, (step, bb) in forms.items():
                was = ssm._BLOCK_BYTES
                if bb:
                    ssm._BLOCK_BYTES = bb
                try:
                    fn = walk(step, Lm)
                    pool, acc = fn(ops, fresh(), ok)
                    jax.block_until_ready(acc)
                    best = float("inf")
                    for _ in range(BATCHES):
                        t0 = time.perf_counter()
                        pool, acc = fn(ops, pool, ok)
                        jax.block_until_ready(acc)
                        best = min(best, time.perf_counter() - t0)
                    del pool
                except Exception as e:       # a block size Mosaic refuses
                    row[form] = {"error": str(e)[:300]}
                    continue
                finally:
                    ssm._BLOCK_BYTES = was
                us = best / (Lm * ROUNDS) * 1e6
                gbps = 2 * int(ok_np.sum()) * slot_bytes / us / 1e3
                row[form] = {"us_a_layer": us, "live_gbps": gbps,
                             "hbm_share": gbps / HBM_GBPS}
            results["cases"].append(row)
            print(json.dumps(row), flush=True)
    results["failures"] = bad
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(results, f, indent=1)
    if bad:
        print("WRONG:\n  " + "\n  ".join(bad), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
