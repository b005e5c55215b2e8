"""The latent paged-attention kernel alone on the chip: the sweep of page
size x pages a loop step (G) x query tokens a slot (T).

    chiprun -- python experiments/mla_kernel_alone.py [--out FILE]

The doc-qa cell's decode shape at the published widths: 64 slots, 32 heads,
a 7-layer latent pool of 640-wide bf16 rows (576 + padding), ~12.9k live
tokens a slot (lengths 8,192-16,384, drawn once), block tables as wide as
``max_seq_len`` 17,408 needs. A case is one jitted program that scans the
layer index over the pool, so a dispatch holds 7 x ROUNDS calls and the host
clock around ``block_until_ready`` reads the kernel, not the dispatch (the
fastest of five batches). ``G`` is what ``mla._pages_a_step`` reads off a
call; the sweep sets it by replacing that function, and ``_PAGES_AHEAD``
likewise (the ring holds ``G * (ahead + 1)`` buffers). Cases: pages of 64 /
128 / 256 rows at G = 1 | 2 | 4 (4 not at 256: 1.3 MB a step) and T = 1 | 2;
at 256 rows also T = 4 / 8 / 16 (where the tile's rows stop gaining) and 1
or 2 steps ahead. Reported a case: us a call, live pages, us a page, GB/s of
live pages read once, the share of 819 GB/s, the share of loop steps that
held a full group, and against the XLA twin at one small case the largest
difference. Also, a page size, the window kernel at the tile that keeps G =
1 (``mla_paged_attention_mq``): one slot, a 1,024-token chunk at a
12,288-token context. Fails (exit 2) without a TPU.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

PKG = "distributed_llm_training_and_inference_system_tpu"
B, N, W, R, L, MAX_SEQ = 64, 32, 640, 512, 7, 17408
POOL_TOKENS = 330_000
ROUNDS, BATCHES = 2, 5
HBM_GBPS = 819.0


def case(ps: int, lengths: np.ndarray, rng):
    maxp, n_pages = MAX_SEQ // ps, POOL_TOKENS // ps
    tables = np.zeros((len(lengths), maxp), np.int32)
    # the cell's documents are SHARED between slots (8 questions each):
    # 16 page chains, a slot reads one of them
    docs = []
    used = 1
    for i in range(16):
        n = -(-int(lengths[i % len(lengths)]) // ps)
        docs.append(np.arange(used, used + n) % (n_pages - 1) + 1)
        used += n
    for slot, length in enumerate(lengths):
        n = -(-int(length) // ps)
        chain = docs[slot % 16]
        tables[slot, :n] = np.resize(chain, n)
    return tables, n_pages


def time_call(fn, args, calls: int) -> float:
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(BATCHES):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e6


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/pr64/mla_kernel_alone.json")
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: nothing measured", file=sys.stderr)
        return 2
    from importlib import import_module
    mla = import_module(f"{PKG}.ops.mla_paged_attention")
    read_off_the_call, ahead_0 = mla._pages_a_step, mla._PAGES_AHEAD
    rng = np.random.default_rng(0)
    lengths = np.clip(np.rint(rng.lognormal(np.log(12288), 0.25, B)),
                      8192, 16384).astype(np.int32) + rng.integers(
                          40, 600, B).astype(np.int32)
    results = {"lengths_mean": float(lengths.mean()), "cases": []}

    def attend(q, pool, tables, starts, **kw):
        return mla.mla_paged_attention(q, pool, tables, starts, scale=0.14,
                                       value_width=R, **kw)

    for ps in (64, 128, 256):
        tables, n_pages = case(ps, lengths, rng)
        pool = jax.random.normal(jax.random.PRNGKey(ps),
                                 (L, n_pages, 1, ps, W), jnp.bfloat16)
        pages = np.array([-(-int(n) // ps) for n in lengths])
        live = int(pages.sum())
        small_t = jnp.asarray(tables[:4, :1024 // ps + 1])
        cases = [(T, g, ahead_0) for T in (1, 2)
                 for g in ((1, 2, 4) if ps < 256 else (1, 2))]
        if ps == 256:
            cases += [(T, g, ahead_0) for T in (4, 8, 16) for g in (1, 2)]
            cases += [(T, 2, ahead) for T in (1, 2) for ahead in (1, 2)]
        for T, g, ahead in cases:
            mla._pages_a_step = lambda *_, g=g: g
            mla._PAGES_AHEAD = ahead
            q = jax.random.normal(jax.random.PRNGKey(1), (B, T, N, W),
                                  jnp.bfloat16)

            def run(q, pool, tables, starts):
                def body(acc, i):
                    o = attend(q, pool, tables, starts, layer=i % L)
                    return acc + o.astype(jnp.float32), None
                acc, _ = jax.lax.scan(
                    body, jnp.zeros((B, T, N, R), jnp.float32),
                    jnp.arange(L * ROUNDS, dtype=jnp.int32))
                return acc
            args = (q, pool, jnp.asarray(tables), jnp.asarray(lengths - T))
            us = time_call(jax.jit(run), args, L * ROUNDS)
            gbps = live * ps * W * 2 / (us * 1e-6) / 1e9
            row = {"page_size": ps, "T": T, "group": g, "ahead": ahead,
                   "us_a_call": us, "live_pages": live,
                   "us_a_page": us / live, "live_gb_per_s": gbps,
                   "hbm_share": gbps / HBM_GBPS,
                   "full_group_share": float((pages // g).sum()
                                             / (-(-pages // g)).sum())}
            # against the XLA twin: 4 slots of ~1k tokens, odd and even
            # page counts, one page, length 0
            small_s = jnp.asarray([1000 - T, 517, 64, 0], jnp.int32)
            outs = [np.asarray(attend(q[:4], pool, small_t, small_s, layer=2,
                                      impl=impl).astype(jnp.float32))
                    for impl in ("pallas", "gather")]
            row["max_abs_diff_vs_twin"] = float(
                np.abs(outs[0] - outs[1]).max())
            row["twin_max_abs"] = float(np.abs(outs[1]).max())
            print(json.dumps(row), flush=True)
            results["cases"].append(row)
        mla._pages_a_step, mla._PAGES_AHEAD = read_off_the_call, ahead_0
        # the window kernel as the engine's chunk program calls it: one
        # slot, a 1,024-token chunk at 12,288
        qw = jax.random.normal(jax.random.PRNGKey(2), (1, 1024, N, W),
                               jnp.bfloat16)
        row = {"page_size": ps, "group": mla._tiling(qw, pool)[1],
               "chunk_1024_at_12288_us": time_call(
                   jax.jit(functools.partial(attend, layer=3)),
                   (qw, pool, jnp.asarray(tables[:1]),
                    jnp.asarray([12288], jnp.int32)), 1)}
        print(json.dumps(row), flush=True)
        results["cases"].append(row)
        del pool
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
