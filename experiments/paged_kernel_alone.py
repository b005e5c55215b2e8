"""The paged-attention kernel alone on the chip, microseconds a call.

    chiprun -- python experiments/paged_kernel_alone.py [--parent FILE]
    chiprun -- python experiments/paged_kernel_alone.py --sweep

``--sweep`` (PR 58) asks what a PAGE costs: K/V heads 2 / 4 / 8 / 16 (the
query layouts the benchmark serves over them: 32/2, 20/4, 32/8, 16/16) x
pages of 64 / 128 / 256 tokens, the same live TOKENS under each, at two
cells' decode shapes: ``chat-batch-128`` (128 slots, 88 resident at ~970
cached tokens, 40 idle) and ``batch-64`` (32 slots at ~400). It prints us a
call, us a page beside the bytes of one layer's K + V page, and the GB/s the
live pages' bytes came at: the table that fixes
``serve/kv_cache.py PAGE_COPY_BYTES`` (PERF.md 6, PR 58).

Without ``--sweep``:

32 slots, a block table 32 pages wide, 64-token pages, head size 128, at the
two head layouts the benchmark serves (GQA 32/8 over a 16-layer pool, MHA
16/16 over a 10-layer pool, 715 pages a layer), bf16 pages. One jitted
program scans the layer index ``ROUNDS`` times over the whole pool, so a
dispatch holds 64 / 40 calls and the host clock around ``block_until_ready``
reads the kernel, not the dispatch (the fastest of five batches of ten).
Cases: every slot on one page (the kernel's fixed cost), ~64 live pages (10
resident slots beside 22 idle ones: ``chat``), ~200 (all 32 resident:
``batch-64``), and the full table.

``--parent FILE`` times another copy of ``ops/paged_attention_pallas.py``
beside this tree's (``git show <commit>:<path> > FILE``) and compares the
two kernels' outputs. Fails (exit 2) without a TPU.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

PKG = "distributed_llm_training_and_inference_system_tpu"
B, MAXP, PS, D, NP = 32, 32, 64, 128, 715
LAYOUTS = {"gqa32x8": (32, 8, 16), "mha16x16": (16, 16, 10)}  # Nq, Nkv, L
ROUNDS, REPS, BATCHES = 4, 10, 5


def live_page_cases() -> dict[str, list[int]]:
    chat = [2, 3, 3, 4, 4, 4, 5, 5, 6, 6] + [1] * 22
    batch = [6 + (i % 4 == 0) for i in range(B)]
    return {"32 (one page a slot)": [1] * B, "64": chat, "200": batch,
            "1024 (full table)": [MAXP] * B}


def tables_and_lengths(pages: list[int], rng, maxp: int = MAXP,
                       num_pages: int = NP, page: int = PS
                       ) -> tuple[np.ndarray, np.ndarray]:
    tables = np.zeros((len(pages), maxp), np.int32)  # past the length: page 0
    free = rng.permutation(np.arange(1, num_pages))
    used = 0
    for slot, n in enumerate(pages):
        tables[slot, :n] = free[(used + np.arange(n)) % len(free)]
        used += n
    # an idle slot sits at position 0 (length 1); a resident one ends
    # somewhere inside its last page
    lengths = np.asarray([n * page - (17 if n > 1 else page - 1)
                          for n in pages], np.int32)
    return tables, lengths


def load_kernel(path: str | None):
    name = f"{PKG}.ops.paged_attention_pallas"
    if path is None:
        return importlib.import_module(name)
    spec = importlib.util.spec_from_file_location(name + "_parent", path)
    module = importlib.util.module_from_spec(spec)
    module.__package__ = f"{PKG}.ops"
    spec.loader.exec_module(module)
    return module


def program(kernel, L):
    @jax.jit
    def run(q, kp, vp, tables, lengths):
        def body(acc, i):
            layer = i % L
            out = kernel.paged_attention_pallas(
                q[layer], kp, vp, tables, lengths, layer=layer)
            return acc + out.astype(jnp.float32), None
        acc, _ = jax.lax.scan(body, jnp.zeros(q.shape[1:], jnp.float32),
                              jnp.arange(ROUNDS * L, dtype=jnp.int32))
        return acc
    return run


def time_us_a_call(run, args, L) -> float:
    """The fastest of ``BATCHES`` batches of ``REPS`` dispatches: the chip
    machine now and then stops for 0.1 s (PERF.md 7), which is longer than
    a batch."""
    jax.block_until_ready(run(*args))
    best = float("inf")
    for _ in range(BATCHES):
        t0 = time.perf_counter()
        for _ in range(REPS):
            out = run(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best / (REPS * ROUNDS * L) * 1e6


# the sweep: query heads over K/V heads as the benchmark's configurations
# have them (Nemotron-3-Nano, Falcon-H1 / SDAR's 4, Mistral-7B, OLMoE)
SWEEP_HEADS = {2: 32, 4: 20, 8: 32, 16: 16}
SWEEP_PAGES = (64, 128, 256)
SWEEP_LAYERS = 2


def sweep_cases(rng) -> dict[str, np.ndarray]:
    """Cached tokens a slot (0: idle, position 0) at two cells' decode
    steps: ``chat-batch-128`` (ledger, PR 57: ~88 of 128 slots live at ~970
    tokens) and ``batch-64`` (32 resident slots, ~200 pages of 64)."""
    parallel = np.zeros(128, np.int64)
    parallel[rng.permutation(128)[:88]] = rng.integers(200, 1741, 88)
    return {"chat-batch-128 (88 of 128 slots x ~970 tokens)": parallel,
            "batch-64 (32 slots x ~400 tokens)": rng.integers(150, 651, 32)}


def sweep(kernel, rng) -> dict:
    """us a call and a page at every K/V heads x page size x case."""
    rows = []
    cases = sweep_cases(rng)
    for nkv, nq in SWEEP_HEADS.items():
        for page in SWEEP_PAGES:
            most = max(int(np.maximum(-(-t // page), 1).sum())
                       for t in cases.values())
            L, num_pages = SWEEP_LAYERS, most + 2
            ks = jax.random.split(jax.random.PRNGKey(nkv * page), 3)
            q_all = jax.random.normal(ks[0], (L, 128, nq, D), jnp.bfloat16)
            kp, vp = (jax.random.normal(k, (L, num_pages, nkv, page, D),
                                        jnp.bfloat16) for k in ks[1:])
            run = program(kernel, L)
            for case, tokens in cases.items():
                pages = np.maximum(-(-tokens // page), 1)
                tables, _ = tables_and_lengths(
                    pages.tolist(), rng, maxp=4096 // page,
                    num_pages=num_pages, page=page)
                lengths = np.maximum(tokens, 1).astype(np.int32)
                page_bytes = 2 * nkv * page * D * 2
                try:
                    us = time_us_a_call(run, (
                        q_all[:, :len(tokens)], kp, vp, jnp.asarray(tables),
                        jnp.asarray(lengths)), L)
                except Exception as e:      # the compiler refusing a shape
                    print(f"{case[:14]:14s} kv {nkv:2d} page {page:3d} "
                          f"refused: {str(e)[:200]}", flush=True)
                    rows.append({"case": case, "kv_heads": nkv,
                                 "page_tokens": page, "page_bytes": page_bytes,
                                 "refused": str(e)[:400]})
                    continue
                row = {"case": case, "kv_heads": nkv, "q_heads": nq,
                       "page_tokens": page, "page_bytes": page_bytes,
                       "live_pages": int(pages.sum()),
                       "live_tokens": int(tokens.sum()),
                       "us_a_call": round(us, 2),
                       "us_a_page": round(us / pages.sum(), 4),
                       "gb_s": round(pages.sum() * page_bytes / us / 1e3, 1)}
                rows.append(row)
                print(f"{case[:14]:14s} kv {nkv:2d} page {page:3d} "
                      f"{page_bytes // 1024:5d} KB {row['live_pages']:5d} "
                      f"pages {us:8.2f} us a call {row['us_a_page']:.3f} us "
                      f"a page {row['gb_s']:6.1f} GB/s", flush=True)
            del q_all, kp, vp
    return {"sweep": rows}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--out", default="chiprun_out/paged_kernel_alone.json")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print("paged_kernel_alone: no TPU; a kernel time comes from the chip",
              file=sys.stderr)
        return 2
    if args.sweep:
        result = {"device": {"platform": device.platform,
                             "kind": device.device_kind},
                  **sweep(load_kernel(None), np.random.default_rng(0))}
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
        return 0
    kernels = {"this tree": load_kernel(None)}
    if args.parent:
        kernels["parent"] = load_kernel(args.parent)
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (  # noqa: E501
        paged_attention)
    rng = np.random.default_rng(0)
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind}, "us_a_call": {},
              "max_abs_gap": {}}
    for lname, (nq, nkv, L) in LAYOUTS.items():
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (L, B, nq, D), jnp.bfloat16)
        kp = jax.random.normal(ks[1], (L, NP, nkv, PS, D), jnp.bfloat16)
        vp = jax.random.normal(ks[2], (L, NP, nkv, PS, D), jnp.bfloat16)
        for case, pages in live_page_cases().items():
            tables, lengths = map(jnp.asarray, tables_and_lengths(pages, rng))
            outs = {}
            for kname, kernel in kernels.items():
                run = program(kernel, L)
                us = time_us_a_call(run, (q, kp, vp, tables, lengths), L)
                result["us_a_call"][f"{lname} | {case} | {kname}"] = round(
                    us, 2)
                outs[kname] = np.asarray(run(q, kp, vp, tables, lengths))
                print(f"{lname:9s} {case:22s} {kname:10s} {us:8.2f} us a call",
                      flush=True)
            gather = np.asarray(jax.jit(
                lambda q, kp, vp, t, n: paged_attention(
                    q[0], kp, vp, t, n, impl="gather", layer=0))(
                        q, kp, vp, tables, lengths), np.float32)
            one = np.asarray(jax.jit(
                lambda q, kp, vp, t, n: kernels["this tree"]
                .paged_attention_pallas(q[0], kp, vp, t, n, layer=0))(
                    q, kp, vp, tables, lengths), np.float32)
            gaps = {"kernel against gather": float(np.abs(one - gather).max())}
            if "parent" in outs:
                gaps["this tree against parent (sum over calls)"] = float(
                    np.abs(outs["this tree"] - outs["parent"]).max())
            result["max_abs_gap"][f"{lname} | {case}"] = gaps
            print(f"{lname:9s} {case:22s} gaps {gaps}", flush=True)
        del q, kp, vp
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
