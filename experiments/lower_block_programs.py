"""Lower the programs that run a decoder block and write their StableHLO,
so that two trees can be compared line for line (PERF.md 6, PR 29).

    JAX_PLATFORMS=cpu python experiments/lower_block_programs.py --out DIR

run from the root of each tree, then

    python experiments/lower_block_programs.py --compare DIR_A DIR_B

which says of each program "identical" (byte for byte), "same operations"
(the texts differ, yet hold the same operations once value names are taken
out: operands or independent lines in another order) or "DIFFERENT" with the
operations only one side has. Small sizes, on the CPU: what is compared is
the program's text, not a time.
Programs: the training loss's gradient (``remat="selective"``), cold prefill
(``gpt.forward`` over a dense cache), ``decode_scan`` (K = 8), suffix
prefill (``extend_step_forward``, T = 8) and, beside the issue's four, the
decode step over int8 and int4 weights. Models: a dense GQA model with
``attention_bias`` and ``olmoe-test``, each in float32 and bfloat16.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import os
import re
import sys

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

PKG = "distributed_llm_training_and_inference_system_tpu"


_VALUE_NAME = re.compile(r"%[A-Za-z_]*[0-9]*(#[0-9]+)?(:[0-9]+)?")


def _operations(text: str) -> collections.Counter:
    """The program's lines as a multiset, value names taken out; the typed
    operand lists of loops, calls and returns (what a reordered closure
    permutes) sorted."""
    ops: collections.Counter = collections.Counter()
    for line in text.splitlines():
        line = _VALUE_NAME.sub("%", line.strip())
        if re.match(r"(%.* = )?(stablehlo\.while|stablehlo\.return|"
                    r"func\.call|call|return|func\.func)\b", line):
            line = " ".join(sorted(re.split(r"[ ,()]+", line)))
        ops[line] += 1
    return ops


def compare(dir_a: str, dir_b: str) -> int:
    worst = 0
    for name in sorted(os.listdir(dir_a)):
        with open(os.path.join(dir_a, name)) as f:
            a = f.read()
        with open(os.path.join(dir_b, name)) as f:
            b = f.read()
        if a == b:
            print(f"identical        {name}")
            continue
        ops_a, ops_b = _operations(a), _operations(b)
        if ops_a == ops_b:
            print(f"same operations  {name}")
            worst = max(worst, 1)
            continue
        worst = 2
        print(f"DIFFERENT        {name}")
        for sign, only in (("-", ops_a - ops_b), ("+", ops_b - ops_a)):
            for line, n in sorted(only.items()):
                print(f"    {sign}{n} {line[:300]}")
    return worst


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out")
    ap.add_argument("--compare", nargs=2, metavar="DIR")
    args = ap.parse_args()
    if args.compare:
        sys.exit(2 if compare(*args.compare) == 2 else 0)
    os.makedirs(args.out, exist_ok=True)

    import importlib
    presets = importlib.import_module(f"{PKG}.config.presets")
    gpt = importlib.import_module(f"{PKG}.models.gpt")
    decode = importlib.import_module(f"{PKG}.serve.decode")
    train_step = importlib.import_module(f"{PKG}.exec.train_step")
    quant = importlib.import_module(f"{PKG}.ops.quantization")

    B, S, T, K = 2, 32, 8, 8
    L_pages, PS = 16, 8

    def programs(cfg):
        params = gpt.init(cfg, jax.random.PRNGKey(0))
        tokens = jnp.zeros((B, S), jnp.int32)
        seg = jnp.ones((B, S), jnp.int32)
        dt = jnp.dtype(cfg.dtype)

        def grad(p, tok, seg_):
            return jax.grad(
                lambda p_: train_step._loss_fn(
                    p_, {"tokens": tok, "segment_ids": seg_}, cfg, "xla",
                    "selective", 16)[0])(p)
        yield "train_grad", jax.jit(grad).lower(params, tokens, seg)

        cache = gpt.init_kv_cache(cfg, B, 64, dtype=dt)

        def prefill(p, tok, c, off):
            # as the engine's prefill program calls it (serve/engine.py)
            moe = ({"return_moe_stats": True,
                    "segment_ids": jnp.ones_like(tok)} if cfg.is_moe else {})
            return gpt.forward(p, tok, cfg, kv_cache=c, cache_offset=off,
                               unembed_positions=off + S - 1, **moe)
        yield "cold_prefill", jax.jit(prefill).lower(
            params, tokens, cache, jnp.zeros((B,), jnp.int32))

        pages = jnp.zeros((cfg.num_layers, L_pages, cfg.num_kv_heads, PS,
                           cfg.head_dim), dt)
        tables = jnp.zeros((B, 4), jnp.int32)
        vec = jnp.zeros((B,), jnp.int32)
        fvec = jnp.zeros((B,), jnp.float32)
        keys = jnp.zeros((B, 2), jnp.uint32)

        def dscan(p, tok, pos, kp, vp, bt, stop, sk, te, tk, tp):
            return decode.decode_scan(
                p, tok, pos, kp, vp, bt, stop, sk, te, tk, tp, cfg, K,
                attn_impl="gather", return_moe_stats=True)
        dargs = (vec, vec, pages, pages, tables, vec, keys, fvec, vec, fvec)
        yield "decode_scan_k8", jax.jit(dscan).lower(params, *dargs)

        def suffix(p, tok, start, kp, vp, bt, ok):
            return decode.extend_step_forward(
                p, tok, start, kp, vp, bt, cfg, write_ok=ok,
                attn_impl="gather", return_moe_stats=True)
        yield "suffix_prefill_t8", jax.jit(suffix).lower(
            params, jnp.zeros((B, T), jnp.int32), vec, pages, pages, tables,
            jnp.ones((B, T), bool))

        if not cfg.is_moe:
            # the engine's own calls (serve/engine.py __init__)
            w8 = dict(params, blocks=quant.to_runtime_quant(
                quant.quantize_tree_int8(params["blocks"], min_size=64,
                                         min_ndim=3)))
            w4 = quant.to_runtime_quant(quant.quantize_tree_int4(
                dict(params), min_size=64, group=16))
            for name, qp in (("w8", w8), ("w4", w4)):
                yield f"decode_scan_k8_{name}", jax.jit(dscan).lower(
                    qp, *dargs)

    dense = dataclasses.replace(
        presets.get_model_config("gpt-test"), attention_bias=True)
    moe = presets.get_model_config("olmoe-test")
    for cfg in (dense, moe):
        for dtype in ("float32", "bfloat16"):
            c = dataclasses.replace(cfg, dtype=dtype)
            for prog, lowered in programs(c):
                path = os.path.join(
                    args.out, f"{cfg.name}.{dtype}.{prog}.stablehlo.txt")
                with open(path, "w") as f:
                    f.write(lowered.as_text())
                print(path, flush=True)


if __name__ == "__main__":
    main()
