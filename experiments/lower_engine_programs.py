"""Lower an ENGINE's own programs and write their StableHLO, so that two
trees can be compared letter for letter (PERF.md 6, PR 42): the decode
dispatch, a cold prefill bucket, a suffix prefill bucket and a chunk
bucket, as ``InferenceEngine`` jits them, for one test configuration of each
kind the benchmark serves (GQA dense, uniform MoE, hybrid, latent, linear).

    JAX_PLATFORMS=cpu python experiments/lower_engine_programs.py --out DIR
        [--tree OTHER_CHECKOUT] [--cells]
    python experiments/lower_block_programs.py --compare DIR_A DIR_B

Small sizes, on the CPU: what is compared is the program's text.

``--cells`` (PR 58) lowers the decode dispatch of every SERVING configuration
under ``benchmark/configs`` instead, at its published widths and its own
``serve`` section (its page size, stated or not, above all), over abstract
weights, ``CELL_SLOTS`` slots and a pool of 0.05 GB (nothing of a cell's
size is allocated or compiled here; slots and pool are cut alike in both
trees): which cells' programs a change to the engine's defaults moves.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys

MODELS = ("gpt-test", "olmoe-test", "nemotron-h-test", "xing-test",
          "kimi-linear-test")
PKG = "distributed_llm_training_and_inference_system_tpu"
CELL_SLOTS = 8


def shapes(tree):
    import jax
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def lower_cells(tree: str, out: str) -> None:
    """The decode dispatch of each serving configuration, lowered."""
    import glob
    import json

    import jax
    import jax.numpy as jnp
    schema = importlib.import_module(f"{PKG}.config.schema")
    engine_mod = importlib.import_module(f"{PKG}.serve.engine")
    gpt = importlib.import_module(f"{PKG}.models.gpt")

    for file in sorted(glob.glob(os.path.join(tree, "benchmark", "configs",
                                              "*.json"))):
        with open(file) as f:
            config = json.load(f)
        if "serve" not in config:       # a training configuration
            continue
        cfg = schema.ModelConfig.from_published(config)
        serve = schema.ServeConfig(model=config["name"], **{
            **config["serve"], "max_batch_size": CELL_SLOTS,
            "kv_hbm_budget_gb": 0.05})
        dtype = jnp.dtype(serve.dtype)
        params = jax.eval_shape(lambda k: gpt.init(cfg, k, dtype),
                                jax.random.PRNGKey(0))
        eng = engine_mod.InferenceEngine(cfg, serve, params=params)
        args = shapes((eng.params, eng.kv.k_pages, eng.kv.v_pages,
                       *eng._decode_head_args(), *eng._shared_decode_args(),
                       *eng._decode_tail_args()))
        path = os.path.join(out, f"{config['name']}.decode.stablehlo.txt")
        with open(path, "w") as f:
            f.write(eng._decode_jit.lower(*args).as_text())
        print(path, "page", eng.kv.page_size, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--tree", default=os.getcwd())
    ap.add_argument("--models", nargs="*", default=MODELS)
    ap.add_argument("--cells", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))
    os.makedirs(args.out, exist_ok=True)
    if args.cells:
        lower_cells(os.path.abspath(args.tree), args.out)
        return
    import jax
    import jax.numpy as jnp
    presets = importlib.import_module(f"{PKG}.config.presets")
    schema = importlib.import_module(f"{PKG}.config.schema")
    engine_mod = importlib.import_module(f"{PKG}.serve.engine")
    sampling_mod = importlib.import_module(f"{PKG}.serve.sampling")
    scheduler = importlib.import_module(f"{PKG}.serve.scheduler")

    i32 = jnp.int32
    for name in args.models:
        cfg = presets.get_model_config(name)
        serve = schema.ServeConfig(
            model=name, max_batch_size=4, max_seq_len=128, kv_block_size=16,
            dtype=cfg.dtype, prefill_chunk=16,
            # (state-space layers refuse chunked prefill by name)
            chunked_prefill_tokens=0 if cfg.ssm_layers else 32)
        eng = engine_mod.InferenceEngine(cfg, serve, seed=0)
        state = (shapes(eng.kv.state),) if cfg.is_recurrent else ()
        slot = (jax.ShapeDtypeStruct((), i32),) if state else ()
        common = shapes((eng.params, eng.kv.k_pages, eng.kv.v_pages))
        sampling = shapes(eng._sampling_args(
            sampling_mod.seed_key_data(0), 0, scheduler.SamplingParams()))
        bucket, PS = 32, eng.kv.page_size

        def vec(*shape):
            return jax.ShapeDtypeStruct(shape, i32)
        programs = {
            "decode": (eng._decode_jit, (
                *common, *shapes((jnp.asarray(eng.last_tokens),
                                  jnp.asarray(eng.positions),
                                  *eng._shared_decode_args(),
                                  *eng._decode_tail_args())))),
            "cold_prefill": (eng._prefill_fn(bucket), (
                common[0], vec(1, bucket), vec(1), common[1], common[2],
                vec(bucket // PS), *sampling, *state, *slot)),
            "suffix_prefill": (eng._extend_prefill_fn(bucket), (
                common[0], vec(1, bucket), vec(1), vec(1), common[1],
                common[2], vec(1, eng.kv.max_pages_per_slot), *sampling,
                *state, *slot)),
            "prefill_chunk": (eng._extend_chunk_fn(bucket), (
                common[0], vec(1, bucket), vec(1), vec(1), common[1],
                common[2], vec(1, eng.kv.max_pages_per_slot), *state,
                *slot)),
        }
        if cfg.ssm_layers:      # ... and take no window over their state
            del programs["prefill_chunk"], programs["suffix_prefill"]
        for prog, (fn, fn_args) in programs.items():
            path = os.path.join(args.out, f"{name}.{prog}.stablehlo.txt")
            with open(path, "w") as f:
                f.write(fn.lower(*fn_args).as_text())
            print(path, flush=True)


if __name__ == "__main__":
    main()
