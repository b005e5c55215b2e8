"""The training runner: builds ``RunConfig`` and ``runtime.engine
.TrainingEngine`` (what ``llmctl train launch`` builds) from the cell's
files and steps ``engine.trainer`` under the benchmark's own clock, fenced
on a fetched loss every ``fence_every`` steps (a block). No checkpoint is written.

Order of a run: engine and state from the seed -> the plain reference's
loss of the initial parameters on the first batch -> the first step
(compiles; its loss is held to the reference's) -> one more step -> the
window. Set-up ends at the window's first fence.
"""

from __future__ import annotations

import math
import sys
import time
from importlib import import_module

import numpy as np

from benchmark import harness, traffic as traffic_mod
from benchmark.reference import dense_decoder

# |first step's loss - reference's loss| allowed, in nats. The trainer
# computes the forward in bfloat16 and the reference in float32; at random
# initial weights both sit near ln(vocab) and PR 22's 4-device against
# 1-device runs agreed to 0.0004. A forward without attention or with a
# wrong rope base moves the mean loss of 32k random-weight positions by far
# less than a trained model's would, so this is as tight as rounding allows:
# 0.02 is ~5x the bf16 noise seen there and a 0.2 % error in ln(92544).
LOSS_TOLERANCE = 0.02


def run_config(config: dict, traffic: dict, seed: int, ckpt_dir: str):
    loader = import_module(f"{harness.PKG}.config.loader")
    par = dict(config["train"]["parallel"])
    micro, accum = traffic["micro_batch"], traffic["accumulation"]
    dp = traffic.get("data_shards", 1)
    par.update(micro_batch_size=micro, gradient_accumulation_steps=accum,
               global_batch_size=micro * accum * dp)
    cfg = loader.load_run_config(None, cli_overrides={
        "optimizer": config["train"]["optimizer"],
        "parallel": par,
        "data": {"train": "synthetic", "val": "synthetic",
                 "max_length": traffic["seq_len"],
                 "seed": seed % (2 ** 31 - 1)},
        "training": {"seed": seed % (2 ** 31 - 1),
                     **config["train"].get("training", {})},
        "checkpoint": {"path": ckpt_dir},
    }, environ={})
    schema = import_module(f"{harness.PKG}.config.schema")
    cfg.model = schema.ModelConfig.from_dict(harness.model_dict(config))
    return cfg


def init_state(engine, seed: int) -> None:
    """``engine.initialize(resume=False)`` with the seed as an ARGUMENT of
    the jitted init: ``ShardedTrainer.init_state`` closes over it, so every
    new seed is a new program and 20-25 s of compilation (PR 24)."""
    import jax
    gpt = import_module(f"{harness.PKG}.models.gpt")
    exec_mod = import_module(f"{harness.PKG}.exec")
    sharding = import_module(f"{harness.PKG}.parallel.sharding")
    trainer = engine.trainer

    def make(key):
        return exec_mod.TrainState.create(
            gpt.init(trainer.model_cfg, key), trainer.tx)

    with sharding.use_mesh(trainer.mesh):
        trainer.state = jax.jit(
            make, out_shardings=trainer._state_shardings)(
                jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def reference_loss(params, batch: dict, config: dict) -> float:
    total, count = 0.0, 0
    for row in np.asarray(batch["tokens"]):
        s, n = dense_decoder.next_token_loss(params, row, config)
        total, count = total + s, count + n
    return total / count


def run(cell: dict, config: dict, traffic_path: str, seed: int,
        seconds: float, trace: bool, t_process_start: float,
        require_tpu: bool = True) -> dict:
    device = harness.start(cell["chips"], require_tpu)
    harness.mark("imports and device", t_process_start)
    import jax
    traffic = traffic_mod.load(traffic_path)
    engine_mod = import_module(f"{harness.PKG}.runtime.engine")
    fence_every = int(traffic.get("fence_every", 4))
    with harness.scratch_dir("bench_ckpt_") as ckpt:
        cfg = run_config(config, traffic, seed, ckpt)
        engine = engine_mod.TrainingEngine(
            cfg, devices=jax.devices()[:cell["chips"]])
        try:
            init_state(engine, seed)
            harness.mark("engine and state", t_process_start)
            trainer = engine.trainer
            tokens_per_step = (cfg.parallel.global_batch_size
                               * cfg.data.max_length)
            batch = next(engine.train_data)
            ref = reference_loss(trainer.state.params, batch, config)
            harness.mark("reference loss", t_process_start)
            first = float(trainer.step(batch)["loss"])
            check = {"ok": bool(abs(first - ref) <= LOSS_TOLERANCE),
                     "first_loss": first, "reference_loss": ref,
                     "tol": LOSS_TOLERANCE}
            print(f"[bench] reference check {check}", file=sys.stderr)
            float(trainer.step(next(engine.train_data))["loss"])
            harness.mark("two steps", t_process_start)

            losses, blocks, waits, traced = [], [], [], None
            t0 = time.monotonic()
            while True:
                # the second block of a traced run is the traced stretch
                with harness.Trace(trace and len(blocks) == 1) as tr:
                    for _ in range(fence_every):
                        w0 = time.monotonic()
                        batch = next(engine.train_data)
                        waits.append(time.monotonic() - w0)
                        losses.append(trainer.step(batch)["loss"])
                    float(losses[-1])        # the fence: a fetched value
                blocks.append((tr.t0, tr.t1, fence_every))
                if tr.on:
                    traced = tr
                # stop where another block would overrun the window
                if time.monotonic() - t0 + (tr.t1 - tr.t0) > seconds:
                    break
            losses = [float(x) for x in losses]
        finally:
            engine.close()
    return {
        "kind": "train", "config": config, "cell": cell, "check": check,
        "device": device, "chips": cell["chips"], "traffic": traffic,
        "setup_s": t0 - t_process_start, "blocks": blocks,
        "tokens_per_step": tokens_per_step, "seq_len": cfg.data.max_length,
        "losses": losses, "data_wait_s": waits,
        "all_finite": bool(all(math.isfinite(x) for x in losses)),
        "trace": traced.result if traced else {},
        "trace_steps": fence_every if traced else 0,
        "trace_listing": traced.listing if traced else None,
        "compiled_in_window": None,
        "memory_peak_bytes": harness.memory_peak_bytes(cell["chips"]),
    }
