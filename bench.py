"""Headline benchmark: training throughput + MFU on the local chip(s).

Prints ONE JSON line:
  {"metric": ..., "value": tokens/sec/chip, "unit": ..., "vs_baseline": ...}

The reference publishes no measured numbers (BASELINE.md: bench is
"coming soon" at reference cli/commands/bench.py:33-75), so the comparison
base is the BASELINE.json north-star target: >=50% MFU for training.
``vs_baseline`` = measured_MFU / 0.50 — 1.0 means the target is met.

Model: gpt-750m (H=2048/D=128) — the largest template whose AdamW state +
grads fits one 16 GB v5e chip. Round 1 benched gpt-350m, but its H=1024
matmul shapes cap at 17-30% of the v5e MXU peak in isolation (measured via
matmul-probe sweeps, BASELINE.md round-2 notes), so its 0.34 MFU was a
model-shape ceiling, not a framework one. bf16 compute, flash attention
Pallas kernel, selective remat, chunked cross-entropy (the [B,S,V] fp32
logits pair is never materialised), bf16 Adam moments
(OptimizerConfig.moment_dtype/nu_dtype — measured +0.035 MFU at this
scale, the freed HBM improves XLA scheduling), and 16-microbatch gradient
accumulation (global batch 64 — the round-3 sweep: the optimizer +
fixed-cost tail amortises over microbatches, per-microbatch cost falls
416 -> 391 ms, MFU 0.494 -> 0.524) — the same code path `llmctl train`
uses. It measures the chip: with no TPU it fails (exit 2) instead of
printing a CPU number under a device metric's name.

Timing: pipelined windows of 5 steps, each fenced by a scalar fetch (a
value that depends on the step cannot arrive before the step has run);
reports the best window (min) plus the per-window spread so
round-over-round deltas are trustworthy.
"""

from __future__ import annotations

import json
import sys
import time


def main() -> None:
    from distributed_llm_training_and_inference_system_tpu.utils.platform import (
        chip_peaks, device_summary, enable_compile_cache)

    # persistent XLA compilation cache ($JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache): the 7B-shape flagship program takes minutes
    # to compile cold — without the cache a fresh `python bench.py` would
    # spend most of its watchdog budget compiling a program an earlier
    # run already built
    enable_compile_cache()

    import jax
    import jax.numpy as jnp

    device = device_summary()
    if device["platform"] != "tpu":
        print(json.dumps({"error": "bench.py measures a TPU and found none",
                          "device": device}), file=sys.stderr)
        sys.exit(2)
    # a kind the peaks table does not know raises: no made-up peak
    peak_tflops = chip_peaks(device["platform"],
                             device["kind"])["peak_bf16_tflops"]

    from distributed_llm_training_and_inference_system_tpu.config import (
        OptimizerConfig, ParallelConfig, get_model_config)
    from distributed_llm_training_and_inference_system_tpu.exec import (
        TrainState, make_train_step)
    from distributed_llm_training_and_inference_system_tpu.models import init
    from distributed_llm_training_and_inference_system_tpu.models.gpt import (
        flops_per_token)

    # per-model shape recipe (measured, BASELINE.md): batch fills HBM,
    # accumulation amortises the optimizer tail, loss_chunk caps the CE
    # workspace. LLMCTL_BENCH_MODEL overrides for flagship candidates
    # (e.g. gpt-7b-4l) without changing the recorded default statistic.
    import os as _os
    recipes = {
        "gpt-750m": dict(batch=4, accum=16, chunk=1024),
        # THE NORTH-STAR SHAPE (H=4096, ffn 11008, V=50304 — gpt-7b's
        # per-layer geometry). AdamW cannot fit accumulation here on a
        # 16 GB chip (fp32 master 4.9 + moments 4.9 + carry + ~6 GB
        # transient — every round-5 row OOM'd); the measured fit is
        # adafactor (factored second moment, no mu) + bf16 accumulation
        # carry + chunk-512 CE: MFU 0.5817 at b2 x accum8
        # (round-5 row mfu7b4l_b2_a8_adafactor) — above the >=0.50 bar.
        "gpt-7b-4l": dict(batch=2, accum=8, chunk=512,
                          accum_dtype="bfloat16", opt="adafactor"),
    }
    # flagship: the north-star shape now that its recipe measures >=0.50
    # (round-4 verdict item 2); LLMCTL_BENCH_MODEL=gpt-750m recovers the
    # round-3/4 comparison statistic
    model_name = _os.environ.get("LLMCTL_BENCH_MODEL") or "gpt-7b-4l"
    r = recipes.get(model_name, recipes["gpt-750m"])
    seq_len = 2048
    batch = r["batch"]
    accum = r["accum"]

    cfg = get_model_config(model_name)
    par = ParallelConfig(activation_checkpoint="selective",
                         micro_batch_size=batch,
                         global_batch_size=batch * accum,
                         gradient_accumulation_steps=accum)
    opt_type = r.get("opt", "adamw")
    step_fn, tx, _ = make_train_step(
        cfg, OptimizerConfig(
            type=opt_type, lr=1e-4,
            # moment dtypes and the fused kernel are adam-family knobs;
            # adafactor goes through the optax path
            moment_dtype="bfloat16" if opt_type == "adamw" else "float32",
            nu_dtype="bfloat16" if opt_type == "adamw" else "float32",
            fused=opt_type == "adamw",
            accum_dtype=r.get("accum_dtype", "float32")),
        par, attn_impl="flash", loss_chunk=r["chunk"])
    params = init(cfg, jax.random.PRNGKey(0))
    state = TrainState.create(params, tx)
    jstep = jax.jit(step_fn, donate_argnums=(0,))

    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (batch * accum, seq_len), 1,
                                cfg.vocab_size)
    b = {"tokens": tokens}

    # warmup (compile) + sync fence via host transfer
    state, m = jstep(state, b)
    float(m["loss"])

    # fixed across rounds: min-of-4-windows is the statistic BENCH_r* rows
    # are compared with; changing the window count would change the
    # sample-minimum's bias and break round-over-round comparability
    n_windows, per_window = 4, 5
    windows = []
    final_loss = 0.0
    for _ in range(n_windows):
        t0 = time.perf_counter()
        for _ in range(per_window):
            state, m = jstep(state, b)
        final_loss = float(m["loss"])   # forces the dependency chain
        windows.append((time.perf_counter() - t0) / per_window)

    dt = min(windows)
    spread = (max(windows) - dt) / dt
    steps_per_sec = 1.0 / dt
    tokens_per_sec = steps_per_sec * batch * accum * seq_len
    fpt = flops_per_token(cfg, seq_len)
    mfu = tokens_per_sec * fpt / (peak_tflops * 1e12)

    print(json.dumps({
        "metric": f"{model_name} train tokens/sec/chip (seq {seq_len}, "
                  f"bf16, flash-attn, chunked-CE, {device['kind']})",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(mfu / 0.50, 4),
        "mfu": round(mfu, 4),
        "step_time_ms": round(dt * 1e3, 2),
        "window_spread": round(spread, 4),
        "loss": round(final_loss, 4),
        "device": device,
    }))


def _watchdog(seconds: float):
    """Hard deadline for the whole bench. A device that stops answering
    makes every jax op block forever (seen once on an earlier remote dev
    chip; not re-observed on a directly attached one — whether the
    watchdog is still needed is left to a later simplicity pass). A hung
    bench records nothing; this prints an explicit failure line and exits
    instead, so the capture shows WHAT happened rather than an empty
    timeout.

    Returns the Timer (cancel it once the measurement prints — a success
    landing near the deadline must not emit a second line), or None when
    disabled (seconds <= 0, the usual timeout-env convention)."""
    import os
    import threading

    if seconds <= 0:
        return None

    def fire():
        print(json.dumps({
            "metric": "bench watchdog",
            "value": 0.0,
            "unit": "tokens/sec/chip",
            "vs_baseline": 0.0,
            "error": f"device did not respond within {seconds:.0f}s; "
                     "no measurement taken",
        }), flush=True)
        os._exit(3)
    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t


if __name__ == "__main__":
    import os
    # 1500 s: room for the 7B flagship's cold compile (minutes; not
    # re-measured on a directly attached chip) + ~1 min of measurement.
    # A device that hangs still trips this — a hang lasts forever.
    _timer = _watchdog(float(os.environ.get("LLMCTL_BENCH_WATCHDOG_S",
                                            "1500")))
    main()
    if _timer is not None:
        _timer.cancel()
