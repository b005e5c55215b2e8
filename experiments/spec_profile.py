"""Isolate the cost of one verify window vs decode steps (gpt-1b, chip).

Times three jitted programs over the same paged state:
  decode1   — decode_multi_step, 1 step
  decode8   — decode_multi_step, 8 steps
  verify8   — speculative_verify alone (T=8 window)
  verify8s  — extend_step_forward alone (no sampling/argmax)
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_training_and_inference_system_tpu.config import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.models import init
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        decode_multi_step, extend_step_forward)
    from distributed_llm_training_and_inference_system_tpu.serve.speculative import (
        speculative_verify)

    model = sys.argv[1] if len(sys.argv) > 1 else "gpt-1b"
    cfg = get_model_config(model)
    B, T, PS, NP, maxP = 4, 8, 64, 80, 18
    params = init(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    shape = (cfg.num_layers, NP, cfg.num_kv_heads, PS, cfg.head_dim)
    kp, vp = jnp.zeros(shape, jnp.bfloat16), jnp.zeros(shape, jnp.bfloat16)
    tables = jnp.asarray(
        np.arange(1, B * maxP + 1).reshape(B, maxP), jnp.int32)
    pos = jnp.full((B,), 640, jnp.int32)
    stops = jnp.full((B,), 1100, jnp.int32)
    keys = jnp.asarray(np.tile(np.asarray(
        jax.random.key_data(jax.random.PRNGKey(0)))[None], (B, 1)), jnp.uint32)
    temp = jnp.zeros((B,), jnp.float32)
    tk = jnp.zeros((B,), jnp.int32)
    tp_ = jnp.ones((B,), jnp.float32)
    toks1 = jnp.ones((B,), jnp.int32)
    toksT = jnp.ones((B, T), jnp.int32)

    out = {"model": model}

    def timed(name, fn, *args):
        r = jax.block_until_ready(fn(*args))
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(4):
                r = jax.block_until_ready(fn(*args))
            best = min(best, (time.perf_counter() - t0) / 4)
        out[name] = round(best * 1e3, 1)

    # params is a jit ARGUMENT everywhere: closing over it would bake the
    # 2.9 GB weight pytree into each program as captured constants —
    # minutes of lowering per program and a duplicated weight residency
    # (the first round-3 battery run timed out exactly this way)
    d1 = jax.jit(lambda p, kp_, vp_: decode_multi_step(
        p, toks1, pos, kp_, vp_, tables, stops, keys, temp, tk, tp_,
        cfg, num_steps=1)[0])
    d8 = jax.jit(lambda p, kp_, vp_: decode_multi_step(
        p, toks1, pos, kp_, vp_, tables, stops, keys, temp, tk, tp_,
        cfg, num_steps=8)[0])
    v8 = jax.jit(lambda p, kp_, vp_: speculative_verify(
        p, toksT, pos, kp_, vp_, tables, stops, keys, temp, tk, tp_,
        cfg)[0])
    e8 = jax.jit(lambda p, kp_, vp_: extend_step_forward(
        p, toksT, pos, kp_, vp_, tables, cfg)[0])

    which = (sys.argv[2] if len(sys.argv) > 2 else "d8,v8").split(",")
    progs = {"d1": ("decode1_ms", d1), "d8": ("decode8_ms", d8),
             "v8": ("verify8_ms", v8), "e8": ("extend8_ms", e8)}
    for w in which:
        name, fn = progs[w]
        timed(name, fn, params, kp, vp)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
