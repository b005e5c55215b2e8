"""Attribute the gpt-7b int8 serve decode cost (round-4 headline probe).

The first 7B smoke measured ~310 ms per decode step wall — ~30x the
~10 ms data floor (6.5 GB int8 weights + ~1.2 GB live KV at 820 GB/s).
This probe separates:
  - device decode ms/step + device prefill ms (engine.measure_device_times:
    pipelined dispatches, one fence — link RTT amortised out)
  - wall ms/dispatch for the same K-step program (includes the
    dispatch round trip and any host-side per-dispatch cost)
  - weight-streaming floor for the loaded tree (tree_weight_bytes / peak BW)

Usage: python experiments/profile7b.py [artifact] [slots] [ctx] [K]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    artifact = sys.argv[1] if len(sys.argv) > 1 else \
        "experiments/artifacts/gpt7b-int8.safetensors"
    slots = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    ctx = int(sys.argv[3]) if len(sys.argv) > 3 else 512
    K = int(sys.argv[4]) if len(sys.argv) > 4 else 8

    import jax

    from distributed_llm_training_and_inference_system_tpu.config import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ServeConfig)
    from distributed_llm_training_and_inference_system_tpu.ops.quantization import (
        tree_weight_bytes)
    from distributed_llm_training_and_inference_system_tpu.serve import (
        InferenceEngine, SamplingParams)

    cfg = get_model_config("gpt-7b")
    t0 = time.time()
    eng = InferenceEngine(cfg, ServeConfig(
        model="gpt-7b", artifact=artifact, max_batch_size=slots,
        max_seq_len=max(768, ctx + 192), kv_block_size=64,
        kv_hbm_budget_gb=4.0, admission="ondemand",
        dtype="bfloat16"), seed=0)
    print(json.dumps({"build_s": round(time.time() - t0, 1),
                      "quant": eng.quantization,
                      "kv_pages": eng.kv.num_pages}), flush=True)

    wb = tree_weight_bytes(eng.params)
    print(json.dumps({"weight_bytes_gb": round(wb / 1e9, 2),
                      "stream_floor_ms": round(wb / 819e9 * 1e3, 2)}),
          flush=True)

    # occupy slots with real prefills so decode touches live context
    prompts = [list(range(1, ctx + 1)) for _ in range(slots)]
    t0 = time.time()
    eng.generate(prompts, SamplingParams(temperature=0.0, max_tokens=2))
    print(json.dumps({"warm_generate_s": round(time.time() - t0, 1)}),
          flush=True)

    # device-time calibration: pipelined dispatches, one fence
    dt = eng.measure_device_times(buckets=(ctx,), iters=8)
    print(json.dumps({"device_times": dt}), flush=True)

    # wall per-dispatch: run the SAME decode program K-step, fenced per
    # dispatch (the serving pattern) — difference vs device = RTT + host
    for trial in range(3):
        t0 = time.time()
        out = eng._decode_device()
        wall = time.time() - t0
        print(json.dumps({"trial": trial,
                          "wall_dispatch_ms": round(wall * 1e3, 1),
                          "wall_per_step_ms": round(wall * 1e3 / K, 1)}),
              flush=True)

    eng.release()


if __name__ == "__main__":
    main()
