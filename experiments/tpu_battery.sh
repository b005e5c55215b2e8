#!/bin/bash
# Round-3 TPU measurement battery — every number queued behind the chip
# outage, one serial pass, each step timeboxed. Results land in
# experiments/results_r3/ as JSON lines; BASELINE.md rows come from these.
#
# Usage: bash experiments/tpu_battery.sh [outdir]
set -u
cd "$(dirname "$0")/.."
OUT=${1:-experiments/results_r3}
mkdir -p "$OUT"

# 0. chip sanity (fail the whole battery fast if the chip is unreachable or
#    jax silently fell back to CPU — CPU times against TPU peaks would
#    fill the logs with nonsense)
source experiments/battery_lib.sh   # cwd is the repo root after the cd
tpu_guard

# 1. headline train bench (flagship MFU) — the BENCH_r03 statistic
# outer timeout ABOVE the watchdog's 900s default so a wedge produces
# the watchdog's self-describing failure line, not an empty SIGTERM
run bench_headline 1200 python bench.py

# 2. optimizer: fused vs optax at full step + the new nu_dtype lever;
#    then the memory-unlocked configs (b6/b8, remat none)
run mfu_b4_nufp32 700 python experiments/mfu_sweep.py 4 selective gpt-750m bfloat16 1024 true
run mfu_b4_nubf16_sel 700 python experiments/mfu_sweep.py 4 selective gpt-750m bfloat16 1024 true bfloat16
run mfu_b4_nubf16_none 700 python experiments/mfu_sweep.py 4 none gpt-750m bfloat16 1024 true bfloat16
run mfu_b6_nubf16 700 python experiments/mfu_sweep.py 6 selective gpt-750m bfloat16 1024 true bfloat16

# 3. serving under load: ondemand vs reserve at the same KV budget,
#    with device-time TTFT (the co-located figure)
run serve_load_ondemand 900 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    bench e2e --model gpt-1b --mode serve-load --requests 32 \
    --prompt-len 512 --gen-len 128 --rps 2,6,12 --concurrency 4,8,16 \
    --admission ondemand --kv-blocks 96
run serve_load_reserve 900 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    bench e2e --model gpt-1b --mode serve-load --requests 32 \
    --prompt-len 512 --gen-len 128 --rps 2,6,12 --concurrency 4,8,16 \
    --admission reserve --kv-blocks 96

# 4a. verify-window cost isolation
run spec_profile_paged 700 python experiments/spec_profile.py gpt-1b

# 4b. speculation crossover (oracle acceptance sweep)
run spec_crossover 1200 python experiments/spec_crossover.py gpt-1b 8 7

# 5. int4 decode throughput vs int8 vs bf16
run int4_serve 900 python experiments/int8_serve_bench.py  # bf16+int8 rows
run int4_only 900 python -c "
import sys, time, json
sys.path.insert(0, '.')
import numpy as np
from distributed_llm_training_and_inference_system_tpu.config import get_model_config
from distributed_llm_training_and_inference_system_tpu.config.schema import ServeConfig
from distributed_llm_training_and_inference_system_tpu.serve import InferenceEngine, SamplingParams
from distributed_llm_training_and_inference_system_tpu.ops.quantization import tree_weight_bytes
cfg = get_model_config('gpt-1b')
for q in ('int4', 'int4-awq'):
    eng = InferenceEngine(cfg, ServeConfig(model='gpt-1b', max_batch_size=4,
        max_seq_len=704, kv_block_size=64, dtype='bfloat16',
        quantization=q, decode_steps_per_dispatch=8), seed=0)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, 512).tolist() for _ in range(4)]
    eng.generate([prompts[0]], SamplingParams(temperature=0.0, max_tokens=2))
    t0 = time.perf_counter()
    reqs = eng.generate(prompts, SamplingParams(temperature=0.0, max_tokens=128))
    dt = time.perf_counter() - t0
    print(json.dumps({'quant': q,
        'decode_tok_s': round(sum(len(r.generated_tokens) for r in reqs)/dt, 1),
        'weight_gb': round(tree_weight_bytes(eng.params)/1e9, 3)}))
"

# 6. ring vs ulysses at 8k/16k on the sp mesh (8 fake CPU devices is NOT
#    the target here — this one needs the real chip... single chip can't
#    do sp>1; measure per-device attention time via the kernels instead)
run attn_ring_vs_ulysses 600 python -c "
import sys, time, json
sys.path.insert(0, '.')
# single-chip proxy: time the flash kernel at the per-device shapes each
# SP scheme produces (ring: S/sp keys per step x sp steps; ulysses: full S
# keys, Nq/sp heads) — the selection rule input the planner needs
import jax, jax.numpy as jnp
from distributed_llm_training_and_inference_system_tpu.ops.attention import flash_attention
B, H, D, sp = 1, 16, 128, 8
for S in (8192, 16384):
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S//sp, H, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S//sp, H, D), jnp.bfloat16)
    f = jax.jit(lambda q,k: flash_attention(q, k, k, causal=False))
    f(q, k).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(8): out = f(q, k)
    out.block_until_ready(); ring_step = (time.perf_counter()-t0)/8
    qU = jax.random.normal(jax.random.PRNGKey(0), (B, S, H//sp, D), jnp.bfloat16)
    kU = jax.random.normal(jax.random.PRNGKey(1), (B, S, H//sp, D), jnp.bfloat16)
    fU = jax.jit(lambda q,k: flash_attention(q, k, k, causal=True))
    fU(qU, kU).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(8): out = fU(qU, kU)
    out.block_until_ready(); uly = (time.perf_counter()-t0)/8
    print(json.dumps({'S': S, 'ring_compute_ms_per_device': round(ring_step*sp*1e3, 2),
                      'ulysses_compute_ms_per_device': round(uly*1e3, 2)}))
"

# 7. serve-planner calibration on the real chip, then the priced sweep
run plan_serve_calibrate 700 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    plan serve --model gpt-1b --hardware v5e-8 --calibrate
run plan_serve_sweep 300 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    plan serve --model gpt-1b --hardware v5e-8 --candidates 6

echo "battery complete; results in $OUT/"
