#!/bin/bash
# Round-4 battery 8: THE NORTH-STAR MODEL. Serve gpt-7b int8 on the real
# chip through the full stack (pre-quantized export artifact ->
# serve engine -> bench e2e serve-load), light load + saturation, plus
# serve-planner validation at the same operating points.
# Prereq: experiments/artifacts/gpt7b-int8.safetensors (llmctl export synth).
set -u
cd "$(dirname "$0")/.."
OUT=${1:-experiments/results_r4}
mkdir -p "$OUT"
source experiments/battery_lib.sh
tpu_guard

ART=experiments/artifacts/gpt7b-int8.safetensors
[ -f "$ART" ] || { echo "missing $ART (run: llmctl export synth --model gpt-7b --quant int8 --out $ART)"; exit 1; }

# Light load: open-loop 0.25 rps + closed-loop c=1 — the <200 ms p50 TTFT
# north star, measured as device TTFT (dispatch RTT excluded). At 7B shapes
# a K=8 decode dispatch occupies the device ~326 ms (profile7b: 40.8
# ms/step), so light-load TTFT hinges on dispatch granularity — measure
# with the latency-adaptive short dispatch both off and on.
run serve7b_light 3600 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    bench e2e --model gpt-7b --mode serve-load --artifact "$ART" \
    --requests 16 --prompt-len 512 --gen-len 64 \
    --rps 0.25 --concurrency 1 --admission ondemand --kv-blocks 96
run serve7b_light_adapt 3600 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    bench e2e --model gpt-7b --mode serve-load --artifact "$ART" \
    --requests 16 --prompt-len 512 --gen-len 64 \
    --rps 0.25 --concurrency 1 --admission ondemand --kv-blocks 96 \
    --latency-dispatch-steps 2

# Saturation: closed-loop c=4,8 — goodput + tails. KV: 640 tok/req =
# 10 pages; c=8 needs 80 pages live; 96 pages = 3.2 GB bf16 KV on top of
# 7.3 GB weights (the first attempt at 120 pages OOM'd the decode
# program by 118 MB — the K-step scan transiently holds ~2x the pool,
# so 7B KV budgets must leave that headroom; 16-page slack changes the
# admission regime vs the 1B rows' 96-of-96, noted in BASELINE).
run serve7b_load 3600 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    bench e2e --model gpt-7b --mode serve-load --artifact "$ART" \
    --requests 24 --prompt-len 512 --gen-len 128 \
    --rps "" --concurrency 4,8 --admission ondemand --kv-blocks 96

# int8 KV pages (160 = 2.7 GB): 2x KV capacity/byte + half the decode
# KV streaming — does it pay at 7B the way it didn't at 1B?
run serve7b_load_kv8 3600 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    bench e2e --model gpt-7b --mode serve-load --artifact "$ART" \
    --requests 24 --prompt-len 512 --gen-len 128 --kv-quant int8 \
    --rps "" --concurrency 4,8 --admission ondemand --kv-blocks 160

# 16 decode slots under int8 KV (capacity headroom): where does goodput
# knee at 7B?
run serve7b_slots16 3600 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    bench e2e --model gpt-7b --mode serve-load --artifact "$ART" \
    --requests 32 --prompt-len 512 --gen-len 128 --kv-quant int8 \
    --slots 16 --rps "" --concurrency 16 --admission ondemand \
    --kv-blocks 200

# Serve-planner calibration on the live chip at the 7B shapes: measured
# prefill/decode device times -> chip-stamped (decode_efficiency,
# mfu_prefill); `plan serve` predictions validated against the rows above.
run plan7b_calibrate 3600 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    plan serve --model gpt-7b --hardware v5e-8 --quant int8 --calibrate \
    --artifact "$ART" \
    --batch 8 --prompt-len 512 --context-len 640

echo "battery8 complete; results in $OUT/"
