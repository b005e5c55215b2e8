#!/bin/bash
# Round-4 battery 14: pipelined decode dispatch A/B (the round's serve
# throughput lever). The engine keeps one un-fetched K-step dispatch in
# flight and chains the next on the device-resident scan carry, so the
# per-dispatch host round trip overlaps execution. Battery-8/10
# measured the unpipelined baselines; these rows are the same cells with
# --pipelined, interleaved off-runs re-measured for drift control.
set -u
cd "$(dirname "$0")/.."
OUT=${1:-experiments/results_r4}
mkdir -p "$OUT"
source experiments/battery_lib.sh
tpu_guard

ART=experiments/artifacts/gpt7b-int8.safetensors

# 1B saturation: pipelined on/off interleaved x2
for i in 1 2; do
  run pipe1b_c8_on_$i 900 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
      bench e2e --model gpt-1b --mode serve-load --requests 32 \
      --prompt-len 512 --gen-len 128 --rps "" --concurrency 8 \
      --admission ondemand --kv-blocks 96 --pipelined
  run pipe1b_c8_off_$i 900 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
      bench e2e --model gpt-1b --mode serve-load --requests 32 \
      --prompt-len 512 --gen-len 128 --rps "" --concurrency 8 \
      --admission ondemand --kv-blocks 96
done

# 1B decode-dominated at 16/32 slots (battery-10 cells, pipelined)
run pipe1b_slots16_decode 900 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    bench e2e --model gpt-1b --mode serve-load --requests 48 \
    --prompt-len 64 --gen-len 256 --rps "" --concurrency 16 \
    --slots 16 --admission ondemand --kv-blocks 112 --pipelined
run pipe1b_slots32_decode 1200 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    bench e2e --model gpt-1b --mode serve-load --requests 64 \
    --prompt-len 64 --gen-len 256 --rps "" --concurrency 32 \
    --slots 32 --admission ondemand --kv-blocks 208 --pipelined

# 7B saturation pipelined (vs battery-8's 95.8 tok/s at c8). A queued
# second dispatch may hold another pool transient on top of the measured
# 2x (battery-8 OOM rule) — if 96 pages OOM, the 72-page run below
# carries the A/B (slightly throttled admission: 72 < the 80 live pages
# c8 wants).
run pipe7b_c8 3600 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    bench e2e --model gpt-7b --mode serve-load --artifact "$ART" \
    --requests 24 --prompt-len 512 --gen-len 128 \
    --rps "" --concurrency 8 --admission ondemand --kv-blocks 96 --pipelined
if grep -q "Ran out of memory\|RESOURCE_EXHAUSTED" "$OUT/pipe7b_c8.log"; then
  run pipe7b_c8_72p 3600 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
      bench e2e --model gpt-7b --mode serve-load --artifact "$ART" \
      --requests 24 --prompt-len 512 --gen-len 128 \
      --rps "" --concurrency 8 --admission ondemand --kv-blocks 72 --pipelined
fi

# light-load sanity: the occupancy gate must keep pipelining OUT of the
# TTFT path — expect p50/p99 ~= the battery-8 unpipelined rows
run pipe7b_light_gate 3600 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    bench e2e --model gpt-7b --mode serve-load --artifact "$ART" \
    --requests 16 --prompt-len 512 --gen-len 64 \
    --rps 0.25 --concurrency 1 --admission ondemand --kv-blocks 96 --pipelined

echo "battery14 complete; results in $OUT/"
