#!/bin/bash
# Round-3 fourth wave: re-measure everything the folded paged-attention
# kernel + T=1 window write changed (decode step 24.2 -> 13.8 ms), plus
# the accum asymptote probe.
set -u
cd "$(dirname "$0")/.."
OUT=${1:-experiments/results_r3}
mkdir -p "$OUT"
source experiments/battery_lib.sh
tpu_guard

# accum asymptote (battery-3: accum8 = 0.5187, marginal microbatch 389 ms)
run mfu_b4_sel_accum16 1500 python experiments/mfu_sweep.py 4 selective gpt-750m bfloat16 1024 true bfloat16 16

# decode throughput rows with the folded kernel: quantization should pay
# again now that matmuls are back at the weight-streaming floor
run int8_serve_v2 900 python experiments/int8_serve_bench.py
run int4_v2 900 python experiments/int4_bench.py

# ondemand load rerun for a fair A/B against battery-3's reserve run
# (both on the new kernel)
run serve_load_ondemand_v2 1500 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    bench e2e --model gpt-1b --mode serve-load --requests 32 \
    --prompt-len 512 --gen-len 128 --rps "" --concurrency 4,8,16 \
    --admission ondemand --kv-blocks 96

# light-load TTFT rerun: the K=8 dispatch is ~40% shorter now
run serve_load_light_v2 900 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    bench e2e --model gpt-1b --mode serve-load --requests 16 \
    --prompt-len 512 --gen-len 64 --rps 0.25,0.5 --concurrency 1,2 \
    --admission ondemand --kv-blocks 96

# spec profile rerun: verify-window cost under the folded kernel
run spec_profile_v2 700 python experiments/spec_profile.py gpt-1b

echo "battery4 complete; results in $OUT/"
