#!/bin/bash
# Round-3 second-wave TPU measurements (run AFTER tpu_battery.sh):
#  - MFU levers untested by the first pass: selective_attn now that bf16 nu
#    freed ~1.4 GB, and gradient accumulation amortising the optimizer tail
#  - ring-vs-ulysses calibration on the real chip (tune sp)
# Results land in experiments/results_r3/ like the first battery.
set -u
cd "$(dirname "$0")/.."
OUT=${1:-experiments/results_r3}
mkdir -p "$OUT"

source experiments/battery_lib.sh   # cwd is the repo root after the cd
tpu_guard

# selective_attn with both moments bf16 (untested combination)
run mfu_b4_selattn_nubf16 700 python experiments/mfu_sweep.py 4 selective_attn gpt-750m bfloat16 1024 true bfloat16
run mfu_b4_selattn_nubf16_c2048 700 python experiments/mfu_sweep.py 4 selective_attn gpt-750m bfloat16 2048 true bfloat16

# gradient accumulation: same microbatch, optimizer amortised 2x / 4x
run mfu_b4_accum2 700 python experiments/mfu_sweep.py 4 selective gpt-750m bfloat16 1024 true bfloat16 2
run mfu_b4_accum4 900 python experiments/mfu_sweep.py 4 selective gpt-750m bfloat16 1024 true bfloat16 4
run mfu_b4_selattn_accum4 900 python experiments/mfu_sweep.py 4 selective_attn gpt-750m bfloat16 1024 true bfloat16 4

# spec-profile rerun: the first battery's runs timed out lowering 2.9 GB
# of closure-captured weights (fixed: params passed as a jit argument)
run spec_profile_paged 700 python experiments/spec_profile.py gpt-1b

# reserve-admission load sweep rerun: the first battery's run died
# RESOURCE_EXHAUSTED on its 4th engine (fixed: engine.release() between
# sweep points)
run serve_load_reserve 900 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    bench e2e --model gpt-1b --mode serve-load --requests 32 \
    --prompt-len 512 --gen-len 128 --rps 2,6,12 --concurrency 4,8,16 \
    --admission reserve --kv-blocks 96

# int4 rerun with the kernel-oriented packed layout (the first battery
# measured 19.6 tok/s — the old layout's per-layer fp32 transpose inside
# the decode scan)
run int4_only 900 python experiments/int4_bench.py

# decode-step component ablation: where the ~35 ms device step goes
run decode_profile 700 python experiments/decode_profile.py gpt-1b 8 512 8

# sub-saturation serve load: the unloaded device-TTFT figure (the first
# battery's rps 2-12 grid all sits past the ~0.9 req/s saturation point
# for 128-token gens, so every TTFT there is queue-dominated)
run serve_load_light 900 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    bench e2e --model gpt-1b --mode serve-load --requests 16 \
    --prompt-len 512 --gen-len 64 --rps 0.25,0.5 --concurrency 1,2 \
    --admission ondemand --kv-blocks 96

# speculation crossover rerun: the first battery's run tripped a bitwise
# assert on the TPU verify-vs-decode tiling divergence (now reported as
# diverged_streams instead — the curve keys on MEASURED acceptance)
run spec_crossover 1200 python experiments/spec_crossover.py gpt-1b 8 7

# ring-vs-ulysses per-scheme efficiencies, persisted for the planner
run tune_sp 700 python -m distributed_llm_training_and_inference_system_tpu.cli.main \
    tune sp --seq-lens 8192,16384 --sp 8

echo "battery2 complete; results in $OUT/"
