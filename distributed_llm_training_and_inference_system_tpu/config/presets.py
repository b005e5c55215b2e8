"""Built-in model templates and TPU hardware presets.

Parity: the reference ships MODEL_TEMPLATES for gpt-7b/gpt-13b/llama-7b
(reference llmctl/cli/commands/init.py:16-51) and an 8xA100 hardware preset
(reference configs/presets/a100x8.toml). Here the template set is wider
(125m..13b for single-chip through pod-scale work) and hardware presets are
TPU slices.
"""

from __future__ import annotations

from .schema import (
    DiffusionConfig,
    HardwareConfig,
    ModelConfig,
    MoEConfig,
    RopeConfig,
    SSMConfig,
)

# ---------------------------------------------------------------------------
# Model templates. vocab_size padded to a multiple of 128 (MXU lane width)
# except llama-7b which keeps its canonical 32000 vocab for checkpoint parity.
# ---------------------------------------------------------------------------

MODEL_TEMPLATES: dict[str, ModelConfig] = {
    "gpt-125m": ModelConfig(
        name="gpt-125m", num_layers=12, hidden_size=768, ffn_size=2048,
        num_heads=12, num_kv_heads=12, head_dim=64, vocab_size=50304,
        max_position_embeddings=2048, activation="silu",
        tie_word_embeddings=True,
    ),
    "gpt-350m": ModelConfig(
        name="gpt-350m", num_layers=24, hidden_size=1024, ffn_size=2816,
        num_heads=16, num_kv_heads=16, head_dim=64, vocab_size=50304,
        max_position_embeddings=2048, activation="silu",
        tie_word_embeddings=True,
    ),
    # gpt-750m: the single-chip benchmark flagship — the largest model whose
    # fp32-AdamW train state + grads (~11.5 GB) fits one 16 GB v5e chip with
    # batch headroom. H=2048/D=128 shapes sustain ~2.3x the matmul
    # efficiency of gpt-350m's H=1024 on the v5e MXU (measured: H=1024
    # matmuls cap at 17-30% of peak — round 1 benched gpt-350m and its
    # 0.34 MFU was the SHAPE ceiling, not a kernel deficit).
    "gpt-750m": ModelConfig(
        name="gpt-750m", num_layers=12, hidden_size=2048, ffn_size=5632,
        num_heads=16, num_kv_heads=16, head_dim=128, vocab_size=50304,
        max_position_embeddings=4096, activation="silu",
        tie_word_embeddings=True,
    ),
    "gpt-1b": ModelConfig(
        name="gpt-1b", num_layers=24, hidden_size=2048, ffn_size=5632,
        num_heads=16, num_kv_heads=16, head_dim=128, vocab_size=50304,
        max_position_embeddings=4096, activation="silu",
    ),
    # gpt-7b mirrors the reference template (init.py:17-28): 32L, 4096h,
    # 32 heads — llama-7b-shaped.
    "gpt-7b": ModelConfig(
        name="gpt-7b", num_layers=32, hidden_size=4096, ffn_size=11008,
        num_heads=32, num_kv_heads=32, head_dim=128, vocab_size=50304,
        max_position_embeddings=4096, activation="silu",
    ),
    # gpt-13b mirrors reference init.py:29-39: 40L, 5120h, 40 heads.
    "gpt-13b": ModelConfig(
        name="gpt-13b", num_layers=40, hidden_size=5120, ffn_size=13824,
        num_heads=40, num_kv_heads=40, head_dim=128, vocab_size=50304,
        max_position_embeddings=4096, activation="silu",
    ),
    # llama-7b mirrors reference configs/models/llama-7b.json:1-24 exactly.
    "llama-7b": ModelConfig(
        name="llama-7b", num_layers=32, hidden_size=4096, ffn_size=11008,
        num_heads=32, num_kv_heads=32, head_dim=128, vocab_size=32000,
        max_position_embeddings=4096, activation="silu", norm_eps=1e-5,
        rope=RopeConfig(base=10000.0, scaling="linear"),
        tie_word_embeddings=False,
    ),
    # GQA + long-context flavour (llama-2/3 style) for serve benchmarks.
    "llama-8b-gqa": ModelConfig(
        name="llama-8b-gqa", num_layers=32, hidden_size=4096, ffn_size=14336,
        num_heads=32, num_kv_heads=8, head_dim=128, vocab_size=128256,
        max_position_embeddings=8192, activation="silu", norm_eps=1e-5,
        rope=RopeConfig(base=500000.0),
    ),
    # Mistral-7B-shaped: llama architecture with GQA-8 and a 32k context
    # window (the HF llama-format import path covers it unchanged).
    "mistral-7b": ModelConfig(
        name="mistral-7b", num_layers=32, hidden_size=4096, ffn_size=14336,
        num_heads=32, num_kv_heads=8, head_dim=128, vocab_size=32000,
        max_position_embeddings=32768, activation="silu", norm_eps=1e-5,
        rope=RopeConfig(base=1000000.0),
    ),
    # Qwen2-7B-shaped: GQA-4 + ATTENTION BIAS on q/k/v (the bias flag the
    # other families leave off) + 1M rope base + large vocab.
    "qwen2-7b": ModelConfig(
        name="qwen2-7b", num_layers=28, hidden_size=3584, ffn_size=18944,
        num_heads=28, num_kv_heads=4, head_dim=128, vocab_size=152064,
        max_position_embeddings=32768, activation="silu", norm_eps=1e-6,
        rope=RopeConfig(base=1000000.0), attention_bias=True,
    ),
    # MoE template exercising the expert-parallel mesh axis (no reference
    # equivalent; SURVEY §2.2 row EP).
    "gpt-moe-8x1b": ModelConfig(
        name="gpt-moe-8x1b", num_layers=16, hidden_size=2048, ffn_size=5632,
        num_heads=16, num_kv_heads=16, head_dim=128, vocab_size=50304,
        max_position_embeddings=4096, activation="silu",
        moe=MoEConfig(num_experts=8, experts_per_token=2),
    ),
    # OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct config.json): 64 small
    # experts of width 1024, 8 a token, weights NOT renormalised over the
    # top-8, RMSNorm over the whole q / k projection before rope, MHA.
    # 6.9 B parameters, 1.3 B active a token. Served dropless
    # (models/layers.py moe_block).
    "olmoe-1b-7b": ModelConfig(
        name="olmoe-1b-7b", num_layers=16, hidden_size=2048, ffn_size=1024,
        num_heads=16, num_kv_heads=16, head_dim=128, vocab_size=50304,
        max_position_embeddings=4096, activation="silu", norm_eps=1e-5,
        rope=RopeConfig(base=10000.0), qk_norm="projection",
        moe=MoEConfig(num_experts=64, experts_per_token=8,
                      norm_topk_prob=False),
    ),
    # Mellum2-12B-A2.5B-Instruct (huggingface.co/JetBrains/..., config.json,
    # model_type mellum) at its published sizes: 28 layers, three WINDOW
    # layers (1,024 keys, plain rope) to every full layer (YaRN, factor 16
    # over 8,192, cos / sin x attention_factor), GQA 32 / 4 heads of 128
    # with per-head q/k norms, 64 experts of width 896, 8 a token,
    # renormalised, no shared expert; 12.1 B parameters, 2.5 B active.
    # Served with the window layers' K/V in a ring of pages a slot
    # (serve/kv_cache.py); 24 GB of bfloat16 weights: one chip holds a cut
    # (benchmark/configs/mellum2-12b-a2.5b-8l.json)
    "mellum2-12b-a2.5b": ModelConfig(
        name="mellum2-12b-a2.5b", num_layers=28, hidden_size=2304,
        ffn_size=896, num_heads=32, num_kv_heads=4, head_dim=128,
        vocab_size=98304, max_position_embeddings=131072,
        activation="silu", norm_eps=1e-6, qk_norm="head",
        rope=RopeConfig(base=500000.0, scaling="yarn", scaling_factor=16.0,
                        original_max_position=8192, beta_fast=32.0,
                        beta_slow=1.0,
                        attention_factor=1.2772588722239782),
        window_rope=RopeConfig(base=500000.0),
        sliding_window=1024,
        layer_types=("sliding", "sliding", "sliding", "full") * 7,
        moe=MoEConfig(num_experts=64, experts_per_token=8,
                      norm_topk_prob=True),
    ),
    # NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (huggingface.co/nvidia/...,
    # config.json, model_type nemotron_h) at its published sizes: a LAYER
    # TABLE of 52 layers, each one norm and one mixer: 23 Mamba-2
    # state-space mixers (64 heads of 64, state 128, 8 groups, conv 4), 6
    # GQA attention layers (32 / 2 heads of 128, NO position embedding)
    # and 23 expert layers (128 routed experts of width 1856, sigmoid
    # router with a selection bias, top-6 renormalised x 2.5, squared-ReLU
    # experts without a gate, one shared expert of 3712). 31.6 B
    # parameters: it fits no chip here whole; the benchmark serves one
    # chip's share (benchmark/configs/nemotron-3-nano-30b-a3b-14l-ep2.json).
    "nemotron-3-nano-30b-a3b": ModelConfig(
        name="nemotron-3-nano-30b-a3b", num_layers=52, hidden_size=2688,
        ffn_size=1856, num_heads=32, num_kv_heads=2, head_dim=128,
        vocab_size=131072, max_position_embeddings=262144,
        activation="relu2", mlp_gated=False, norm_eps=1e-5,
        position_embedding="none",
        layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        ssm=SSMConfig(num_heads=64, head_dim=64, state_size=128, n_groups=8,
                      conv_kernel=4, chunk_size=128),
        moe=MoEConfig(num_experts=128, experts_per_token=6,
                      norm_topk_prob=True, router_score="sigmoid",
                      selection_bias=True, routed_scaling_factor=2.5,
                      shared_expert_size=3712),
    ),
    # Depth-truncated gpt-7b: the SAME H=4096/D=128/F=11008 layer at 4
    # layers, so one 16 GB chip can STEP the north-star model's real
    # matmul shapes (full gpt-7b training state needs ~27 GB params+Adam
    # alone). Per-layer time measured on this proxy calibrates `plan
    # compute` for multi-chip gpt-7b predictions (BASELINE round-4).
    "gpt-7b-4l": ModelConfig(
        name="gpt-7b-4l", num_layers=4, hidden_size=4096, ffn_size=11008,
        num_heads=32, num_kv_heads=32, head_dim=128, vocab_size=50304,
        max_position_embeddings=4096, activation="silu",
    ),
    # Chip-sized MoE for single-chip measurement (BASELINE round-4 MoE
    # rows): ~0.94B total params, ~0.33B active/token (8 experts, top-2) —
    # params + AdamW state fit one 16 GB v5e the way gpt-750m does.
    "gpt-moe-1b": ModelConfig(
        name="gpt-moe-1b", num_layers=12, hidden_size=1024, ffn_size=2816,
        num_heads=8, num_kv_heads=8, head_dim=128, vocab_size=50304,
        max_position_embeddings=4096, activation="silu",
        moe=MoEConfig(num_experts=8, experts_per_token=2),
    ),
}

# Tiny models for tests/CI (not listed in user-facing templates).
TEST_TEMPLATES: dict[str, ModelConfig] = {
    "gpt-test": ModelConfig(
        name="gpt-test", num_layers=2, hidden_size=64, ffn_size=128,
        num_heads=4, num_kv_heads=2, head_dim=16, vocab_size=256,
        max_position_embeddings=128, activation="silu", dtype="float32",
    ),
    "gpt-test-moe": ModelConfig(
        name="gpt-test-moe", num_layers=2, hidden_size=64, ffn_size=128,
        num_heads=4, num_kv_heads=4, head_dim=16, vocab_size=256,
        max_position_embeddings=128, activation="silu", dtype="float32",
        moe=MoEConfig(num_experts=4, experts_per_token=2),
    ),
    # OLMoE's shape in small: many narrow experts, top-k weights not
    # renormalised, q/k projection norms, MHA.
    "olmoe-test": ModelConfig(
        name="olmoe-test", num_layers=2, hidden_size=64, ffn_size=32,
        num_heads=4, num_kv_heads=4, head_dim=16, vocab_size=256,
        max_position_embeddings=128, activation="silu", dtype="float32",
        qk_norm="projection",
        moe=MoEConfig(num_experts=8, experts_per_token=2,
                      norm_topk_prob=False),
    ),
    # sdar_moe's shape in small: generation by diffusion over blocks of 4
    # (rows of a block see each other; the mask token is the vocabulary's
    # last id), renormalised top-k experts, per-head q/k norms, GQA.
    "sdar-test": ModelConfig(
        name="sdar-test", num_layers=2, hidden_size=64, ffn_size=32,
        num_heads=4, num_kv_heads=2, head_dim=16, vocab_size=256,
        max_position_embeddings=256, activation="silu", dtype="float32",
        norm_eps=1e-6, rope=RopeConfig(base=1e6), qk_norm="head",
        moe=MoEConfig(num_experts=8, experts_per_token=2,
                      norm_topk_prob=True),
        diffusion=DiffusionConfig(block_length=4, denoising_steps=4,
                                  mask_token_id=255),
    ),
    # mellum's shape in small: two periods of three window layers (16 keys,
    # plain rope) and a full one (YaRN with an attention factor), GQA with
    # per-head q/k norms, renormalised top-2 of 8 experts. Served over
    # pages of 8, its window is two pages.
    "mellum-test": ModelConfig(
        name="mellum-test", num_layers=8, hidden_size=128, ffn_size=32,
        num_heads=4, num_kv_heads=2, head_dim=16, vocab_size=256,
        max_position_embeddings=256, activation="silu", dtype="float32",
        norm_eps=1e-6, qk_norm="head",
        rope=RopeConfig(base=10000.0, scaling="yarn", scaling_factor=4.0,
                        original_max_position=64, beta_fast=32.0,
                        beta_slow=1.0, attention_factor=1.1386294361119891),
        window_rope=RopeConfig(base=10000.0),
        sliding_window=16,
        layer_types=("sliding", "sliding", "sliding", "full") * 2,
        moe=MoEConfig(num_experts=8, experts_per_token=2,
                      norm_topk_prob=True),
    ),
    # nemotron_h's shape in small: one 7-layer motif of its layer table,
    # state-space mixers beside GQA attention without rope and sigmoid-
    # routed squared-ReLU experts, HALF of the router's 8 experts held
    # here (the other half is ``first_expert=4``), a shared expert.
    "nemotron-h-test": ModelConfig(
        name="nemotron-h-test", num_layers=7, hidden_size=64, ffn_size=32,
        num_heads=4, num_kv_heads=2, head_dim=16, vocab_size=256,
        max_position_embeddings=256, activation="relu2", mlp_gated=False,
        dtype="float32", position_embedding="none",
        layer_pattern="MEMEM*E",
        ssm=SSMConfig(num_heads=8, head_dim=8, state_size=16, n_groups=2,
                      conv_kernel=4, chunk_size=16),
        moe=MoEConfig(num_experts=4, router_experts=8, experts_per_token=3,
                      norm_topk_prob=True, router_score="sigmoid",
                      selection_bias=True, routed_scaling_factor=2.5,
                      shared_expert_size=48),
    ),
}


# Xing4.0's shape in small, in its published keys (the plain reference of
# the benchmark reads these): latent attention (two head sizes, a rope part
# under YaRN), four residual streams, one leading dense layer, then
# sigmoid-routed experts with a shared one
XING_TEST_PUBLISHED = {
    "name": "xing-test", "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "routed_scaling_factor": 2.0,
    "norm_topk_prob": True, "vocab_size": 256, "max_position_embeddings": 512,
    "rms_norm_eps": 1e-6, "dtype": "float32", "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "rope_theta": 10000.0,
    "rope_scaling": {"type": "yarn", "factor": 8, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64},
}
TEST_TEMPLATES["xing-test"] = ModelConfig.from_published(XING_TEST_PUBLISHED)

# Kimi-Linear's shape in small, in its published keys (the plain reference
# of the benchmark reads these): two periods ``K K K *`` of delta-rule
# linear attention beside latent attention without a query bottleneck and
# without rope, one leading dense layer, then sigmoid-routed experts with a
# shared one, HALF of the router's 8 experts held here. The ``K`` layers'
# chunk (published 64) is 8 here, so that a short test prompt spans several
KIMI_LINEAR_TEST_PUBLISHED = {
    "name": "kimi-linear-test", "model_type": "kimi_linear",
    "num_hidden_layers": 8, "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": None,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "mla_use_nope": True, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_experts": 4, "router_experts": 8,
    "first_expert": 0, "num_experts_per_token": 3, "num_shared_experts": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "routed_scaling_factor": 2.446, "num_expert_group": 1, "topk_group": 1,
    "vocab_size": 256, "max_position_embeddings": 512, "rms_norm_eps": 1e-5,
    "dtype": "float32", "rope_theta": 10000.0, "hidden_act": "silu",
    "linear_attn_config": {
        "num_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4,
        "kda_layers": [1, 2, 3, 5, 6, 7],
        "full_attn_layers": [4, 8]},
}
TEST_TEMPLATES["kimi-linear-test"] = ModelConfig.from_published(
    KIMI_LINEAR_TEST_PUBLISHED)

# Solar-Open2-250B as published (``model_type: solar_open2``): 48 decoder
# layers, every fourth from 0 a gated softmax GQA layer with no position
# embedding, the others delta-rule linear attention whose beta reaches 2,
# every feed-forward 320 sigmoid-routed experts of width 1280 (8 a token)
# and one shared expert. ``intermediate_size`` is used by no layer
# (``first_k_dense_replace`` 0)
SOLAR_OPEN2_PUBLISHED = {
    "name": "solar-open2-250b", "model_type": "solar_open2",
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_routed_experts": 320, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8,
    "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
}
MODEL_TEMPLATES["solar-open2-250b"] = ModelConfig.from_published(
    SOLAR_OPEN2_PUBLISHED)

# ... and its shape in small, in the same keys (the plain reference of the
# benchmark reads these): one period ``* K K K`` and one more ``*``, every
# layer's feed-forward 16 sigmoid-routed experts (3 a token) and a shared
# one. ALL 16 are held here; ``solar_open2_test_share`` is one chip's share
SOLAR_OPEN2_TEST_PUBLISHED = {
    **SOLAR_OPEN2_PUBLISHED,
    "name": "solar-open2-test", "hidden_size": 64, "num_hidden_layers": 5,
    "num_attention_heads": 4, "head_dim": 16, "num_key_value_heads": 2,
    "vocab_size": 256, "intermediate_size": 96, "moe_intermediate_size": 32,
    "max_position_embeddings": 512, "gqa_layers": [0, 4],
    "n_routed_experts": 16, "num_experts_per_tok": 3, "dtype": "float32",
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
}
TEST_TEMPLATES["solar-open2-test"] = ModelConfig.from_published(
    SOLAR_OPEN2_TEST_PUBLISHED)


def solar_open2_test_share(held: int, first: int = 0) -> ModelConfig:
    """``solar-open2-test`` with ``held`` of its 16 experts held from
    ``first`` on (a chip's share under expert parallelism 16 / ``held``):
    the router keeps its 16 outputs."""
    return ModelConfig.from_published({
        **SOLAR_OPEN2_TEST_PUBLISHED, "n_routed_experts": held,
        "router_experts": 16, "first_expert": first})


# JoyAI-LLM-Flash as published (``model_type: joyai_llm_flash``, 48B-A2.7B):
# 40 decoder layers of latent attention behind a 1,536-wide query
# bottleneck, one leading dense layer, then 256 sigmoid-routed experts of
# width 768 (8 a token) and a shared one, plain rope, and ONE next-token
# prediction module, which serves as the drafter of ``speculative: mtp``
JOYAI_LLM_FLASH_PUBLISHED = {
    "name": "joyai-llm-flash", "model_type": "joyai_llm_flash",
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-6,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280,
}
MODEL_TEMPLATES["joyai-llm-flash"] = ModelConfig.from_published(
    JOYAI_LLM_FLASH_PUBLISHED)

# ... and its shape in small, in the same keys (the plain reference of the
# benchmark reads these): a dense layer and two expert layers, 8 experts
# (3 a token) ALL held, the module behind them; ``joyai_test_share`` is one
# chip's share
JOYAI_TEST_PUBLISHED = {
    **JOYAI_LLM_FLASH_PUBLISHED,
    "name": "joyai-test", "num_hidden_layers": 3, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_head_dim": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "num_experts_per_tok": 3, "vocab_size": 256,
    "max_position_embeddings": 512, "rope_theta": 10000.0,
    "dtype": "float32",
}
TEST_TEMPLATES["joyai-test"] = ModelConfig.from_published(
    JOYAI_TEST_PUBLISHED)


def joyai_test_share(held: int, first: int = 0, vocab: int = 256,
                     **more) -> ModelConfig:
    """``joyai-test`` with ``held`` of its 8 experts held from ``first`` on
    and the first ``vocab`` rows of its vocabulary (a chip's share where 8 /
    ``held`` chips share each layer): the router keeps its 8 outputs."""
    return ModelConfig.from_published({
        **JOYAI_TEST_PUBLISHED, "n_routed_experts": held,
        "router_experts": 8, "first_expert": first, "vocab_size": vocab,
        **more})


# Falcon-H1-34B-Instruct as published (``model_type: falcon_h1``): 72
# layers, each attention (20 / 4 heads of 128, rope base 1e11) AND a Mamba-2
# mixer (32 heads of 128, 2 groups, state 256) side by side under one norm,
# then a gated MLP of width 21,504 under a second; twelve muP multipliers
FALCON_H1_34B_PUBLISHED = {
    "name": "falcon-h1-34b", "model_type": "falcon_h1",
    "hidden_size": 5120, "num_hidden_layers": 72, "num_attention_heads": 20,
    "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 21504,
    "vocab_size": 261120, "hidden_act": "silu", "rms_norm_eps": 1e-5,
    "rope_theta": 100000000000, "rope_scaling": None,
    "max_position_embeddings": 262144, "tie_word_embeddings": False,
    "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
    "attn_layer_indices": None, "num_logits_to_keep": 1,
    "mlp_expansion_factor": 8,
    "mamba_n_heads": 32, "mamba_d_head": 128, "mamba_d_ssm": 4096,
    "mamba_d_state": 256, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 128, "mamba_expand": 2, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_use_mlp": True,
    "embedding_multiplier": 5.656854249492381,
    "lm_head_multiplier": 0.0078125, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375,
    "key_multiplier": 0.011048543456039804, "ssm_in_multiplier": 0.25,
    "ssm_out_multiplier": 0.08838834764831845,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
}
MODEL_TEMPLATES["falcon-h1-34b"] = ModelConfig.from_published(
    FALCON_H1_34B_PUBLISHED)

# ... and its shape in small, in the same keys (the plain reference of the
# benchmark reads these): two published layers (``PDPD``), a query group of
# FIVE (10 / 2 heads), 2 groups of state-space heads, a chunk of 16 so that
# a short test prompt spans several, and all twelve multipliers at values
# that differ from each other and from 1 (``attention_in`` too)
FALCON_H1_TEST_PUBLISHED = {
    **FALCON_H1_34B_PUBLISHED,
    "name": "falcon-h1-test", "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 10, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 96, "vocab_size": 256,
    "max_position_embeddings": 512, "dtype": "float32",
    "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_ssm": 64,
    "mamba_d_state": 16, "mamba_chunk_size": 16,
    "embedding_multiplier": 2.5, "lm_head_multiplier": 0.6,
    "attention_in_multiplier": 1.3, "attention_out_multiplier": 0.8,
    "key_multiplier": 1.7, "ssm_in_multiplier": 0.7,
    "ssm_out_multiplier": 1.4,
    "ssm_multipliers": [0.9, 1.2, 0.75, 1.5, 1.1],
    "mlp_multipliers": [0.65, 1.6],
}
TEST_TEMPLATES["falcon-h1-test"] = ModelConfig.from_published(
    FALCON_H1_TEST_PUBLISHED)

# LFM2-8B-A1B as published (``model_type: lfm2_moe``): 24 decoder layers, 18
# of them a gated short convolution (``conv_L_cache`` 3 taps; the ``C``
# letter) and 6 grouped-query attention (32 / 8 heads of 64 = hidden /
# heads: the file has no ``head_dim``; a norm over each head's q and k;
# rope base 1e6), 2 leading dense MLPs of 7,168 and then 32 sigmoid experts
# of 1,792, 4 a token, picked by score + ``expert_bias``. Keys that are
# read: ``layer_types``, ``num_dense_layers``, ``conv_L_cache``,
# ``conv_bias`` (false alone), ``use_expert_bias``, ``norm_topk_prob``,
# ``routed_scaling_factor``, ``norm_eps`` and the usual sizes. The family
# ties its embeddings (the published config's key; stated here)
LFM2_8B_A1B_PUBLISHED = {
    "name": "lfm2-8b-a1b", "model_type": "lfm2_moe",
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": (["conv", "conv", "full_attention", "conv"] * 5
                    + ["conv", "full_attention", "conv", "conv"]),
    "max_position_embeddings": 128000, "moe_intermediate_size": 1792,
    "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
    "tie_word_embeddings": True,
}
MODEL_TEMPLATES["lfm2-8b-a1b"] = ModelConfig.from_published(
    LFM2_8B_A1B_PUBLISHED)

# ... and its shape in small, in the same keys (the plain reference of the
# benchmark reads these): six decoder layers ``c c a c c a`` with the
# ``CDCD`` head (two leading dense layers), heads of 64 (4 / 2: a PAIR of
# KV heads on the 128 lanes), 8 experts, 2 a token
LFM2_TEST_PUBLISHED = {
    **LFM2_8B_A1B_PUBLISHED,
    "name": "lfm2-test", "hidden_size": 256, "num_hidden_layers": 6,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 192, "moe_intermediate_size": 128,
    "num_experts": 8, "num_experts_per_tok": 2, "vocab_size": 320,
    "max_position_embeddings": 512, "dtype": "float32",
}
TEST_TEMPLATES["lfm2-test"] = ModelConfig.from_published(LFM2_TEST_PUBLISHED)

# Ouro-2.6B as published (``model_type: ouro``, a looped language model): 48
# layers of hidden 2,048, 16 heads of 128 with as many K/V heads, a SwiGLU of
# 5,632, an untied head over 49,152, rope base 1e6, walked ``total_ut_steps``
# 4 times over ONE set of weights. The type brings what the file has no key
# for: four norms a layer (the second of each pair on the sub-layer's output,
# inside the residual), the final norm after EVERY pass (its output is what
# the next pass reads), an exit gate on each pass's normed state, K and V
# kept per (pass, layer). ``early_exit_threshold`` 1: every token runs every
# pass (a threshold below 1 is refused at load)
OURO_2_6B_PUBLISHED = {
    "name": "ouro-2.6b", "model_type": "ouro", "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
    "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-6, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4,
    "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152,
}
MODEL_TEMPLATES["ouro-2.6b"] = ModelConfig.from_published(OURO_2_6B_PUBLISHED)

# ... and its shape in small, in the same keys: 2 passes over 3 layers of
# hidden 128 (6 planes a pool)
OURO_TEST_PUBLISHED = {
    **OURO_2_6B_PUBLISHED,
    "name": "ouro-test", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 3, "layer_types": ["full_attention"] * 3,
    "max_window_layers": 3, "num_attention_heads": 2,
    "num_key_value_heads": 2, "head_dim": 64, "total_ut_steps": 2,
    "vocab_size": 320, "max_position_embeddings": 512, "dtype": "float32",
}
TEST_TEMPLATES["ouro-test"] = ModelConfig.from_published(OURO_TEST_PUBLISHED)


def get_model_config(name: str) -> ModelConfig:
    """Look up a template by name (also accepts test templates), or read a
    model's published ``config.json`` from a path ending in ``.json``.

    Returns a deep copy so callers can mutate freely without corrupting the
    global template table.
    """
    import copy
    if name.endswith(".json"):
        # a published config.json (benchmark/configs/*.json)
        import json
        from pathlib import Path
        return ModelConfig.from_published(json.loads(Path(name).read_text()))
    if name in MODEL_TEMPLATES:
        return copy.deepcopy(MODEL_TEMPLATES[name])
    if name in TEST_TEMPLATES:
        return copy.deepcopy(TEST_TEMPLATES[name])
    raise KeyError(
        f"unknown model template {name!r}; available: "
        f"{sorted(MODEL_TEMPLATES)} (+test: {sorted(TEST_TEMPLATES)})")


# ---------------------------------------------------------------------------
# TPU hardware presets — the analog of configs/presets/a100x8.toml in the
# reference. Numbers are public v4/v5e/v5p datasheet figures.
# ---------------------------------------------------------------------------

HARDWARE_PRESETS: dict[str, HardwareConfig] = {
    "v5e-1": HardwareConfig(chip_type="v5e", num_chips=1, num_hosts=1,
                            hbm_gb_per_chip=16, peak_bf16_tflops=197,
                            hbm_bw_gbps=819, ici_bw_gbps=186, topology="1x1"),
    "v5e-4": HardwareConfig(chip_type="v5e", num_chips=4, num_hosts=1,
                            hbm_gb_per_chip=16, peak_bf16_tflops=197,
                            hbm_bw_gbps=819, ici_bw_gbps=186, topology="2x2"),
    "v5e-8": HardwareConfig(chip_type="v5e", num_chips=8, num_hosts=1,
                            hbm_gb_per_chip=16, peak_bf16_tflops=197,
                            hbm_bw_gbps=819, ici_bw_gbps=186, topology="2x4"),
    "v5e-64": HardwareConfig(chip_type="v5e", num_chips=64, num_hosts=8,
                             hbm_gb_per_chip=16, peak_bf16_tflops=197,
                             hbm_bw_gbps=819, ici_bw_gbps=186, topology="8x8"),
    "v5e-256": HardwareConfig(chip_type="v5e", num_chips=256, num_hosts=32,
                              hbm_gb_per_chip=16, peak_bf16_tflops=197,
                              hbm_bw_gbps=819, ici_bw_gbps=186, topology="16x16"),
    "v4-8": HardwareConfig(chip_type="v4", num_chips=4, num_hosts=1,
                           hbm_gb_per_chip=32, peak_bf16_tflops=275,
                           hbm_bw_gbps=1228, ici_bw_gbps=448, topology="2x2x1"),
    "v5p-8": HardwareConfig(chip_type="v5p", num_chips=4, num_hosts=1,
                            hbm_gb_per_chip=95, peak_bf16_tflops=459,
                            hbm_bw_gbps=2765, ici_bw_gbps=600, topology="2x2x1"),
    "cpu-8": HardwareConfig(platform="cpu", chip_type="cpu-fake", num_chips=8,
                            num_hosts=1, hbm_gb_per_chip=4, peak_bf16_tflops=0.2,
                            hbm_bw_gbps=50, ici_bw_gbps=10, topology="8"),
}


def get_hardware_preset(name: str) -> HardwareConfig:
    if name not in HARDWARE_PRESETS:
        raise KeyError(f"unknown hardware preset {name!r}; available: "
                       f"{sorted(HARDWARE_PRESETS)}")
    return HARDWARE_PRESETS[name]
