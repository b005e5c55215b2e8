"""The hybrid cell (ISSUE 31): its configuration file against the catalog
row, its traffic and manifest entries as the issue names them,
``flops_hybrid.py`` by hand at the published sizes, each new reader on a
hand-made run, and the runner rehearsed on the CPU at a tiny size."""

import json
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import (end_to_end, flops_hybrid, harness, hybrid_counters,
                       layer_metrics)
from benchmark.run import load_cell, result_line
from benchmark.runners import hybrid as hybrid_runner
from manifest_pins import assert_lists, entry, listed_by

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = "nemotron-3-nano-30b-a3b-14l-ep2"
CELL = CONFIG + ".reason-batch-128"
NEW_METRICS = ["serve_programs.decode_hbm_roofline_share",
               "kernels.ssm_decode_ms_per_decode_step",
               "kernels.ssm_decode_hbm_roofline_share",
               "kernels.ssm_prefill_roofline_share",
               "kernels.moe_gmm_hbm_roofline_share",
               "moe.held_experts_hit_share", "moe.held_choice_share",
               "ssm.state_share_of_decode_bytes",
               # (the review's: what a skewed selection bias would show)
               "kernels.moe_gmm_ms_per_decode_step",
               "moe.held_expert_load_imbalance"]
APPENDED_TO = ["engine.decode_slot_utilization",
               "serve_programs.decode_step_device_ms",
               "device_idle.serve", "engine.host_ms_per_decode_step",
               "engine.prefill_stall_ms_per_decode_step",
               "engine.device_starved_share"]
# the model-configs catalog row NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, "config"
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}

TINY = {"name": "tiny-hybrid", "model_type": "nemotron_h",
        "num_hidden_layers": 7, "hybrid_override_pattern": "MEMEM*E",
        "hidden_size": 64, "intermediate_size": 32,
        "moe_intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 48, "n_shared_experts": 1,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512, "max_position_embeddings": 512,
        "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16,
        "n_groups": 2, "conv_kernel": 4, "chunk_size": 16,
        "n_routed_experts": 4, "router_experts": 8, "first_expert": 0,
        "num_experts_per_tok": 3, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "mlp_hidden_act": "relu2",
        "layer_norm_epsilon": 1e-5, "rope_theta": 10000,
        "position_embedding": "none", "tie_word_embeddings": False,
        "serve": {"dtype": "float32", "max_batch_size": 4,
                  "max_seq_len": 256, "kv_hbm_budget_gb": 0.01,
                  "prefill_chunk": 64}}
TINY_TRAFFIC = {
    "kind": "hybrid-closed", "clients": 6, "pool_per_client": 100,
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 8, "max": 120},
    "output_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                      "min": 2, "max": 24},
    "sampling": {"temperature": 0.0}, "warmup_s": 0.5, "drain_s": 10.0,
    "shape_seed": 0}


def _config():
    return load_cell(CELL, MANIFEST)["config"]


# -- the configuration, the traffic, the manifest -------------------------------

def test_only_the_three_cuts_differ_from_the_catalog_row():
    cfg = _config()
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
        "blob/main/config.json")
    differs = sorted(k for k, v in CATALOG.items() if cfg.get(k, "gone") != v)
    assert differs == sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "hybrid_override_pattern", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    for key, cut in cfg["reduced"].items():
        assert cut["published"] == CATALOG[key] and cut["here"] == cfg[key]
    assert CATALOG["hybrid_override_pattern"].startswith(
        cfg["hybrid_override_pattern"])
    assert len(cfg["hybrid_override_pattern"]) == cfg["num_hidden_layers"]
    # no width among the cuts
    assert not [k for k in entry["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    assert cfg["router_experts"] == 128 and cfg["num_experts_per_tok"] == 6
    assert cfg["position_embedding"] == "none"
    assert cfg["ssm_state_dtype"] == "float32"
    said = " ".join(cfg["assumed"])
    for word in ("position_embedding", "ssm_state_dtype", "router_experts"):
        assert word in said
    assert "2 chips share each layer" in cfg["deployment"]
    assert cfg["serve"] == {"dtype": "bfloat16", "max_batch_size": 64,
                            "max_seq_len": 2048, "kv_hbm_budget_gb": 0.25}


def test_the_program_builds_the_published_model_from_the_file():
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    cfg = _config()
    model = ModelConfig.from_dict(harness.model_dict(cfg))
    assert model.layer_pattern == "MEMEM*EMEMEM*E"
    assert (model.ssm_layers, model.moe_layers, model.kv_layers) == (6, 6, 2)
    assert (model.moe.num_experts, model.moe.router_width,
            model.moe.experts_per_token) == (64, 128, 6)
    assert model.moe.router_score == "sigmoid" and model.moe.selection_bias
    assert model.moe.shared_expert_size == 3712 and model.ffn_size == 1856
    assert model.activation == "relu2" and not model.mlp_gated
    assert model.position_embedding == "none"
    assert (model.ssm.num_heads, model.ssm.head_dim, model.ssm.state_size,
            model.ssm.n_groups, model.ssm.conv_kernel) == (64, 64, 128, 8, 4)
    assert not hasattr(model.ssm, "state_dtype")    # float32, no option
    with pytest.raises(Exception, match="float32"):
        ModelConfig.from_dict(harness.model_dict(
            dict(cfg, ssm_state_dtype="bfloat16")))
    assert model.param_count == flops_hybrid.total_params(cfg)
    hybrid_runner.require_hybrid_support(cfg)


def test_the_traffic_is_the_issues_letter_for_letter():
    t = json.loads((ROOT / "benchmark/traffic/reason-batch-128.json"
                    ).read_text())
    assert t["kind"] == "hybrid-closed"
    assert t["clients"] == 128 and t["pool_per_client"] == 1
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 128,
                                  "sigma": 0.8, "min": 32, "max": 512}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 512,
                                  "sigma": 0.6, "min": 128, "max": 1024}
    assert t["sampling"] == {"temperature": 0.0}
    assert t["shared_prefix_tokens"] == 0 and t["shape_seed"] == 0
    assert t["warmup_s"] == 10.0


def test_the_cell_and_its_metrics_are_appended_as_the_issue_names_them():
    """By name and by membership: where the cell and its entries stand in
    their lists, and what joined them since, is nobody's pin."""
    [cell] = [c for c in MANIFEST["workloads"] if c["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "reason-batch-128"
    assert CONFIG in {c["name"] for c in MANIFEST["configs"]}
    assert MANIFEST["run_seconds"] == 51
    spec = load_cell(CELL, MANIFEST)
    assert {m["name"] for m in spec["end_to_end"]} == {
        "tpot_p95_ms", "serve_tokens_per_s", "setup_s"}
    assert set(APPENDED_TO + NEW_METRICS) <= listed_by(CELL)
    for name in APPENDED_TO + NEW_METRICS:
        m = assert_lists(name, CELL)
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        layer_metrics.load(m["name"])              # its reader exists
    assert {entry(n)["layer"] for n in NEW_METRICS if "ssm" in n} == {
        "state-space mixer (ops/ssm.py, the state pools of "
        "serve/kv_cache.py)"}
    # readers that would miscount this model do not list it: the page share
    # of a GQA pool's ten-line kernel reading, the live rows of cold
    # prefills it no longer runs in its window (it rides since PR 44)
    for name in ("kernels.paged_attention_live_page_share",
                 "serve_programs.prefill_live_row_share",
                 "kernels.paged_attention_ms_per_decode_step",
                 "serve_programs.prefill_device_ms_per_ktok"):
        assert CELL not in entry(name)["workloads"], name


# -- operations and bytes by hand ------------------------------------------------

def test_hybrid_parameters_by_hand_at_the_published_sizes():
    cfg = _config()
    # a mixer: norm, W_in [2688, 4096 + 6144 + 64], conv (4 + 1) x 6144,
    # dt_bias / A_log / D, the gated norm's weight, W_out [4096, 2688]
    assert flops_hybrid.conv_channels(cfg) == 4096 + 2 * 8 * 128 == 6144
    assert flops_hybrid.mamba_layer_params(cfg) == (
        2688 + 2688 * 10304 + 5 * 6144 + 192 + 4096 + 4096 * 2688
    ) == 38_744_896
    assert flops_hybrid.attention_layer_params(cfg) == (
        2688 + 2 * 2688 * 4096 + 2 * 2688 * 256) == 23_399_040
    assert flops_hybrid.expert_params(cfg) == 2 * 2688 * 1856 == 9_977_856
    assert flops_hybrid.shared_expert_params(cfg) == 19_955_712
    assert flops_hybrid.router_params(cfg) == 2688 * 128 + 128 == 344_192
    assert flops_hybrid.total_params(cfg) == 4_584_903_936       # 4.585 B
    whole = dict(cfg, **{k: CATALOG[k] for k in cfg["reduced"]})
    whole["router_experts"] = 128
    assert (flops_hybrid.layers(whole, "M"), flops_hybrid.layers(whole, "*"),
            flops_hybrid.layers(whole, "E")) == (23, 6, 23)
    assert flops_hybrid.total_params(whole) == 31_577_940_288    # 31.58 B


def test_hybrid_bytes_and_operations_by_hand():
    cfg = _config()
    # a slot's state in one layer: h [64, 64, 128] float32 + 3 x 6144 bf16
    assert flops_hybrid.state_bytes_per_slot(cfg) == (
        2_097_152 + 36_864) == 2_134_016
    # 64 live slots x 6 layers, read and written: 1.64 GB a step
    assert flops_hybrid.state_step_bytes(cfg, 64) == (
        2 * 6 * 64 * 2_134_016) == 1_638_924_288
    assert flops_hybrid.kv_bytes_per_token(cfg) == 2 * 2 * 2 * 128 * 2 == 2048
    once = 2 * (6 * 38_744_896 + 2 * 23_399_040
                + 6 * (2688 + 344_192 + 19_955_712) + 2688 * 65_536)
    assert flops_hybrid.once_a_step_weight_bytes(cfg) == once == 1_154_487_552
    assert flops_hybrid.expert_bytes(cfg, 365.0) == 365 * 19_955_712
    step = flops_hybrid.decode_step_bytes(cfg, 40_000, 365.0, 64)
    assert step == once + 365 * 19_955_712 + 1_638_924_288 + 40_000 * 2048
    # an expert nobody chose is not read; an idle slot's state is not moved
    assert step - flops_hybrid.decode_step_bytes(cfg, 40_000, 364.0, 64) \
        == 19_955_712
    assert step - flops_hybrid.decode_step_bytes(cfg, 40_000, 365.0, 63) \
        == 2 * 6 * 2_134_016
    # the scan: 2 x 128 x (1024 + 4096) + 4 x 64 x 64 x 128 a token a layer
    assert flops_hybrid.scan_flops_per_token(cfg) == 1_310_720 + 2_097_152
    assert flops_hybrid.scan_bytes_per_token(cfg) == (
        2 * (8192 + 2048) + 256 + 2 * 4 * 524_288 / 128)


def _run(ssm, moe, scopes, decode=(10, 2.0)):
    before = {"decode_steps": 0, "prefill_padded_tokens": 0,
              **({"ssm": {k: 0 for k in ssm}} if ssm else {}),
              **({"moe": {k: ([0] * 64 if k == "choices" else 0)
                          for k in moe}} if moe else {})}
    after = {"decode_steps": 80, "prefill_padded_tokens": 4096,
             **({"ssm": ssm} if ssm else {}),
             **({"moe": moe} if moe else {})}
    return {"config": _config(), "device": {"kind": "TPU v5 lite"},
            "runner": "hybrid",
            "serve_cfg": {"decode_steps_per_dispatch": 8,
                          "max_batch_size": 64},
            "stats": {"before": before, "after": after},
            "trace_stats": {"before": before, "after": after},
            "trace": {"programs": {"decode": decode}, "scope_s": scopes,
                      "device_ops": [], "t0": 0.0, "t1": 1.0},
            "stamps": {"records": [
                {"chunks": [-1.0, 2.0], "tokens": 30, "prompt_tokens": 610}
                for _ in range(64)]}}


def test_hybrid_readers_on_a_hand_made_run():
    ssm = {"slot_steps": 80 * 60, "state_bytes": 1, "prefill_tokens": 3000,
           "prefill_padded_tokens": 4096}
    moe = {"choices": [100] * 64, "held_choices": 6400, "all_choices": 12_800,
           "experts_hit": 29_500, "layer_steps": 486,
           "decode_experts_hit": 29_200, "decode_layer_steps": 480}
    scopes = {"ssm_decode": (480, 0.32), "moe_gmm": (960, 0.8),
              "moe_gmm_prefill": (24, 0.5), "ssm_scan_prefill": (12, 0.02)}
    run = _run(ssm, moe, scopes)
    read = lambda name: layer_metrics.load(name).read(run)
    assert hybrid_counters.traced_decode_steps(run) == 80
    assert hybrid_counters.live_slots_per_step(run) == 60.0
    assert hybrid_counters.decode_experts_hit_per_step(run) == 365.0
    assert read("kernels.ssm_decode_ms_per_decode_step") == pytest.approx(4.0)
    assert read("kernels.ssm_decode_hbm_roofline_share") == pytest.approx(
        100 * (2 * 6 * 60 * 2_134_016 / 819e9) / 4e-3)
    # the prefill's grouped matmuls are not the decode step's
    assert read("kernels.moe_gmm_hbm_roofline_share") == \
        pytest.approx(100 * (365 * 19_955_712 / 819e9) / 10e-3)
    per_row = max((1_310_720 + 2_097_152) / 197e12,
                  (2 * 10_240 + 256 + 32_768) / 819e9)
    assert read("kernels.ssm_prefill_roofline_share") == pytest.approx(
        100 * 4096 * 6 * per_row / 0.02)
    assert read("moe.held_experts_hit_share") == pytest.approx(
        100 * 29_500 / (64 * 486))
    assert read("moe.held_choice_share") == pytest.approx(50.0)
    assert read("kernels.moe_gmm_ms_per_decode_step") == \
        pytest.approx(10.0)
    assert read("moe.held_expert_load_imbalance") == pytest.approx(1.0)
    uneven = _run(ssm, dict(moe, choices=[300] + [100] * 63), scopes)
    assert layer_metrics.load("moe.held_expert_load_imbalance").read(
        uneven) == pytest.approx(300 / (6600 / 64))
    # 64 requests of 610 + 15 tokens live over the stretch: 40,000
    state = 2 * 6 * 60 * 2_134_016
    floor = 1_154_487_552 + 365 * 19_955_712 + state + 40_000 * 2048
    assert read("ssm.state_share_of_decode_bytes") == pytest.approx(
        100 * state / floor)
    assert read("serve_programs.decode_hbm_roofline_share") == \
        pytest.approx(100 * (floor / 819e9) / 25e-3)
    for name in NEW_METRICS:
        if "roofline" in name:
            assert 0 < read(name) < 100, name


def test_hybrid_readers_say_nothing_where_there_is_nothing_to_read():
    """A program from before the counters and the scopes (the parent
    commit, traced with this benchmark) leaves the metrics out; so does a
    trace in which no operation carries a scope's name."""
    for run in (_run({}, {}, {}),
                dict(_run({}, {}, {}), trace={}, trace_stats=None),
                _run({"slot_steps": 10}, {}, None)):
        for name in NEW_METRICS:
            assert layer_metrics.load(name).read(run) is None, name


def test_scope_of_reads_kernel_names_and_scoped_operations():
    scope_of = hybrid_runner.scope_of
    assert scope_of(["%moe_gmm.12 = bf16[96,1856] custom-call(...)"]) == \
        "moe_gmm"
    assert scope_of(["moe_gmm_prefill.3:tpu_custom_call"]) == \
        "moe_gmm_prefill"
    assert scope_of(["%fusion.7 = f32[64] fusion(...)",
                     "jit(_decode_impl_n)/while/body/ssm_decode/mul"]) == \
        "ssm_decode"
    assert scope_of(["fusion.9", "jit(prefill)/ssm_scan_prefill/dot"]) == \
        "ssm_scan_prefill"
    assert scope_of(["fusion.9", "jit(f)/moe_experts/relu"]) is None
    assert scope_of(["%paged_attention.7 = ..."]) == "paged_attention"


def test_scope_seconds_reads_kernels_by_name_and_fusions_by_op_name():
    """A trace names an XLA operation by its HLO instruction: its scope is
    the one its ``op_name`` holds in the text of the program it ran in (a
    ``fusion.7`` of the prefill program is not the decode program's)."""
    decode_text = (
        'HloModule jit__decode_impl_n\n'
        '  %fusion.7 = f32[64,64,64,128]{3,2,1,0} fusion(%a), kind=kLoop, '
        'calls=%fc, metadata={op_name="jit(_decode_impl_n)/while/body/'
        'ssm_decode/mul" source_file="ssm.py"}\n'
        '  ROOT %fusion.9 = bf16[64,4096]{1,0} fusion(%b), kind=kLoop, '
        'metadata={op_name="jit(_decode_impl_n)/while/body/ssm_gated_norm/'
        'mul"}\n'
        '  %fusion.11 = bf16[64,2688]{1,0} fusion(%c), kind=kOutput, '
        'metadata={op_name="jit(_decode_impl_n)/while/body/dot_general"}\n')
    prefill_text = (
        '  %fusion.7 = bf16[1,256,64,64]{3,2,1,0} fusion(%a), kind=kLoop, '
        'metadata={op_name="jit(prefill)/ssm_scan_prefill/dot_general"}\n')
    assert hybrid_runner.scopes_of_instructions(decode_text) == {
        "fusion.7": "ssm_decode", "fusion.9": "ssm_gated_norm"}
    op_s = {"decode": {"fusion.7": (480, 0.32), "fusion.9": (480, 0.01),
                       "fusion.11": (960, 0.5), "moe_gmm.160": (480, 0.4),
                       "moe_gmm.161": (480, 0.4),
                       "paged_attention.3": (160, 0.05)},
            "prefill": {"fusion.7": (12, 0.02),
                        "moe_gmm_prefill.5": (24, 0.5)}}
    got = hybrid_runner.scope_seconds(
        op_s, {"_decode_impl_n": decode_text, "prefill 256": prefill_text,
               "prefill 512": prefill_text})
    assert got == {"ssm_decode": (480, 0.32), "ssm_gated_norm": (480, 0.01),
                   "moe_gmm": (960, 0.8), "paged_attention": (160, 0.05),
                   "ssm_scan_prefill": (12, 0.02),
                   "moe_gmm_prefill": (24, 0.5)}


def test_the_engine_gives_the_text_of_its_programs():
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig, ServeConfig)
    from distributed_llm_training_and_inference_system_tpu.serve import (
        InferenceEngine, SamplingParams)
    cfg = ModelConfig.from_dict(dict(harness.model_dict(TINY),
                                     dtype="float32"))
    engine = InferenceEngine(cfg, ServeConfig(model="tiny", **TINY["serve"]))
    engine.generate([[5] * 20], SamplingParams(temperature=0.0, max_tokens=4))
    texts = engine.program_texts()
    # one cold-prefill program, named by its bucket: the chunk rounded up to
    # a page, and the default page follows the K/V row's bytes since PR 58
    # (128 tokens where it was 64), so the name is read, not pinned
    [prefill] = [name for name in texts if name.startswith("prefill ")]
    assert set(texts) == {"_decode_impl_n", prefill}
    scopes = set(hybrid_runner.scopes_of_instructions(
        texts["_decode_impl_n"]).values())
    assert {"ssm_decode", "ssm_conv", "ssm_gated_norm"} <= scopes
    assert "ssm_scan_prefill" in set(hybrid_runner.scopes_of_instructions(
        texts[prefill]).values())


# -- the runner's rehearsal ------------------------------------------------------

def test_hybrid_runner_rehearsal(tmp_path, monkeypatch):
    from distributed_llm_training_and_inference_system_tpu.utils import platform
    monkeypatch.setattr(platform, "enable_compile_cache", lambda: None)
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(TINY_TRAFFIC))
    cell = {"name": "tiny.mix", "chips": 1}
    run = hybrid_runner.run(cell, TINY, str(path), 3000000019, 4.0, False,
                            time.monotonic(), require_tpu=False)
    run["runner"] = "hybrid"        # as run.py stamps it
    assert run["kind"] == "serve" and run["stamps"]["kind"] == "serve-closed"
    assert run["check"]["ok"] and run["compiled_in_window"] == 0
    assert run["check"]["tol"] == pytest.approx(
        hybrid_runner.CHECK_TOLERANCE_STD * run["check"]["logit_std"])
    assert harness.Trace is not hybrid_runner.Trace     # put back
    line = result_line(run, load_cell(CELL, MANIFEST)["end_to_end"],
                       end_to_end.load, traced=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s",
                                    "serve_tokens_per_s"}
    assert line["device"]["platform"] == "cpu"      # and so never a result
    traced = result_line(run, load_cell(CELL, MANIFEST)["per_layer"],
                         layer_metrics.load, traced=True)
    assert {"moe.held_experts_hit_share", "moe.held_choice_share",
            "engine.decode_slot_utilization"} <= set(traced["metrics"])
    assert not {n for n in NEW_METRICS if n.startswith(("kernels.",
                                                        "serve_programs."))
                } & set(traced["metrics"])
    assert 0 < traced["metrics"]["moe.held_choice_share"]["value"] < 100
    ssm = run["stats"]["after"]["ssm"]
    assert ssm["slot_steps"] > 0 and ssm["state_bytes"] > 0
    assert ssm["refused"]["prefix_caching"] > 0


def test_a_program_without_the_layer_table_is_refused(monkeypatch):
    """The parent commit drops the layer table, the state-space keys and
    the held experts without a word: the runner leaves with a reason
    before it touches a device."""
    from distributed_llm_training_and_inference_system_tpu.config import schema
    real = schema.ModelConfig.from_dict
    monkeypatch.setattr(
        schema.ModelConfig, "from_dict", classmethod(lambda cls, d: real(
            {k: v for k, v in d.items()
             if k not in ("hybrid_override_pattern", "n_routed_experts",
                          "router_experts", "mamba_num_heads")})))
    with pytest.raises(SystemExit, match="cannot run this cell"):
        hybrid_runner.run({"name": "tiny.mix", "chips": 1}, TINY, "unused", 1,
                          1.0, False, time.monotonic(), require_tpu=False)


WRONG = ("float8", "float8_experts", "norm_before_gate", "softmax_scores",
         "bias_as_weight", "rope", "padding_in_state")


def test_the_check_fails_for_each_wrong_model(monkeypatch):
    """One server, tokens it served from several slots at once held to the
    right reference and to each of the seven wrong ones (the wrong model is
    the REFERENCE's, as on the chip): the right one passes."""
    from concurrent.futures import ThreadPoolExecutor

    from benchmark.runners import serve
    from distributed_llm_training_and_inference_system_tpu.utils import platform
    monkeypatch.setattr(platform, "enable_compile_cache", lambda: None)
    harness.start(1, require_tpu=False)
    served = hybrid_runner.Served(TINY, 3000000019)
    try:
        blocks = served.params["blocks"]
        assert served.server.engine.params is served.params
        bias = abs(blocks["moe"]["router"]["bias"])
        assert 0.005 < float(bias.max()) <= 0.01       # picks, skews nothing
        assert float(abs(blocks["ssm"]["gate_norm"]["scale"]).max()) > 0.4
        assert float(abs(blocks["ssm"]["D"] - 1.0).max()) > 0.4
        rng = np.random.default_rng(5)
        prompts = [rng.integers(3, 256, n).tolist() for n in (9, 20, 33, 17)]
        with ThreadPoolExecutor(4) as pool:
            list(pool.map(lambda p: serve._post(served.url, {
                "prompt": p, "temperature": 0.0, "max_tokens": 12}),
                prompts))
        sample = list(served.served.values())
        assert sorted(s[1] for s in sample) == sorted(prompts)
        assert len({s[0] for s in sample}) > 1          # more than one slot
        right = served.check_served(sample, detail=True)
        wrong = {w: served.check_served(sample, wrong=w) for w in WRONG}
    finally:
        served.close()
    assert right["ok"] and right["tokens"] == 48 == len(right["margins"])
    assert right["tokens_under_tol"] == 0 < right["tokens_kept"]
    # (which of them a TOKEN check separates is a reading of the chip run
    # at the cell's sizes, PERF.md 6; here: each is computed on the same
    # tokens, and the grossest is seen)
    for name, check in wrong.items():
        assert check["tokens"] == right["tokens"], name
    assert max(c["worst_gap"] for c in wrong.values()) > right["worst_gap"]


def _hand_made_check(monkeypatch, gaps, margins, requests=12):
    """``check_served`` on a reference that says what it is told: request
    r's token j lies ``gaps[r][j]`` under the largest logit (of standard
    deviation 1) at a routing margin ``margins[r][j]``."""
    calls = iter(range(requests))

    def logits(params, tokens, config, positions, **kw):
        r = next(calls)
        n = len(positions)
        lg = np.tile(np.asarray([1.0, 1.0, -1.0], np.float32), (n, 1))
        lg[:, 1] -= np.asarray(gaps[r][:n], np.float32)   # the served token
        return lg, np.asarray(margins[r][:n], np.float32)
    monkeypatch.setattr(hybrid_runner.hybrid_decoder, "logits", logits)
    served = hybrid_runner.Served.__new__(hybrid_runner.Served)
    served.params, served.config = None, {}
    served.server = type("S", (), {"engine": type("E", (), {
        "_bucket": staticmethod(lambda n: n)})})
    n = len(gaps[0])
    return served.check_served([(r, [5] * 8, [1] * n)
                                for r in range(requests)])


def test_the_check_is_aware_of_ties(monkeypatch):
    """Tokens at a routing near-tie are left out; of the rest a share under
    one request's may lie further down than the tolerance; too few kept is
    not correct; one slot served wrong is not correct."""
    n = 50
    wide, tie = [0.01] * n, [0.0001] * n
    clean = [[0.0] * n for _ in range(12)]
    ok = _hand_made_check(monkeypatch, clean, [wide] * 12)
    assert ok["ok"] and ok["tokens"] == ok["tokens_kept"] == 600
    # far-down tokens at a near-tie do not count
    far_at_ties = [[5.0] * 10 + [0.0] * 40 for _ in range(12)]
    at_ties = _hand_made_check(
        monkeypatch, far_at_ties, [[0.0001] * 10 + [0.01] * 40] * 12)
    assert at_ties["ok"] and at_ties["tokens_kept"] == 480
    assert at_ties["tokens_under_tol"] == 0
    # 7 % of the kept tokens further down than the tolerance: a swap in
    # their history; 10 %: not correct
    some = [[5.0 if j < 42 and r == 0 else 0.0 for j in range(n)]
            for r in range(12)]
    assert _hand_made_check(monkeypatch, some, [wide] * 12)["ok"]
    more = [[5.0 if j * 12 + r < 60 else 0.0 for j in range(n)]
            for r in range(12)]
    bad = _hand_made_check(monkeypatch, more, [wide] * 12)
    assert not bad["ok"] and bad["tokens_under_tol"] == 60
    # one slot of the twelve served wrong, beside the right model's own 2 %
    one_slot = [[5.0 if r == 3 or j == 0 else 0.0 for j in range(n)]
                for r in range(12)]
    assert not _hand_made_check(monkeypatch, one_slot, [wide] * 12)["ok"]
    # nearly everything at a near-tie: nothing to hold, not correct
    few = _hand_made_check(monkeypatch, clean, [tie] * 11 + [wide])
    assert not few["ok"] and few["tokens_kept"] == 50
    # a gap inside the tolerance is held
    inside = _hand_made_check(monkeypatch, [[0.2] * n for _ in range(12)],
                              [wide] * 12)
    assert inside["ok"] and 0.2 < inside["tol"] < 0.25
    assert inside["tokens_off_the_reference_argmax"] == 600
