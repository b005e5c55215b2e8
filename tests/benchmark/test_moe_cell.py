"""Tests of what PR 27 adds to the benchmark: the OLMoE configuration
against its published values, the traffic mix against ``batch-64``'s, the
MoE byte and operation counts by hand, the new metric readers on hand-made
counters, the plain MoE reference against the program, and a tiny-size CPU
rehearsal of ``runners/moe.py``. No number from these tests is a device
metric.
"""

import json
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import (end_to_end, flops_moe, harness, layer_metrics,
                       moe_counters)
from benchmark.reference import moe_decoder
from benchmark.run import load_cell, result_line
from benchmark.runners import moe as moe_runner
from manifest_pins import assert_lists, entry, listed_by

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "olmoe-1b-7b-10l.moe-batch-64"
NEW_METRICS = ["serve_programs.decode_hbm_roofline_share",
               "kernels.moe_gmm_ms_per_decode_step",
               "kernels.moe_gmm_hbm_roofline_share",
               "moe.experts_hit_share", "moe.expert_load_imbalance"]
# the model-configs catalog row OLMoE-1B-7B-0125-Instruct, "config"
CATALOG = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
           "hidden_size": 2048, "intermediate_size": 1024,
           "max_position_embeddings": 4096, "model_type": "olmoe",
           "norm_topk_prob": False, "num_attention_heads": 16,
           "num_experts": 64, "num_experts_per_tok": 8,
           "num_hidden_layers": 16, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
           "tie_word_embeddings": False, "vocab_size": 50304}

TINY = {"name": "tiny-olmoe", "model_type": "olmoe", "hidden_size": 64,
        "intermediate_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "vocab_size": 512, "max_position_embeddings": 512,
        "rope_theta": 10000, "rms_norm_eps": 1e-5, "hidden_act": "silu",
        "tie_word_embeddings": False, "num_experts": 8,
        "num_experts_per_tok": 2, "norm_topk_prob": False,
        "qk_norm": "projection", "clip_qkv": None, "rope_scaling": None,
        "serve": {"dtype": "float32", "max_batch_size": 4,
                  "max_seq_len": 256, "kv_hbm_budget_gb": 0.01,
                  "prefill_chunk": 64}}
TINY_TRAFFIC = {
    "kind": "moe-closed", "clients": 3, "pool_per_client": 200,
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 8, "max": 120},
    "output_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                      "min": 2, "max": 24},
    "sampling": {"temperature": 0.0}, "warmup_s": 0.5, "drain_s": 10.0,
    "shape_seed": 0}


def _config():
    return load_cell(CELL, MANIFEST)["config"]


# -- the configuration, the traffic, the manifest -------------------------------

def test_only_depth_is_cut_from_the_catalog_row():
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "olmoe-1b-7b-10l")
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert entry["reduced"] == ["num_hidden_layers"] == list(cfg["reduced"])
    assert cfg["source"] == entry["source"] and cfg["assumed"]
    for key, value in CATALOG.items():
        if key == "num_hidden_layers":
            assert cfg[key] == 10 and cfg["reduced"][key]["published"] == 16
        else:
            assert key in cfg and cfg[key] == value, key
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    assert cfg["qk_norm"] == "projection"


def test_the_program_builds_the_published_model_from_the_file():
    from distributed_llm_training_and_inference_system_tpu.config import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    import dataclasses
    got = ModelConfig.from_dict(harness.model_dict(_config()))
    preset = get_model_config("olmoe-1b-7b")
    assert got.is_moe and got.moe.num_experts == 64
    assert got == dataclasses.replace(preset, name="olmoe-1b-7b-10l",
                                      num_layers=10)
    assert got.param_count == flops_moe.total_params(_config())


def test_the_traffic_is_batch_64s_letter_for_letter():
    ours = json.loads((ROOT / "benchmark/traffic/moe-batch-64.json")
                      .read_text())
    theirs = json.loads((ROOT / "benchmark/traffic/batch-64.json")
                        .read_text())
    assert ours.pop("kind") == "moe-closed" and ours.pop("kind_why")
    assert theirs.pop("kind") == "serve-closed"
    ours.pop("who"), theirs.pop("who")
    assert ours == theirs


def test_the_cell_lists_the_metrics_the_issue_names():
    """By name and by membership: how many entries list the cell, and which
    cells joined these entries since, is nobody's pin."""
    spec = load_cell(CELL, MANIFEST)
    assert spec["cell"]["chips"] == 1
    assert {m["name"] for m in spec["end_to_end"]} == {
        "tpot_p95_ms", "serve_tokens_per_s", "setup_s"}
    for name in NEW_METRICS:
        assert_lists(name, CELL)
    assert set(NEW_METRICS) <= {m["name"] for m in spec["per_layer"]}


# PR 25's five, as tests/benchmark/test_span_metrics.py names them
SPAN_METRICS = ["scheduler.queue_wait_p95_ms",
                "engine.host_ms_per_decode_step",
                "engine.prefill_stall_ms_per_decode_step",
                "engine.device_starved_share",
                "kernels.paged_attention_ms_per_decode_step"]
EARLIER_CELLS = ["mistral-7b-16l.chat", "internlm2-1.8b-6l.pretrain-4k",
                 "mistral-7b-16l.batch-64",
                 "internlm2-1.8b.pretrain-4k-fsdp4"]


@pytest.mark.parametrize("cell,names", [
    ("mistral-7b-16l.chat", SPAN_METRICS),
    ("mistral-7b-16l.batch-64", SPAN_METRICS[1:]),
    ("internlm2-1.8b-6l.pretrain-4k", []),
    (CELL, SPAN_METRICS[1:]),
])
def test_span_metrics_keep_their_cells_layers_and_keys(cell, names):
    """Every cell lists PR 25's metrics that it listed, and each entry keeps
    its layer, what it moves and its keys."""
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in SPAN_METRICS}
    for name in SPAN_METRICS:
        m = entry(name)
        assert m["layer"] in layers and m["moves"] == "tpot_p95_ms"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for name in names:
        assert_lists(name, cell)
    assert listed_by(cell) & set(SPAN_METRICS) == set(names)


def test_earlier_entries_keep_their_cells_and_their_keys():
    """An earlier metric that lists the cell keeps the cells it had, and no
    entry has a key the contract does not name."""
    cells = [c["name"] for c in MANIFEST["workloads"]]
    assert set(EARLIER_CELLS + [CELL]) <= set(cells)
    assert {"mistral-7b-16l", "internlm2-1.8b-6l", "internlm2-1.8b",
            "olmoe-1b-7b-10l"} <= {c["name"] for c in MANIFEST["configs"]}
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in NEW_METRICS}
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        listed = m.get("workloads", [])
        assert listed == [c for c in cells if c in listed]   # manifest order
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "layer", "moves", "workloads"}
    for name in NEW_METRICS[:3]:
        assert entry(name)["layer"] in layers    # the kernels' layer exists


# -- operations and bytes, by hand ----------------------------------------------

def test_moe_bytes_and_operations_by_hand():
    cfg = _config()
    attn = 4 * 2048 * 2048                      # q, k, v, o at MHA
    assert flops_moe.expert_params(cfg) == 3 * 2048 * 1024 == 6_291_456
    assert flops_moe.shared_matmul_params(cfg) == (
        10 * (attn + 2048 * 64) + 2048 * 50304) == 272_105_472
    # 10 x 419.6 M + head + embedding = 4.40 B (the issue's count)
    assert flops_moe.total_params(cfg) == (
        272_105_472 + 2048 * 50304 + 10 * 64 * 6_291_456
        + 10 * (2 * 2048 + 2 * 2048) + 2048) == 4_401_743_872
    assert flops_moe.kv_bytes_per_token(cfg) == 81_920
    # every expert of every layer hit, 16,000 live tokens
    all_hit = flops_moe.decode_step_bytes(cfg, 16_000, 640)
    assert all_hit == (2 * 272_105_472 + 640 * 2 * 6_291_456
                       + 16_000 * 81_920) == 9_907_994_624
    # an expert nobody chose is not read
    assert all_hit - flops_moe.decode_step_bytes(cfg, 16_000, 630) == (
        10 * 12_582_912)
    assert flops_moe.expert_bytes(cfg, 627.5) == 627.5 * 12_582_912
    # 1.55 GFLOP a token: 2 x (272.1 M + 10 x 8 x 6.29 M)
    assert flops_moe.forward_flops_per_token(cfg) == 2.0 * (
        272_105_472 + 80 * 6_291_456) == 1_550_843_904.0


def _run(before, after, ops, decode=(10, 2.0)):
    return {"config": _config(), "device": {"kind": "TPU v5 lite"},
            "runner": "moe",
            "serve_cfg": {"decode_steps_per_dispatch": 8,
                          "max_batch_size": 32},
            "stats": {"before": before, "after": after},
            "trace_stats": {"before": before, "after": after},
            "trace": {"programs": {"decode": decode}, "device_ops": ops,
                      "t0": 0.0, "t1": 1.0},
            "stamps": {"records": [
                {"chunks": [-1.0, 2.0], "tokens": 30, "prompt_tokens": 985}
                for _ in range(16)]}}


def test_moe_readers_on_hand_made_counters():
    moe0 = {"choices": [0] * 64, "experts_hit": 0, "layer_steps": 0,
            "decode_experts_hit": 0, "decode_layer_steps": 0}
    choices = [100] * 63 + [163]
    moe1 = {"choices": choices, "experts_hit": 50_000 + 640,
            "layer_steps": 810, "decode_experts_hit": 50_000,
            "decode_layer_steps": 800}
    ops = [["moe_gmm.3:tpu_custom_call", 0.5], ["moe_gmm.4", 0.25],
           ["moe_gmm.5:tpu_custom_call", 0.25],
           ["moe_gmm_prefill.7:tpu_custom_call", 9.0], ["fusion.1", 0.3]]
    run = _run({"moe": moe0}, {"moe": moe1}, ops)
    read = lambda name: layer_metrics.load(name).read(run)
    assert moe_counters.decode_experts_hit_per_step(run) == 625.0
    assert read("moe.experts_hit_share") == pytest.approx(
        100 * 50_640 / (64 * 810))
    assert read("moe.expert_load_imbalance") == pytest.approx(
        163 / (sum(choices) / 64))
    # 80 steps traced: the prefill's kernels are not the decode step's
    assert read("kernels.moe_gmm_ms_per_decode_step") == pytest.approx(12.5)
    assert read("kernels.moe_gmm_hbm_roofline_share") == pytest.approx(
        100 * 625 * 12_582_912 / 819e9 / 12.5e-3)
    # 16 requests of 985 + 15 tokens live over the stretch: 16,000
    floor = (2 * 272_105_472 + 625 * 12_582_912 + 16_000 * 81_920) / 819e9
    assert read("serve_programs.decode_hbm_roofline_share") == \
        pytest.approx(100 * floor / 25e-3)
    assert read("serve_programs.decode_hbm_roofline_share") < 100


def test_moe_readers_say_nothing_where_there_is_nothing_to_read():
    """A program from before the counters and the kernel (the parent
    commit, traced with this benchmark) leaves the metrics out."""
    run = _run({}, {}, [["fusion.1", 0.3]])
    for name in NEW_METRICS:
        assert layer_metrics.load(name).read(run) is None, name
    untraced = dict(_run({}, {}, []), trace={}, trace_stats=None)
    for name in NEW_METRICS:
        assert layer_metrics.load(name).read(untraced) is None, name


# -- the reference, and the runner's rehearsal ----------------------------------

def test_moe_reference_is_independent_and_masks_by_the_top_k():
    import inspect
    src = inspect.getsource(moe_decoder)
    assert "distributed_llm_training" not in src and "import" in src
    import jax
    import jax.numpy as jnp
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    cfg = ModelConfig.from_dict(dict(harness.model_dict(TINY),
                                     dtype="float32"))
    params = gpt.init(cfg, jax.random.PRNGKey(3))
    tokens = np.random.default_rng(0).integers(258, 512, 33)
    want = np.asarray(moe_decoder.logits(params, tokens, TINY))
    got = np.asarray(gpt.forward(params, jnp.asarray(tokens[None]), cfg))[0]
    # float32 on both sides, sums in another order: see tests/test_olmoe.py
    assert np.abs(got - want).max() < 1e-4
    some = np.asarray(moe_decoder.logits(params, tokens, TINY,
                                         positions=[5, 32]))
    np.testing.assert_allclose(some, want[[5, 32]], atol=1e-6)
    # with k = E every expert weighs in by its whole probability
    dense_weights = np.asarray(moe_decoder.logits(
        params, tokens, dict(TINY, num_experts_per_tok=8)))
    assert np.abs(dense_weights - want).max() > 1e-4


def test_moe_runner_rehearsal(tmp_path, monkeypatch):
    from distributed_llm_training_and_inference_system_tpu.utils import platform
    monkeypatch.setattr(platform, "enable_compile_cache", lambda: None)
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(TINY_TRAFFIC))
    cell = {"name": "tiny.mix", "chips": 1}
    run = moe_runner.run(cell, TINY, str(path), 3000000019, 4.0, False,
                         time.monotonic(), require_tpu=False)
    run["runner"] = "moe"           # as run.py stamps it
    assert run["kind"] == "serve" and run["stamps"]["kind"] == "serve-closed"
    assert run["check"]["ok"] and run["compiled_in_window"] == 0
    assert run["check"]["tol"] == pytest.approx(
        moe_runner.CHECK_TOLERANCE_STD * run["check"]["logit_std"])
    line = result_line(run, load_cell(CELL, MANIFEST)["end_to_end"],
                       end_to_end.load, traced=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s",
                                    "serve_tokens_per_s"}
    assert line["device"]["platform"] == "cpu"      # and so never a result
    # the counters are read on the CPU too; the trace's readers say nothing
    traced = result_line(run, load_cell(CELL, MANIFEST)["per_layer"],
                         layer_metrics.load, traced=True)
    assert {"moe.experts_hit_share", "moe.expert_load_imbalance",
            "engine.decode_slot_utilization"} <= set(traced["metrics"])
    assert not {"kernels.moe_gmm_ms_per_decode_step",
                "kernels.moe_gmm_hbm_roofline_share",
                "serve_programs.decode_hbm_roofline_share",
                "serve_programs.decode_step_device_ms"} & set(
        traced["metrics"])
    assert 0 < traced["metrics"]["moe.experts_hit_share"]["value"] <= 100
    assert traced["metrics"]["moe.expert_load_imbalance"]["value"] >= 1.0
    moe = run["stats"]["after"]["moe"]
    assert sum(moe["choices"]) > 0 and moe["layer_steps"] > 0


def test_a_program_that_reads_the_model_as_dense_is_refused(monkeypatch):
    """The parent commit drops ``num_experts`` and ``qk_norm`` without a
    word: the runner leaves with a reason before it touches a device."""
    from distributed_llm_training_and_inference_system_tpu.config import schema
    moe_runner.require_moe_support(TINY)
    real = schema.ModelConfig.from_dict
    monkeypatch.setattr(
        schema.ModelConfig, "from_dict", classmethod(lambda cls, d: real(
            {k: v for k, v in d.items()
             if k not in ("num_experts", "num_experts_per_tok")})))
    with pytest.raises(SystemExit, match="cannot run this cell"):
        moe_runner.run({"name": "tiny.mix", "chips": 1}, TINY, "unused", 1,
                       1.0, False, time.monotonic(), require_tpu=False)


@pytest.mark.parametrize("served_with,reference_with", [
    # the served model renormalises, the reference (the published form)
    # does not
    ({"norm_topk_prob": True}, {"norm_topk_prob": False}),
    # the reference leaves the q/k norms out: the runner's seeded scales
    # are what makes that visible
    ({}, {"qk_norm": "none"}),
])
def test_the_check_sees_what_the_configuration_names(monkeypatch,
                                                     served_with,
                                                     reference_with):
    from distributed_llm_training_and_inference_system_tpu.utils import platform
    monkeypatch.setattr(platform, "enable_compile_cache", lambda: None)
    harness.start(1, require_tpu=False)
    config = dict(TINY, **served_with)
    served = moe_runner.Served(config, 3000000019)
    try:
        scales = served.params["blocks"]["q_norm"]["scale"]
        assert served.server.engine.params is served.params
        right = served.check_against_reference(3000000019)
        wrong = served.check_against_reference(
            3000000019, config=dict(config, **reference_with))
    finally:
        served.close()
    assert float(np.abs(np.asarray(scales)).max()) > 0.4
    assert right["ok"] and right["worst_gap"] <= wrong["worst_gap"]
    assert right["tokens"] == wrong["tokens"] > 0
    if "qk_norm" in reference_with:
        assert not wrong["ok"]
