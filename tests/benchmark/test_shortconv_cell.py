"""The short-conv cell (lfm2-8b-a1b-16l.assist-batch-256): the manifest's
new entries as ISSUE 55 names them, its configuration file against a literal
copy of the catalog row but for the ``reduced`` keys, its traffic against
the issue, the plain reference's independence, ``flops_shortconv.py``
against the issue's hand arithmetic, each new metric's reader on a recorded
run dict, and the runner's rehearsal at a tiny size on the CPU. Nothing
here pins a position in a list of ``BENCHMARK.json`` or a count of its
entries."""

import ast
import json
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import (end_to_end, flops_shortconv, harness, layer_metrics,
                       shortconv_counters)
from benchmark.reference import shortconv_decoder
from benchmark.run import load_cell, result_line
from benchmark.runners import shortconv as runner
from manifest_pins import assert_lists, listed_by

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = "lfm2-8b-a1b-16l"
CELL = CONFIG + ".assist-batch-256"
NEW_METRICS = {
    "serve_programs.decode_hbm_roofline_share":
        "serve_tokens_per_s",
    "kernels.moe_gmm_ms_per_decode_step": "tpot_p95_ms",
    "kernels.moe_gmm_hbm_roofline_share": "serve_tokens_per_s",
    "kernels.paged_attention_ms_per_decode_step": "tpot_p95_ms",
    "kernels.paged_attention_roofline_share": "serve_tokens_per_s",
    "kernels.paged_attention_live_page_share":
        "serve_tokens_per_s",
    "kernels.shortconv_mixer_ms_per_decode_step": "tpot_p95_ms",
    "kernels.shortconv_mixer_hbm_roofline_share": "serve_tokens_per_s",
    "moe.experts_hit_share": "serve_tokens_per_s",
    "moe.expert_load_imbalance": "serve_tokens_per_s",
    "engine.prefill_ride_token_share": "serve_tokens_per_s",
}
# (``serve_programs.decode_step_device_ms`` stands for the issue's
# ``serve_programs.shortconv_decode_step_device_ms``: the same reading. The
# names are the un-prefixed entries' since PR 59, which folded this cell's
# copies of shared readers into them; the values are what ISSUE 55 had each
# copy move)
APPENDED_TO = ["engine.decode_slot_utilization",
               "serve_programs.decode_step_device_ms", "device_idle.serve",
               "engine.host_ms_per_decode_step",
               "engine.prefill_stall_ms_per_decode_step",
               "engine.device_starved_share", "startup.import_s",
               "startup.program_lowering_s", "startup.program_compile_s",
               "startup.programs", "startup.cache_misses",
               "startup.engine_work_s", "startup.unattributed_s"]
# the model-configs catalog row LFM2-8B-A1B, "config", copied literally
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv", "full_attention", "conv",
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
TINY = {"name": "tiny-shortconv", "model_type": "lfm2_moe",
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 256,
        "intermediate_size": 192, "num_hidden_layers": 6,
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                        "full_attention"],
        "max_position_embeddings": 512, "moe_intermediate_size": 128,
        "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 4,
        "num_dense_layers": 2, "num_experts": 8, "num_experts_per_tok": 2,
        "num_key_value_heads": 2, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 512, "tie_word_embeddings": True,
        "serve": {"dtype": "float32", "max_batch_size": 4,
                  "max_seq_len": 256, "kv_block_size": 16,
                  "kv_hbm_budget_gb": 0.01, "prefill_chunk": 64,
                  "prefix_caching": False}}
TINY_TRAFFIC = {
    "kind": "shortconv-closed", "clients": 8, "pool_per_client": 100,
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 8, "max": 120},
    "output_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                      "min": 2, "max": 24},
    "sampling": {"temperature": 0.0}, "warmup_s": 0.5, "drain_s": 10.0,
    "shape_seed": 0}


def _config():
    return load_cell(CELL, MANIFEST)["config"]


# -- the configuration, the traffic, the manifest -------------------------------

def test_only_the_depth_differs_from_the_catalog_row():
    config = _config()
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    for key, value in CATALOG.items():
        if key == "num_hidden_layers":
            assert config[key] == 16
        elif key == "layer_types":      # cut WITH the depth, a whole period
            assert config[key] == value[:16]
            assert value[:16] == ["conv", "conv", "full_attention",
                                  "conv"] * 4
        else:
            assert config[key] == value, key
    assert set(config["reduced"]) == {"num_hidden_layers"}
    assert config["reduced"]["num_hidden_layers"]["published"] == 24
    # every published width and count, unchanged
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["num_experts"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["intermediate_size"], config["conv_L_cache"],
            config["vocab_size"]) == (2048, 32, 8, 32, 1792, 4, 7168, 3,
                                      65536)
    assumed = " ".join(config["assumed"])
    for word in ("tie_word_embeddings", "head_dim 64", "sigmoid",
                 "expert_bias", "1e-20", "bfloat16"):
        assert word in assumed, word
    assert "two pipeline stages" in config["deployment"]
    assert config["serve"] == {
        "dtype": "bfloat16", "max_batch_size": 256, "max_seq_len": 2048,
        "kv_block_size": 256, "kv_hbm_budget_gb": 3.0,
        "chunked_prefill_tokens": 1024, "prefix_caching": False,
        "max_queue": 512}
    assert config["serve_why"]


def test_the_program_builds_the_cut_model_from_the_file():
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    config = _config()
    for model in (ModelConfig.from_dict(runner.model_dict(config)),
                  ModelConfig.from_published(config)):
        assert model.layer_pattern == "CDCD*ECE" + "CECE*ECE" * 3
        assert model.tie_word_embeddings and model.head_dim == 64
        assert model.param_count == flops_shortconv.total_params(config)
    runner.require_shortconv_support(config)


def test_the_traffic_is_the_issues():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "assist-batch-256", 1)
    t = json.loads(Path(load_cell(CELL, MANIFEST)["traffic_path"]
                        ).read_text())
    assert t["kind"] == "shortconv-closed"
    assert (t["clients"], t["pool_per_client"], t["shape_seed"]) == (512, 2,
                                                                     0)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 256,
                                  "sigma": 0.8, "min": 32, "max": 1024}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 384,
                                  "sigma": 0.6, "min": 64, "max": 1024}
    assert t["sampling"] == {"temperature": 0.0}
    assert t["shared_prefix_tokens"] == 0
    assert (t["warmup_s"], t["drain_s"]) == (10.0, 20.0)
    # a reply fits its slot: the longest prompt and the longest reply
    assert 1024 + 1024 <= _config()["serve"]["max_seq_len"]


# this mechanism's alone; the others are entries other cells list too
OWN = {"kernels.shortconv_mixer_ms_per_decode_step": "tpot_p95_ms",
       "kernels.shortconv_mixer_hbm_roofline_share": "serve_tokens_per_s"}


def test_the_cell_reports_the_metrics_the_issue_names():
    """By name and by membership (PR 59): the un-prefixed entries list the
    cell beside others."""
    spec = load_cell(CELL, MANIFEST)
    assert {m["name"] for m in spec["end_to_end"]} == {
        "tpot_p95_ms", "serve_tokens_per_s", "setup_s"}
    for name in NEW_METRICS:
        m = assert_lists(name, CELL)
        if name in OWN:
            assert m["workloads"] == [CELL] and m["moves"] == OWN[name], name
        layer_metrics.load(name)            # a reader of that name exists
    for name in APPENDED_TO:
        assert_lists(name, CELL)
    assert set(NEW_METRICS) | set(APPENDED_TO) <= listed_by(CELL)
    for m in MANIFEST["per_layer"]:
        if "roofline" in m["name"]:
            assert m["unit"] == "%", m["name"]


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse(Path(shortconv_decoder.__file__).read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "functools", "jax"}


# -- flops_shortconv.py against the issue's arithmetic --------------------------

def test_shortconv_parameters_by_hand_at_the_published_sizes():
    c = _config()
    assert flops_shortconv.expert_params(c) == 3 * 2048 * 1792 == 11_010_048
    assert flops_shortconv.conv_mixer_params(c) == (
        2048 * 6144 + 2048 * 2048 + 3 * 2048) == 16_783_360
    assert flops_shortconv.attention_params(c) == (
        2 * 2048 * 2048 + 2 * 2048 * 512 + 128) == 10_485_888
    assert flops_shortconv.dense_mlp_params(c) == 3 * 2048 * 7168
    assert [flops_shortconv.layers(c, k) for k in (
        "conv", "full_attention", "dense", "experts")] == [12, 4, 2, 14]
    # 10.8 GB of weights in bfloat16: experts 9.86, mixers 0.40, dense MLPs
    # 0.18, attention 0.08, the ONE table 0.27
    total = 2 * flops_shortconv.total_params(c)
    assert 10.78e9 < total < 10.82e9
    assert 14 * 32 * 11_010_048 * 2 == pytest.approx(9.86e9, rel=2e-3)
    whole = dict(CATALOG, tie_word_embeddings=True)
    assert 16.6e9 < 2 * flops_shortconv.total_params(whole) < 16.8e9


def test_shortconv_bytes_by_hand():
    c = _config()
    assert flops_shortconv.kv_bytes_per_token(c) == 4 * 8 * 64 * 2 * 2 == 8192
    assert flops_shortconv.window_bytes_per_slot(c) == 2 * 2048 * 2
    # 12 layers x 2 rows x 256 slots x 2,048 x 2 B = 25 MB, read and written
    assert flops_shortconv.window_step_bytes(c, 256) == 2 * 25_165_824
    assert flops_shortconv.mixer_step_bytes(c, 256) == (
        12 * 16_783_360 * 2 + 2 * 25_165_824)
    # the step's floor at every expert hit and ~580 tokens a slot: 12.0 GB,
    # 14.7 ms at 819 GB/s
    step = flops_shortconv.decode_step_bytes(c, 580 * 256, 14 * 32, 256)
    assert 11.9e9 < step < 12.15e9
    assert step / 819e9 == pytest.approx(14.7e-3, rel=0.02)
    # the experts are 82 % of what a step reads
    assert flops_shortconv.expert_bytes(c, 14 * 32) / step == pytest.approx(
        0.82, abs=0.01)


# -- the readers on a recorded run ----------------------------------------------

def _run(shortconv=True, scopes=None, decode=(10, 1.6)):
    scopes = {"moe_gmm": (2240, 0.96), "paged_attention": (320, 0.24),
              "shortconv_mixer": (4000, 0.16),
              "shortconv_step": (960, 0.02)} if scopes is None else scopes

    def stats(steps, moe, pages, table, ride):
        return {"decode_steps": steps, "prefill_tokens": ride * 5 // 4,
                "prefill_ride_tokens": ride,
                "moe": moe,
                "kv": {"live_pages": pages, "table_pages": table,
                       "page_size": 256, "kind": "kv"},
                **({"shortconv": {"slot_steps": steps * 250,
                                  "state_bytes": 25_165_824}}
                   if shortconv else {})}
    zero = {"choices": [0] * 32, "experts_hit": 0, "layer_steps": 0,
            "decode_experts_hit": 0, "decode_layer_steps": 0}
    # 80 steps of 14 expert layers, 440 of 448 (layer, expert) pairs hit
    moe = {"choices": [1000] * 31 + [1500], "experts_hit": 80 * 440,
           "layer_steps": 80 * 14, "decode_experts_hit": 80 * 440,
           "decode_layer_steps": 80 * 14}
    before = stats(0, zero, 0, 0, 0)
    after = stats(80, moe, 10 * 700, 10 * 2048, 6000)
    return {"config": _config(), "device": {"kind": "TPU v5 lite"},
            "runner": "shortconv",
            "serve_cfg": {"decode_steps_per_dispatch": 8,
                          "max_batch_size": 256},
            "stats": {"before": before, "after": after},
            "trace_stats": {"before": before, "after": after},
            "trace": {"programs": {"decode": decode}, "scope_s": scopes,
                      "decode_scope_s": scopes, "device_ops": [],
                      "t0": 0.0, "t1": 1.0},
            "stamps": {"records": []}}


def test_shortconv_readers_on_a_hand_made_run():
    run = _run()
    c, peak = run["config"], 819e9

    def read(name):
        return layer_metrics.load(name).read(run)
    # 10 dispatches of 8 steps in 1.6 s of device time
    assert read("serve_programs.decode_step_device_ms") == pytest.approx(20.0)
    assert shortconv_counters.decode_experts_hit_per_step(run) == 440
    assert shortconv_counters.live_kv_tokens(run) == 700 * 256
    moved = flops_shortconv.decode_step_bytes(c, 700 * 256, 440, 256)
    assert read("serve_programs.decode_hbm_roofline_share") == \
        pytest.approx(100 * moved / peak / 20e-3)
    assert read("kernels.moe_gmm_ms_per_decode_step") == \
        pytest.approx(12.0)
    assert read("kernels.moe_gmm_hbm_roofline_share") == \
        pytest.approx(100 * 440 * 11_010_048 * 2 / peak / 12e-3)
    assert read("kernels.paged_attention_ms_per_decode_step") == \
        pytest.approx(3.0)
    assert read("kernels.paged_attention_roofline_share") == \
        pytest.approx(100 * 8192 * 700 * 256 / peak / 3e-3)
    assert read("kernels.paged_attention_live_page_share") == \
        pytest.approx(100 * 700 / 2048)
    assert read("kernels.shortconv_mixer_ms_per_decode_step") == \
        pytest.approx(2.0)
    assert read("kernels.shortconv_mixer_hbm_roofline_share") == \
        pytest.approx(100 * flops_shortconv.mixer_step_bytes(c, 256) / peak
                      / 2e-3)
    assert read("moe.experts_hit_share") == pytest.approx(
        100 * 440 / 448)
    assert read("moe.expert_load_imbalance") == pytest.approx(
        1500 / (32500 / 32))
    assert read("engine.prefill_ride_token_share") == \
        pytest.approx(80.0)
    # no share of a roofline over 100 on a run at these sizes
    for name in NEW_METRICS:
        if "roofline" in name:
            assert 0 < read(name) <= 100, name


def test_shortconv_readers_say_nothing_where_there_is_nothing_to_read():
    """The parent of PR 55 serves no ``C`` model: its stats have no
    ``shortconv`` group and its trace none of the scopes. Every new reader
    returns None and does not raise."""
    for run in (_run(shortconv=False), _run(scopes={}),
                _run(decode=(0, 0.0))):
        if "shortconv" in run["stats"]["after"] and run["trace"][
                "programs"]["decode"][0]:
            names = [n for n in NEW_METRICS if n.startswith("kernels.")
                     and "live_page" not in n]
        elif "shortconv" not in run["stats"]["after"]:
            names = [n for n in NEW_METRICS if "ride" not in n]
        else:
            names = [n for n in NEW_METRICS if "device" in
                     next(m["source"] for m in MANIFEST["per_layer"]
                          if m["name"] == n)]
        for name in names:
            assert layer_metrics.load(name).read(run) is None, name
    bare = _run()
    bare["trace"], bare["trace_stats"] = {}, {}
    for name in NEW_METRICS:
        if "device" in next(m["source"] for m in MANIFEST["per_layer"]
                            if m["name"] == name):
            assert layer_metrics.load(name).read(bare) is None, name


# -- the runner, rehearsed on the CPU at a tiny size ----------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    from distributed_llm_training_and_inference_system_tpu.utils import platform
    held = platform.enable_compile_cache
    platform.enable_compile_cache = lambda: None
    path = tmp_path_factory.mktemp("shortconv") / "mix.json"
    path.write_text(json.dumps(TINY_TRAFFIC))
    try:
        run = runner.run(
            {"name": "tiny.mix", "chips": 1}, TINY, str(path), 3000000019,
            4.0, False, time.monotonic(), require_tpu=False)
        return dict(run, runner="shortconv")    # as run.py stamps it
    finally:
        platform.enable_compile_cache = held


def test_shortconv_runner_rehearsal(rehearsal):
    run = rehearsal
    assert run["kind"] == "serve" and run["stamps"]["kind"] == "serve-closed"
    assert run["check"]["ok"] and run["compiled_in_window"] == 0
    assert run["check"]["requests"] >= 1 and run["check"]["tokens"] >= 2
    assert run["check"]["tol"] == pytest.approx(
        runner.CHECK_TOLERANCE_STD * run["check"]["logit_std"])
    # the route every attention program took is in the run's record (the
    # gather baseline here: a CPU; on the chip that is not correct)
    assert run["check"]["attention_impls"]
    assert all(i.endswith("=gather") for i in run["check"]["attention_impls"])
    assert harness.Trace is not runner.hybrid.Trace             # put back
    assert harness.model_dict is not runner.model_dict
    assert runner.parallel.SCOPES != runner.SCOPES
    line = result_line(run, load_cell(CELL, MANIFEST)["end_to_end"],
                       end_to_end.load, traced=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s",
                                    "serve_tokens_per_s"}
    assert line["device"]["platform"] == "cpu"      # and so never a result
    traced = result_line(run, load_cell(CELL, MANIFEST)["per_layer"],
                         layer_metrics.load, traced=True)
    assert {"engine.decode_slot_utilization",
            "moe.experts_hit_share",
            "moe.expert_load_imbalance",
            "kernels.paged_attention_live_page_share",
            "engine.prefill_ride_token_share"} <= set(
                traced["metrics"])
    state = run["stats"]["after"]["shortconv"]
    assert state["slot_steps"] > 0
    assert state["state_bytes"] == 4 * 2 * 4 * 256 * 4
    # 8 callers over 4 slots: prompts rode the residents' decode steps
    assert run["stats"]["after"]["prefill_ride_tokens"] > 0


def test_a_gather_route_on_the_chip_is_not_correct(rehearsal, monkeypatch):
    """The same sample, the same tokens: held to the page-streaming route,
    a run whose attention programs report ``gather`` is not correct."""
    served = runner.Served.__new__(runner.Served)
    served.params = None
    monkeypatch.setattr(runner.shortconv_decoder, "logits", lambda *a, **k: (
        np.eye(4, 8, dtype=np.float32)[:len(k["positions"])],
        np.ones(len(k["positions"]))))
    sample = [(0, [1, 2, 3], [0, 1, 2])]
    served.config, served.require_streaming = TINY, False
    assert served.check_served(sample)["ok"]
    served.require_streaming = True
    out = served.check_served(sample)
    assert not out["ok"] and out["tokens_under_tol"] == 0
    monkeypatch.setattr(runner, "attention_impls", lambda: [
        ("paged_attention", "pallas"), ("paged_attention_multi", "pallas")])
    assert served.check_served(sample)["ok"]


def test_a_program_without_the_lfm2_keys_is_refused(monkeypatch):
    """The parent commit reads none of the ``lfm2_moe`` keys and would
    build a uniform attention-then-experts stack without a word: the
    runner leaves with a reason before it touches a device."""
    from distributed_llm_training_and_inference_system_tpu.config import schema
    real = schema.ModelConfig.from_dict
    dropped = ("model_type", "layer_types", "conv_L_cache",
               "num_dense_layers", "use_expert_bias")
    monkeypatch.setattr(
        schema.ModelConfig, "from_dict", classmethod(lambda cls, d: real(
            {k: v for k, v in d.items() if k not in dropped})))
    with pytest.raises(SystemExit, match="cannot run this cell"):
        runner.run({"name": "x", "chips": 1}, _config(), "unused", 0, 1.0,
                   False, time.monotonic())


@pytest.mark.parametrize("wrong", ["swap_bc", "taps_reversed",
                                   "rope_on_conv", "drop_conv", "no_rope"])
def test_the_check_fails_for_a_wrong_model(monkeypatch, wrong):
    """The runner's check, in its own form, on tokens a float32 server
    would serve (the reference's own argmax, teacher-forced): the right
    model passes with every gap 0, a wrong one does not."""
    import jax
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    cfg = ModelConfig.from_dict(runner.model_dict(TINY))
    params = runner.seeded_shortconv_params(
        gpt.init(cfg, jax.random.PRNGKey(3)), 3)
    # (at a width of 256 under the plain 0.02 the six layers vanish beside
    # the embedding, and under a tied head every model repeats its last
    # token: the projections four times as large, as 2,048 columns make
    # them at the published width)
    params = dict(params, blocks=jax.tree_util.tree_map_with_path(
        lambda path, x: x * 4.0 if path[-1].key == "kernel"
        and path[-2].key != "conv" else x, params["blocks"]))
    rng = np.random.default_rng(5)
    sample = []
    for slot in range(3):
        prompt = rng.integers(258, 512, 30).tolist()
        served = []
        for _ in range(8):
            lg = shortconv_decoder.logits(params, prompt + served, TINY)
            served.append(int(np.argmax(np.asarray(lg)[-1])))
        sample.append((slot, prompt, served))
    served_ = runner.Served.__new__(runner.Served)
    served_.params, served_.config = params, TINY
    served_.require_streaming = False
    monkeypatch.setattr(runner, "CHECK_ROUND_TO", 64)
    monkeypatch.setattr(runner, "ROUTER_TIE_MARGIN", 0.0)
    right = served_.check_served(sample)
    assert right["ok"] and right["worst_gap"] == 0.0
    assert right["tokens"] == 24 and right["slots"] == 3
    assert not served_.check_served(sample, wrong=wrong)["ok"]
