"""The parallel cell (falcon-h1-34b-4l.chat-batch-128): its configuration
file against the catalog row, its traffic against the issue, the plain
reference's independence, ``flops_parallel.py`` against hand-reckoned
numbers, each new metric's reader on a recorded run dict, and the runner's
rehearsal at a tiny size on the CPU. Nothing here pins a position in a list
of ``BENCHMARK.json`` or a count of its entries."""

import ast
import json
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import (end_to_end, flops_parallel, harness, layer_metrics,
                       parallel_counters)
from benchmark.reference import parallel_decoder
from benchmark.run import load_cell, result_line
from benchmark.runners import parallel as parallel_runner
from manifest_pins import assert_lists, entry, listed_by

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = "falcon-h1-34b-4l"
CELL = CONFIG + ".chat-batch-128"
NEW_METRICS = {
    "serve_programs.decode_hbm_roofline_share": "serve_tokens_per_s",
    "kernels.ssm_decode_ms_per_decode_step": "serve_tokens_per_s",
    "kernels.ssm_decode_hbm_roofline_share": "serve_tokens_per_s",
    "kernels.ssm_prefill_roofline_share": "tpot_p95_ms",
    "kernels.paged_attention_ms_per_decode_step": "tpot_p95_ms",
    "kernels.paged_attention_roofline_share": "tpot_p95_ms",
    "ssm.state_share_of_decode_bytes": "serve_tokens_per_s",
    "serve_programs.parallel_mixers_share_of_decode_step":
        "serve_tokens_per_s",
    "serve_programs.parallel_head_ms_per_decode_step": "tpot_p95_ms",
    "engine.prefill_ride_token_share": "serve_tokens_per_s",
}
APPENDED_TO = ["engine.decode_slot_utilization",
               "serve_programs.decode_step_device_ms", "device_idle.serve",
               "engine.host_ms_per_decode_step",
               "engine.prefill_stall_ms_per_decode_step",
               "engine.device_starved_share", "startup.import_s",
               "startup.program_lowering_s", "startup.program_compile_s",
               "startup.programs", "startup.cache_misses",
               "startup.engine_work_s", "startup.unattributed_s"]
# the model-configs catalog row Falcon-H1-34B-Instruct, "config"
CATALOG = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120}

TINY = {"name": "tiny-parallel", "model_type": "falcon_h1",
        "num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 10, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512, "max_position_embeddings": 512,
        "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_ssm": 64,
        "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
        "mamba_chunk_size": 16, "mamba_expand": 2, "rms_norm_eps": 1e-5,
        "rope_theta": 100000000000, "tie_word_embeddings": False,
        "embedding_multiplier": 2.5, "lm_head_multiplier": 0.6,
        "attention_in_multiplier": 1.3, "attention_out_multiplier": 0.8,
        "key_multiplier": 1.7, "ssm_in_multiplier": 0.7,
        "ssm_out_multiplier": 1.4,
        "ssm_multipliers": [0.9, 1.2, 0.75, 1.5, 1.1],
        "mlp_multipliers": [0.65, 1.6],
        "serve": {"dtype": "float32", "max_batch_size": 4,
                  "max_seq_len": 256, "kv_hbm_budget_gb": 0.01,
                  "prefill_chunk": 64}}
TINY_TRAFFIC = {
    "kind": "parallel-closed", "clients": 8, "pool_per_client": 100,
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
    entry, = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/"
        "config.json")
    differ = {k for k, v in CATALOG.items() if config[k] != v}
    assert differ == {"num_hidden_layers"} == set(config["reduced"])
    cut = config["reduced"]["num_hidden_layers"]
    assert (cut["published"], cut["here"]) == (72, 4) \
        == (CATALOG["num_hidden_layers"], config["num_hidden_layers"])
    assert "pipeline" in config["deployment"]
    assert "both the embedding and the head" in config["deployment"]
    assert config["serve"] == {"dtype": "bfloat16", "max_batch_size": 128,
                               "max_seq_len": 4096, "kv_hbm_budget_gb": 1.25}
    said = " ".join(config["assumed"])
    for word in ("float32", "bfloat16", "softplus", "mamba_expand",
                 "attention_in_multiplier", "std"):
        assert word in said, word


def test_the_program_builds_the_published_model_from_the_file():
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    config = _config()
    parallel_runner.require_parallel_support(config)
    c = ModelConfig.from_dict(parallel_runner.model_dict(config))
    assert c.layer_pattern == "PDPDPDPD" and c.vocab_size == 261120
    assert c.param_count == flops_parallel.total_params(config)
    assert c.kv_bytes_per_token() == flops_parallel.kv_bytes_per_token(
        config)
    assert c.mup.mlp == tuple(config["mlp_multipliers"])
    # the harness's own reduction drops the lists: the runner's keeps them
    with pytest.raises(SystemExit, match="cannot run this cell"):
        held = parallel_runner.model_dict
        parallel_runner.model_dict = harness.model_dict
        try:
            parallel_runner.require_parallel_support(config)
        finally:
            parallel_runner.model_dict = held


def test_the_traffic_is_the_issues():
    spec = load_cell(CELL, MANIFEST)
    assert (spec["cell"]["chips"], spec["cell"]["traffic"]) == (
        1, "chat-batch-128")
    assert len(spec["cell"]["why"]) <= 200
    t = json.loads(Path(spec["traffic_path"]).read_text())
    assert t["kind"].split("-")[0] == "parallel"
    assert (t["clients"], t["pool_per_client"], t["shape_seed"]) == (
        256, 2, 0)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 384,
                                  "sigma": 0.8, "min": 64, "max": 2048}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 384,
                                  "sigma": 0.6, "min": 64, "max": 1024}
    assert t["sampling"] == {"temperature": 0.0}
    assert (t["shared_prefix_tokens"], t["warmup_s"], t["drain_s"]) == (
        0, 10.0, 20.0)
    assert t["clients"] == 2 * spec["config"]["serve"]["max_batch_size"]


# this cell's alone; the others are entries other cells list too
OWN = ("serve_programs.parallel_mixers_share_of_decode_step",
       "serve_programs.parallel_head_ms_per_decode_step")


def test_the_cell_reports_the_metrics_the_issue_names():
    """By name and by membership (PR 59): the un-prefixed entries list the
    cell beside others, and move what PR 59's rule gives a shared entry."""
    spec = load_cell(CELL, MANIFEST)
    assert {m["name"] for m in spec["end_to_end"]} == {
        "tpot_p95_ms", "serve_tokens_per_s", "setup_s"}
    assert set(NEW_METRICS) | set(APPENDED_TO) <= listed_by(CELL)
    for name in APPENDED_TO:
        assert_lists(name, CELL)
    for name, moves in NEW_METRICS.items():
        m = assert_lists(name, CELL)
        if name in OWN:
            assert m["workloads"] == [CELL] and m["moves"] == moves, name
        assert ("roofline" in name) <= (m["unit"] == "%"), name
    # no layer of its own: every one is a layer some other cell's metric names
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if set(m["workloads"]) - {CELL}}
    assert {entry(n)["layer"] for n in NEW_METRICS} <= layers


def test_the_reference_imports_nothing_of_the_program():
    """``jax`` and the standard library alone: no import from the package
    under test, nor from the benchmark's other references."""
    source = (ROOT / "benchmark/reference/parallel_decoder.py").read_text()
    imported = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"__future__", "functools", "jax"}
    assert harness.PKG not in source and "benchmark." not in source.replace(
        "benchmark/", "")


# -- flops_parallel.py by hand ---------------------------------------------------

def test_parallel_parameters_by_hand_at_the_published_sizes():
    c = _config()
    assert flops_parallel.conv_channels(c) == 4096 + 2 * 2 * 256 == 5120
    assert flops_parallel.attention_params(c) == 31_457_280
    assert flops_parallel.mamba_params(c) == (
        5120 * 9248 + 5 * 5120 + 3 * 32 + 4096 + 4096 * 5120) == 68_351_072
    assert flops_parallel.mlp_params(c) == 330_301_440
    assert flops_parallel.layer_params(c) == 430_120_032
    assert flops_parallel.total_params(c) == (
        4 * 430_120_032 + 2 * 261_120 * 5120 + 5120) == 4_394_354_048
    # 8.79 GB of bfloat16 weights; the whole model 33.6 B parameters
    assert 2 * flops_parallel.total_params(c) == pytest.approx(8.79e9,
                                                               rel=1e-3)
    assert flops_parallel.total_params(
        dict(c, num_hidden_layers=72)) == pytest.approx(33.64e9, rel=1e-3)


def test_parallel_bytes_and_operations_by_hand():
    c = _config()
    assert flops_parallel.state_bytes_per_slot(c) == 4_194_304 + 30_720
    assert flops_parallel.kv_bytes_per_token(c) == 8192
    assert flops_parallel.head_weight_bytes(c) == 2_673_868_800
    assert flops_parallel.once_a_step_weight_bytes(c) == (
        2 * 4 * 430_120_032 + 2_673_868_800) == 6_114_829_056
    assert flops_parallel.state_step_bytes(c, 128) == (
        2 * 4 * 128 * 4_225_024) == 4_326_424_576
    # the issue's 11.1 GB a step at 128 live slots of ~700 live tokens
    step = flops_parallel.decode_step_bytes(c, 128 * 700, 128)
    assert step == 6_114_829_056 + 4_326_424_576 + 8192 * 89_600
    assert step == pytest.approx(11.1e9, rel=0.01)
    assert step / 819e9 == pytest.approx(13.6e-3, rel=0.01)
    assert flops_parallel.scan_flops_per_token(c) == (
        2 * 128 * (512 + 4096) + 4 * 32 * 128 * 256) == 5_373_952
    # activations alone: the state moves outside the scan's scope
    assert flops_parallel.scan_bytes_per_token(c) == (
        2 * (2 * 4096 + 2 * 512) + 128) == 18_560


# -- the readers on a recorded run -----------------------------------------------

def _run(ssm, scopes, decode=(10, 1.6), kv=True):
    def stats(steps, padded, ssm_, pages, ride):
        return {"decode_steps": steps, "prefill_padded_tokens": padded,
                "prefill_tokens": padded * 3 // 4,
                "prefill_ride_tokens": ride,
                **({"ssm": ssm_} if ssm_ else {}),
                **({"kv": {"live_pages": pages, "page_size": 64,
                           "kind": "kv"}} if kv else {})}
    before = stats(0, 0, {k: 0 for k in ssm} if ssm else None, 0, 0)
    after = stats(80, 8192, ssm, 10 * 1400, 6000)
    return {"config": _config(), "device": {"kind": "TPU v5 lite"},
            "runner": "parallel",
            "serve_cfg": {"decode_steps_per_dispatch": 8,
                          "max_batch_size": 128},
            "stats": {"before": before, "after": after},
            "trace_stats": {"before": before, "after": after},
            "trace": {"programs": {"decode": decode}, "scope_s": scopes,
                      "decode_scope_s": scopes, "device_ops": [],
                      "t0": 0.0, "t1": 1.0},
            "stamps": {"records": []}}


def test_parallel_readers_on_a_hand_made_run():
    ssm = {"slot_steps": 80 * 120, "state_bytes": 1}
    scopes = {"ssm_decode": (320, 0.56), "paged_attention": (320, 0.16),
              "parallel_attention": (900, 0.24),
              "parallel_ssm": (2000, 0.80), "lm_head": (80, 0.28),
              "sampler": (160, 0.04), "ssm_scan_prefill": (40, 0.002)}
    run = _run(ssm, scopes)
    read = lambda name: layer_metrics.load(name).read(run)
    assert parallel_counters.traced_decode_steps(run) == 80
    assert parallel_counters.live_slots_per_step(run) == 120.0
    # 10 dispatches counted 1,400 pages of 64 rows each
    assert parallel_counters.live_kv_tokens(run) == 1400 * 64
    state = 2 * 4 * 120 * 4_225_024
    floor = 6_114_829_056 + state + 8192 * 89_600
    assert parallel_counters.decode_step_bytes(run) == floor
    assert read("serve_programs.decode_hbm_roofline_share") == \
        pytest.approx(100 * (floor / 819e9) / 20e-3)
    assert read("kernels.ssm_decode_ms_per_decode_step") == \
        pytest.approx(7.0)
    assert read("kernels.ssm_decode_hbm_roofline_share") == \
        pytest.approx(100 * (state / 819e9) / 7e-3)
    per_row = max(5_373_952 / 197e12, 18_560 / 819e9)
    assert read("kernels.ssm_prefill_roofline_share") == \
        pytest.approx(100 * 8192 * 4 * per_row / 0.002)
    assert read("kernels.paged_attention_ms_per_decode_step") == \
        pytest.approx(2.0)
    assert read("kernels.paged_attention_roofline_share") == \
        pytest.approx(100 * (8192 * 89_600 / 819e9) / 2e-3)
    assert read("ssm.state_share_of_decode_bytes") == \
        pytest.approx(100 * state / floor)
    assert read("serve_programs.parallel_mixers_share_of_decode_step") == \
        pytest.approx(100 * 1.04 / 1.6)
    assert read("serve_programs.parallel_head_ms_per_decode_step") == \
        pytest.approx(4.0)
    assert read("engine.prefill_ride_token_share") == \
        pytest.approx(100 * 6000 / 6144)
    for name in NEW_METRICS:
        if "roofline" in name:
            assert 0 < read(name) < 100, name


def test_parallel_readers_say_nothing_where_there_is_nothing_to_read():
    """A program from before the counters and the scopes (the parent
    commit, traced with this benchmark) leaves the metrics out; so does a
    trace in which no operation carries a scope's name."""
    silent = [n for n in NEW_METRICS if not n.startswith("engine.")]
    for run in (_run({}, {}, kv=False),
                dict(_run({}, {}), trace={}, trace_stats=None)):
        for name in silent:
            assert layer_metrics.load(name).read(run) is None, name
    # counters and no scope: what reads a scope's seconds is left out
    unscoped = _run({"slot_steps": 10}, None)
    for name in silent:
        if "kernels." in name or "mixers" in name or "head" in name:
            assert layer_metrics.load(name).read(unscoped) is None, name
    old = _run({}, {})
    for half in old["stats"].values():
        del half["prefill_ride_tokens"]
    assert layer_metrics.load(
        "engine.prefill_ride_token_share").read(old) is None


def test_an_operation_counts_under_every_scope_it_lies_in():
    assert parallel_runner.scopes_of(["paged_attention_mq.3"]) == {
        "paged_attention_mq"}
    assert parallel_runner.scopes_of(["paged_attention.32"]) == {
        "paged_attention"}
    op_s = {"decode": {"fusion.7": (8, 0.4), "paged_attention.32": (8, 0.1),
                       "fusion.9": (8, 0.2), "fusion.1": (8, 0.3)},
            "prefill": {"fusion.7": (1, 0.05)}}
    texts = {"_decode_impl_n": "\n".join([
        '  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(f)/while/body/parallel_ssm/ssm_decode/mul"}',
        '  %paged_attention.32 = bf16[8]{0} custom-call(%q), metadata={'
        'op_name="jit(f)/while/body/parallel_attention/paged_attention/'
        'pallas_call"}',
        '  ROOT %fusion.9 = f32[8]{0} fusion(%p), kind=kOutput, metadata={'
        'op_name="jit(f)/while/body/lm_head/dot_general"}',
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(f)/while/body/add"}']),
        "prefill 256": '  %fusion.7 = f32[8]{0} fusion(%p), metadata={'
                       'op_name="jit(g)/parallel_ssm/ssm_scan_prefill/dot"}'}
    got = parallel_runner.scope_seconds(op_s, texts)
    assert got["decode"] == {
        "parallel_ssm": (8, 0.4), "ssm_decode": (8, 0.4),
        "parallel_attention": (8, 0.1), "paged_attention": (8, 0.1),
        "lm_head": (8, 0.2)}
    assert got["prefill"] == {"parallel_ssm": (1, 0.05),
                              "ssm_scan_prefill": (1, 0.05)}


# -- the runner's rehearsal ------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    from distributed_llm_training_and_inference_system_tpu.utils import platform
    held = platform.enable_compile_cache
    platform.enable_compile_cache = lambda: None
    path = tmp_path_factory.mktemp("parallel") / "mix.json"
    path.write_text(json.dumps(TINY_TRAFFIC))
    try:
        run = parallel_runner.run(
            {"name": "tiny.mix", "chips": 1}, TINY, str(path), 3000000019,
            4.0, False, time.monotonic(), require_tpu=False)
        return dict(run, runner="parallel")     # as run.py stamps it
    finally:
        platform.enable_compile_cache = held


def test_parallel_runner_rehearsal(rehearsal):
    run = rehearsal
    assert run["kind"] == "serve" and run["stamps"]["kind"] == "serve-closed"
    assert run["check"]["ok"] and run["compiled_in_window"] == 0
    assert run["check"]["requests"] >= 1 and run["check"]["tokens"] >= 2
    assert run["check"]["tol"] == pytest.approx(
        parallel_runner.CHECK_TOLERANCE_STD * run["check"]["logit_std"])
    assert harness.Trace is not parallel_runner.hybrid.Trace    # put back
    assert harness.model_dict is not parallel_runner.model_dict
    line = result_line(run, load_cell(CELL, MANIFEST)["end_to_end"],
                       end_to_end.load, traced=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s",
                                    "serve_tokens_per_s"}
    assert line["device"]["platform"] == "cpu"      # and so never a result
    traced = result_line(run, load_cell(CELL, MANIFEST)["per_layer"],
                         layer_metrics.load, traced=True)
    assert {"engine.decode_slot_utilization",
            "engine.prefill_ride_token_share"} <= set(
                traced["metrics"])
    assert not {n for n in NEW_METRICS if n.startswith(
        ("kernels.", "serve_programs."))} & set(traced["metrics"])
    ssm = run["stats"]["after"]["ssm"]
    assert ssm["slot_steps"] > 0 and ssm["state_bytes"] > 0
    assert ssm["refused"]["prefix_caching"] > 0
    # 8 callers over 4 slots: prompts rode the residents' decode steps
    assert run["stats"]["after"]["prefill_ride_tokens"] > 0


def test_a_program_without_the_falcon_h1_keys_is_refused(monkeypatch):
    """The parent commit reads none of the ``falcon_h1`` keys and would
    build a uniform attention-then-MLP stack without a word: the runner
    leaves with a reason before it touches a device."""
    from distributed_llm_training_and_inference_system_tpu.config import schema
    real = schema.ModelConfig.from_dict
    dropped = ("model_type", "ssm_multipliers", "mlp_multipliers")
    monkeypatch.setattr(
        schema.ModelConfig, "from_dict", classmethod(lambda cls, d: real(
            {k: v for k, v in d.items()
             if k not in dropped and "mamba" not in k
             and "multiplier" not in k})))
    with pytest.raises(SystemExit, match="cannot run this cell"):
        parallel_runner.run({"name": "x", "chips": 1}, _config(), "unused",
                            0, 1.0, False, time.monotonic())


@pytest.mark.parametrize("wrong", ["drop_attention", "drop_ssm",
                                   "one:embedding", "one:mlp0",
                                   "norm_before_gate", "drop_D", "no_rope"])
def test_the_check_fails_for_a_wrong_model(monkeypatch, wrong):
    """The runner's check, in its own form, on tokens a float32 server
    would serve (the reference's own argmax, teacher-forced): the right
    model passes with every gap 0, a wrong one does not."""
    import jax
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    cfg = ModelConfig.from_dict(parallel_runner.model_dict(TINY))
    params = parallel_runner.seeded_parallel_params(
        gpt.init(cfg, jax.random.PRNGKey(3)), 3)
    rng = np.random.default_rng(5)
    sample = []
    for slot in range(3):
        prompt = rng.integers(258, 512, 30).tolist()
        served = []
        for _ in range(8):
            lg = parallel_decoder.logits(params, prompt + served, TINY)
            served.append(int(np.argmax(np.asarray(lg)[-1])))
        sample.append((slot, prompt, served))
    served_ = parallel_runner.Served.__new__(parallel_runner.Served)
    served_.params, served_.config = params, TINY
    served_.state_dtype = "float32"
    monkeypatch.setattr(parallel_runner, "CHECK_ROUND_TO", 64)
    right = served_.check_served(sample)
    assert right["ok"] and right["worst_gap"] == 0.0
    assert right["tokens"] == 24 and right["slots"] == 3
    assert not served_.check_served(sample, wrong=wrong)["ok"]
    # the state pool's dtype is held by name
    served_.state_dtype = "bfloat16"
    assert not served_.check_served(sample)["ok"]
