"""The looped cell (ouro-2.6b.loop-batch-16): the manifest's new entries as
ISSUE 60 names them, its configuration file against the catalog row (nothing
reduced), its traffic against the issue, the plain reference's independence,
``flops_looped.py`` against the issue's hand arithmetic, each new metric's
reader on a fabricated run, and the runner's rehearsal at a tiny size on the
CPU. Nothing here pins a position in a list of ``BENCHMARK.json`` or a count
of its entries."""

import ast
import json
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import (end_to_end, facts, families, flops, flops_looped,
                       harness, layer_metrics, looped_counters)
from benchmark.reference import looped_decoder
from benchmark.run import load_cell, result_line
from benchmark.runners import looped as runner
from fabricated_runs import DISPATCHES, STEPS, fabricated
from manifest_pins import assert_lists, listed_by

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = "ouro-2.6b"
CELL = CONFIG + ".loop-batch-16"
LAYER = ("looped stack (models/gpt.py forward, serve/decode.py "
         "extend_step_forward, the pool's planes in serve/kv_cache.py)")
NEW_METRICS = {
    "loop.passes_per_decode_token": ("passes/token", "serve_tokens_per_s"),
    "loop.pass_ms_per_decode_step": ("ms", "tpot_p95_ms"),
    "loop.exit_gate_ms_per_decode_step": ("ms", "tpot_p95_ms"),
    "kv.loop_share_of_decode_bytes": ("%", "serve_tokens_per_s"),
    "kv.pool_live_page_share": ("%", "serve_tokens_per_s"),
}
APPENDED_TO = [
    "serve_programs.decode_step_device_ms",
    "serve_programs.decode_hbm_roofline_share", "device_idle.serve",
    "engine.decode_slot_utilization", "engine.host_ms_per_decode_step",
    "engine.prefill_stall_ms_per_decode_step", "engine.device_starved_share",
    "engine.starved_ms_per_decode_step.deliver",
    "engine.starved_ms_per_decode_step.dispatch",
    "engine.slot_steps.useful_share", "engine.slot_steps.overrun_share",
    "engine.slot_steps.prompt_wait_share", "engine.slot_steps.empty_share",
    "engine.wall_ms_per_decode_step", "engine.ledger_tokens_per_s",
    "engine.seat_to_first_token_mean_ms", "engine.prefill_ride_token_share",
    "kernels.paged_attention_ms_per_decode_step",
    "kernels.paged_attention_roofline_share",
    "kernels.paged_attention_live_page_share", "startup.import_s",
    "startup.program_lowering_s", "startup.program_compile_s",
    "startup.programs", "startup.cache_misses", "startup.engine_work_s",
    "startup.unattributed_s"]
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")

TINY = {"name": "tiny-looped", "model_type": "ouro", "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 128, "intermediate_size": 256,
        "layer_types": ["full_attention"] * 3,
        "max_position_embeddings": 512, "num_attention_heads": 2,
        "num_hidden_layers": 3, "num_key_value_heads": 2,
        "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "total_ut_steps": 2,
        "early_exit_threshold": 1, "vocab_size": 512,
        "serve": {"dtype": "float32", "max_batch_size": 4,
                  "max_seq_len": 256, "kv_block_size": 16,
                  "kv_hbm_budget_gb": 0.01, "prefill_chunk": 64}}
TINY_TRAFFIC = {
    "kind": "looped-closed", "clients": 8, "pool_per_client": 100,
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 8, "max": 120},
    "output_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                      "min": 2, "max": 24},
    "sampling": {"temperature": 0.0, "ignore_eos": True}, "warmup_s": 0.5,
    "drain_s": 10.0, "shape_seed": 0}


def _config():
    return load_cell(CELL, MANIFEST)["config"]


# -- the configuration, the traffic, the manifest -------------------------------

def test_nothing_differs_from_the_catalog_row():
    if not CATALOG.exists():
        pytest.skip("the catalog of architectures is not on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Ouro-2.6B")
    config = _config()
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] and config["reduced"] == {}
    assert entry["source"] == config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert config[key] == value, key
    # (14 slots and 28 callers: the issue's stated fallback, taken because
    # the pool preempted at 16 and 32 on the chip; PERF.md 6, PR 60)
    assert config["serve"] == {"dtype": "bfloat16", "max_batch_size": 14,
                               "max_seq_len": 1024, "kv_hbm_budget_gb": 8.0}
    assert len(config["assumed"]) >= 7 and "one TPU v5e chip" in config[
        "deployment"]


def test_the_traffic_is_the_issues():
    spec = load_cell(CELL, MANIFEST)
    assert spec["cell"]["chips"] == 1
    t = json.loads(Path(spec["traffic_path"]).read_text())
    assert t["kind"] == "looped-closed"
    assert (t["clients"], t["pool_per_client"]) == (28, 8)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 128,
                                  "sigma": 0.6, "min": 32, "max": 512}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 128,
                                  "sigma": 0.6, "min": 16, "max": 384}
    assert t["sampling"] == {"temperature": 0.0, "ignore_eos": True}
    assert (t["shared_prefix_tokens"], t["shape_seed"], t["warmup_s"],
            t["drain_s"]) == (0, 0, 10.0, 20.0)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_entry_lists_the_cell_alone(name):
    unit, moves = NEW_METRICS[name]
    e = assert_lists(name, CELL, unit=unit, moves=moves, layer=LAYER)
    assert e["workloads"] == [CELL]


@pytest.mark.parametrize("name", APPENDED_TO)
def test_the_cell_is_appended_to_an_accepted_entry(name):
    assert_lists(name, CELL)


def test_the_cell_reports_what_the_issue_names():
    assert listed_by(CELL) == set(NEW_METRICS) | set(APPENDED_TO)
    assert {m["name"] for m in load_cell(CELL, MANIFEST)["end_to_end"]} == {
        "tpot_p95_ms", "serve_tokens_per_s", "setup_s"}


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse(Path(looped_decoder.__file__).read_text())
    imported = {n.module or "" for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom)} | {
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names}
    assert imported == {"__future__", "functools", "jax", "jax.numpy"}


# -- the bytes, by hand ------------------------------------------------------------

def test_flops_looped_is_the_issues_arithmetic():
    c = _config()
    assert flops_looped.layer_params(c) == 51_388_416
    assert flops_looped.total_params(c) == 2_667_974_657
    assert flops_looped.planes(c) == 192
    assert flops_looped.kv_bytes_per_token(c) == 1_572_864
    # 4 x (48 layers, norms and all, + the final norm and the gate) + the
    # head once, in bfloat16
    assert flops_looped.weight_bytes_a_step(c) == 2 * (
        4 * (48 * 51_388_416 + 4097) + 2048 * 49152) == 19_934_511_112


def test_decode_step_bytes_is_the_hand_sum_at_4160_live_tokens():
    """16 slots at ~260 tokens each: 4,160 live rows a plane."""
    c = _config()
    want = (19_934_511_112             # weights: layers x 4, head once
            + 16 * 2048 * 2            # the 16 embedding rows gathered
            + 1_572_864 * 4160         # every live K/V row of 192 planes
            + 1_572_864 * 16)          # the step's rows written
    assert flops_looped.decode_step_bytes(c, 4160, 16) == want
    assert want / 819e9 == pytest.approx(0.03236, rel=1e-3)     # 32 ms
    # 16 rows and a riding piece of 128: ~2.9 TFLOP a step
    assert flops_looped.decode_step_flops(c, 144, 4160) == pytest.approx(
        2.87e12, rel=0.02)


# -- the readers on a fabricated run ---------------------------------------------

def _fabricated(**loop):
    """``fabricated_runs.fabricated`` of this cell with a ``loop`` group and
    the two scopes in it (the shared module knows neither)."""
    cell = next(c for c in MANIFEST["workloads"] if c["name"] == CELL)
    run = fabricated(cell)
    assert run["runner"] == "looped"
    steps = DISPATCHES * STEPS
    for n, which in ((1, "before"), (2, "after")):
        run["stats"][which]["loop"] = {
            "passes": 4, "pool_planes": 192, "decode_tokens": n * 1_003,
            "decode_token_passes": n * 4_012, **loop}
        run["stats"][which]["kv"].update(num_pages=79,
                                         free_pages=14 - 2 * n)
    run["trace"]["decode_scope_s"] = dict(
        run["trace"]["decode_scope_s"], loop_pass=(steps * 4 * 50, 2.3041),
        exit_gate=(steps * 4 * 3, 0.0127))
    run["serve_cfg"]["max_batch_size"] = 16
    return run


def _live_rows(run):
    """The stamps' own count over the traced stretch [10, 15]: every one of
    the 64 fabricated requests streams across it (first chunk ~9 s, last
    ~16 s), so each holds its prompt and the tokens streamed by 12.5 s."""
    rows = facts.live_kv_tokens(run, 10.0, 15.0)
    assert rows == pytest.approx(sum(
        600 + 7 * i + (12.5 - (9.0 - 0.01 * i)) / (7.0 + 0.03 * i) * (30 + i)
        for i in range(64)))
    return rows


def test_the_new_readers_read_a_fabricated_run():
    run, steps = _fabricated(), DISPATCHES * STEPS
    read = {name: layer_metrics.load(name).read(run) for name in NEW_METRICS}
    assert read["loop.passes_per_decode_token"] == 4.0
    assert read["loop.pass_ms_per_decode_step"] == pytest.approx(
        1e3 * 2.3041 / steps / 4)
    assert read["loop.exit_gate_ms_per_decode_step"] == pytest.approx(
        1e3 * 0.0127 / steps)
    rows = _live_rows(run)
    kv = 1_572_864 * rows
    assert read["kv.loop_share_of_decode_bytes"] == pytest.approx(
        100 * kv / flops_looped.decode_step_bytes(run["config"], rows, 16))
    # 78 pages but the scratch one; 12 then 10 free
    assert read["kv.pool_live_page_share"] == pytest.approx(
        100 * ((78 - 12) / 78 + (78 - 10) / 78) / 2)


def test_a_program_without_a_loop_group_reads_nothing():
    cell = next(c for c in MANIFEST["workloads"] if c["name"] == CELL)
    for bare in (False, True):
        run = fabricated(cell, bare)
        for name in NEW_METRICS:
            assert layer_metrics.load(name).read(run) is None, name


def test_the_shared_readers_take_this_familys_bytes():
    run = _fabricated()
    family = families.of(run)
    assert family is families.load("looped")
    rows = _live_rows(run)
    assert family.live_kv_tokens(run) == rows
    assert family.kv_bytes_per_token(run) == 1_572_864
    assert family.decode_step_bytes(run) == flops_looped.decode_step_bytes(
        run["config"], rows, 16)
    step_ms = 1e3 * 1.6127 / (DISPATCHES * STEPS)
    assert family.decode_step_ms(run) == pytest.approx(step_ms)
    peak = flops.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert layer_metrics.load(
        "serve_programs.decode_hbm_roofline_share").read(run) == \
        pytest.approx(100 * family.decode_step_bytes(run) / peak
                      / (step_ms * 1e-3))
    kernel_ms = 1e3 * 0.1043 / (DISPATCHES * STEPS)
    assert family.paged_attention_ms_per_decode_step(run) == pytest.approx(
        kernel_ms)
    assert layer_metrics.load(
        "kernels.paged_attention_roofline_share").read(run) == pytest.approx(
            100 * 1_572_864 * rows / peak / (kernel_ms * 1e-3))


# -- the runner, rehearsed on the CPU at a tiny size ----------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    from distributed_llm_training_and_inference_system_tpu.utils import platform
    held = platform.enable_compile_cache
    platform.enable_compile_cache = lambda: None
    path = tmp_path_factory.mktemp("looped") / "mix.json"
    path.write_text(json.dumps(TINY_TRAFFIC))
    try:
        run = runner.run(
            {"name": "tiny.mix", "chips": 1}, TINY, str(path), 3000000019,
            4.0, False, time.monotonic(), require_tpu=False)
        return dict(run, runner="looped")       # as run.py stamps it
    finally:
        platform.enable_compile_cache = held


def test_looped_runner_rehearsal(rehearsal):
    run = rehearsal
    assert run["kind"] == "serve" and run["stamps"]["kind"] == "serve-closed"
    assert run["check"]["ok"] and run["compiled_in_window"] == 0
    assert run["check"]["requests"] >= 1 and run["check"]["tokens"] >= 2
    assert run["check"]["tol"] == pytest.approx(
        runner.CHECK_TOLERANCE_STD * run["check"]["logit_std"])
    assert run["check"]["pool_shape"][0] == 6       # 2 passes x 3 layers
    assert run["check"]["preemptions_in_window"] == 0
    # the route every attention program took is in the run's record (the
    # gather baseline here: a CPU; on the chip that is not correct)
    assert run["check"]["attention_impls"]
    assert all(i.endswith("=gather") for i in run["check"]["attention_impls"])
    assert harness.Trace is not runner.hybrid.Trace             # put back
    assert runner.parallel.SCOPES != runner.SCOPES
    line = result_line(run, load_cell(CELL, MANIFEST)["end_to_end"],
                       end_to_end.load, traced=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s",
                                    "serve_tokens_per_s"}
    assert line["device"]["platform"] == "cpu"      # and so never a result
    traced = result_line(run, load_cell(CELL, MANIFEST)["per_layer"],
                         layer_metrics.load, traced=True)
    assert {"loop.passes_per_decode_token", "engine.decode_slot_utilization",
            "kernels.paged_attention_live_page_share",
            "engine.prefill_ride_token_share"} <= set(traced["metrics"])
    assert traced["metrics"]["loop.passes_per_decode_token"]["value"] == 2.0
    loop = run["stats"]["after"]["loop"]
    assert (loop["passes"], loop["pool_planes"]) == (2, 6)
    # 8 callers over 4 slots: prompts rode the residents' decode steps
    assert run["stats"]["after"]["prefill_ride_tokens"] > 0


@pytest.fixture(scope="module")
def greedy_sample():
    """(params, sample): the tiny model on the runner's seeded weights and
    two prompts with the 6 tokens the REFERENCE decodes greedily behind
    each: what a right server serves."""
    import jax
    import jax.numpy as jnp
    from distributed_llm_training_and_inference_system_tpu.config import schema
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    cfg = schema.ModelConfig.from_dict(harness.model_dict(TINY))
    params = runner.seeded_looped_params(
        gpt.init(cfg, jax.random.PRNGKey(3), jnp.float32), 3)
    rng, sample = np.random.default_rng(3), []
    for slot, n in enumerate((21, 40)):
        prompt, served = rng.integers(258, 512, n).tolist(), []
        for _ in range(6):
            lg = looped_decoder.logits(params, prompt + served, TINY)
            served.append(int(np.argmax(np.asarray(lg)[-1])))
        sample.append((slot, prompt, served))
    return params, sample


@pytest.mark.parametrize("wrong", [None, *looped_decoder.WRONG])
def test_the_check_fails_each_wrong_reference(greedy_sample, wrong):
    """Tokens the right reference decodes pass its own check; each wrong
    reference explains them worse than the tolerance allows."""
    served = runner.Served.__new__(runner.Served)
    served.params, sample = greedy_sample
    served.config, served.require_streaming = TINY, False
    served.pool_shape = (6,)
    out = served.check_served(sample, wrong=wrong)
    assert out["tokens"] == 12
    if wrong is None:
        assert out["ok"] and out["worst_gap"] == 0.0
    else:
        # (at this size and in float32 most greedy tokens survive a wrong
        # reference: the chip's readings, PERF.md 6, are what sets the
        # limits; here each wrong reference must at least SHOW)
        assert out["mean_gap"] > 0.02 * out["logit_std"]
        assert out["tokens_off_the_reference_argmax"] >= 1


def test_a_gather_route_or_a_pool_of_layers_alone_is_not_correct(
        monkeypatch):
    served = runner.Served.__new__(runner.Served)
    served.params, served.config = None, TINY
    monkeypatch.setattr(runner.looped_decoder, "logits", lambda *a, **k: (
        np.eye(4, 8, dtype=np.float32)[:len(k["positions"])]))
    sample = [(0, [1, 2, 3], [0, 1, 2])]
    served.require_streaming, served.pool_shape = False, (6, 9, 1, 16, 128)
    assert served.check_served(sample)["ok"]
    served.pool_shape = (3, 9, 1, 16, 128)      # a plane a LAYER: one pass
    assert not served.check_served(sample)["ok"]
    served.require_streaming, served.pool_shape = True, (6, 9, 1, 16, 128)
    out = served.check_served(sample)
    assert not out["ok"] and out["tokens_under_tol"] == 0
    monkeypatch.setattr(runner.shortconv, "attention_impls", lambda: [
        ("paged_attention", "pallas"), ("paged_attention_multi", "pallas")])
    assert served.check_served(sample)["ok"]


def test_a_program_without_the_loop_is_refused(monkeypatch):
    """The parent commit reads neither ``total_ut_steps`` nor ``model_type:
    ouro`` and would serve a plain stack walked once without a word: the
    runner leaves with a reason before it touches a device."""
    from distributed_llm_training_and_inference_system_tpu.config import schema
    real = schema.ModelConfig.from_dict
    dropped = ("model_type", "total_ut_steps", "early_exit_threshold")
    monkeypatch.setattr(
        schema.ModelConfig, "from_dict", classmethod(
            lambda cls, d: real({k: v for k, v in d.items()
                                 if k not in dropped})))
    with pytest.raises(SystemExit) as e:
        runner.require_looped_support(_config())
    assert "(passes, sandwich norms, planes of the K/V pool) = (1, False, " \
        "48)" in str(e.value)
    monkeypatch.undo()
    runner.require_looped_support(_config())        # this program: silent
