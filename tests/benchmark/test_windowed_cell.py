"""The windowed cell (mellum2-12b-a2.5b-8l.code-context-48): the manifest's new
entries as ISSUE 63 names them, its configuration file against the catalog row
(depth and the two per-layer lists cut, nothing else), its traffic against the
issue, the plain reference's independence, ``flops_windowed.py`` against the
issue's hand arithmetic, each new metric's reader on a fabricated run, and the
runner's rehearsal at a tiny size on the CPU. Nothing here pins a position in
a list of ``BENCHMARK.json`` or a count of its entries."""

import ast
import json
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import (end_to_end, facts, families, flops, flops_windowed,
                       harness, layer_metrics, windowed_counters)
from benchmark.reference import windowed_decoder
from benchmark.run import load_cell, result_line
from benchmark.runners import windowed as runner
from fabricated_runs import DISPATCHES, STEPS, fabricated
from manifest_pins import assert_lists, listed_by

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = "mellum2-12b-a2.5b-8l"
CELL = CONFIG + ".code-context-48"
LAYER = ("window ring (serve/kv_cache.py SplitPages pools, "
         "ops/paged_attention_pallas.py window_attention)")
NEW_METRICS = {
    "kernels.window_attention_ms_per_decode_step": ("ms", "tpot_p95_ms"),
    "kernels.window_attention_hbm_roofline_share": (
        "%", "serve_tokens_per_s"),
    "kv.window_live_row_share": ("%", "serve_tokens_per_s"),
    "kv.window_share_of_decode_bytes": ("%", "serve_tokens_per_s"),
}
APPENDED_TO = [
    "serve_programs.decode_step_device_ms",
    "serve_programs.decode_hbm_roofline_share",
    "serve_programs.prefill_live_row_share", "device_idle.serve",
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
    "kernels.paged_attention_live_page_share",
    "kernels.moe_gmm_ms_per_decode_step",
    "kernels.moe_gmm_hbm_roofline_share", "moe.experts_hit_share",
    "moe.expert_load_imbalance", "startup.import_s",
    "startup.program_lowering_s", "startup.program_compile_s",
    "startup.programs", "startup.cache_misses", "startup.engine_work_s",
    "startup.unattributed_s"]
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")

TINY = {"name": "tiny-windowed", "model_type": "mellum", "head_dim": 32,
        "hidden_act": "silu", "hidden_size": 128, "intermediate_size": 128,
        "moe_intermediate_size": 64, "attention_bias": False,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "mlp_layer_types": ["sparse"] * 4, "max_position_embeddings": 512,
        "num_attention_heads": 4, "num_hidden_layers": 4,
        "num_key_value_heads": 2, "num_experts": 8,
        "num_experts_per_tok": 2, "norm_topk_prob": True,
        "rms_norm_eps": 1e-6, "sliding_window": 16,
        "use_sliding_window": True, "tie_word_embeddings": False,
        "vocab_size": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                "original_max_position_embeddings": 64, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.1386294361119891},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000}},
        "serve": {"dtype": "float32", "max_batch_size": 4,
                  "max_seq_len": 256, "kv_block_size": 8,
                  "kv_hbm_budget_gb": 0.01, "prefill_chunk": 16}}
TINY_TRAFFIC = {
    "kind": "windowed-closed", "clients": 8, "pool_per_client": 100,
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 20, "max": 100},
    "output_tokens": {"dist": "lognormal", "median": 56, "sigma": 0.1,
                      "min": 48, "max": 64},
    "sampling": {"temperature": 0.0, "ignore_eos": True}, "warmup_s": 1.0,
    "drain_s": 10.0, "shape_seed": 0}


def _config():
    return load_cell(CELL, MANIFEST)["config"]


# -- the configuration, the traffic, the manifest -------------------------------

def test_depth_and_its_two_lists_alone_differ_from_the_catalog_row():
    if not CATALOG.exists():
        pytest.skip("the catalog of architectures is not on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    config = _config()
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    cut = {"num_hidden_layers", "layer_types", "mlp_layer_types"}
    assert set(entry["reduced"]) == set(config["reduced"]) == cut
    assert entry["source"] == config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in cut:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 8
    assert config["layer_types"] == row["config"]["layer_types"][:8] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert config["mlp_layer_types"] == ["sparse"] * 8
    for key in cut:
        assert {"published", "here", "why"} <= set(config["reduced"][key])
    assert config["serve"] == {"dtype": "bfloat16", "max_batch_size": 48,
                               "max_seq_len": 16384, "kv_hbm_budget_gb": 3.0}
    assert len(config["assumed"]) >= 6 and "first stage" in config[
        "deployment"].lower()
    assert "7.59 GB" in config["deployment"]


def test_the_traffic_is_the_issues():
    spec = load_cell(CELL, MANIFEST)
    assert spec["cell"]["chips"] == 1
    t = json.loads(Path(spec["traffic_path"]).read_text())
    assert t["kind"] == "windowed-closed"
    assert (t["clients"], t["pool_per_client"]) == (96, 1)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 3072,
                                  "sigma": 0.6, "min": 1024, "max": 12288}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 2048,
                                  "sigma": 0.3, "min": 1024, "max": 3072}
    assert t["sampling"] == {"temperature": 0.0, "ignore_eos": True}
    assert (t["shared_prefix_tokens"], t["shape_seed"], t["warmup_s"],
            t["drain_s"]) == (0, 0, 20.0, 20.0)
    assert "ENDED inside" in t["pool_why"]
    # (the contract's limit on a cell's and a configuration's one line)
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert len(spec["cell"]["why"]) <= 200 and len(entry["why"]) <= 200
    # every context fits max_seq_len, and the pool's contexts the full pool
    from benchmark import traffic
    reqs = traffic.requests(dict(t, kind="serve-closed"), 1, 71.0, 98304)
    assert len(reqs) == 96
    contexts = [len(r["prompt"]) + r["max_tokens"] for r in reqs]
    assert max(contexts) <= 16384 and 5000 < np.mean(contexts) < 7000


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_entry_lists_the_cell_alone(name):
    unit, moves = NEW_METRICS[name]
    e = assert_lists(name, CELL, unit=unit, moves=moves, layer=LAYER)
    assert e["workloads"] == [CELL]


@pytest.mark.parametrize("name", APPENDED_TO)
def test_the_cell_is_appended_to_an_accepted_entry(name):
    assert_lists(name, CELL)


def test_the_cell_reports_what_the_issue_names():
    """(``kv.pool_live_page_share`` is not among them: its accepted reader
    wants a ``loop`` group and would find nothing to read here.)"""
    assert listed_by(CELL) == set(NEW_METRICS) | set(APPENDED_TO)
    assert {m["name"] for m in load_cell(CELL, MANIFEST)["end_to_end"]} == {
        "tpot_p95_ms", "serve_tokens_per_s", "setup_s"}


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse(Path(windowed_decoder.__file__).read_text())
    imported = {n.module or "" for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom)} | {
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names}
    assert imported == {"__future__", "functools", "math", "jax",
                        "jax.numpy"}


# -- the bytes, by hand ------------------------------------------------------------

def test_flops_windowed_is_the_issues_arithmetic():
    c = _config()
    assert flops_windowed.attention_params(c) == 21_233_664
    assert flops_windowed.expert_params(c) == 3 * 2304 * 896
    assert flops_windowed.layer_params(c) == 417_747_712
    assert flops_windowed.total_params(c) == 3_794_968_832       # 7.59 GB
    assert flops_windowed.kv_row_bytes(c) == 2048
    assert flops_windowed.kv_bytes_per_token(c, "full_attention") == 4096
    assert flops_windowed.kv_bytes_per_token(c, "sliding_attention") == 12288
    # attention, routers and norms of 8 layers, the final norm, the head
    assert flops_windowed.shared_weight_bytes(c) == 2 * (
        8 * (21_233_664 + 2304 * 64 + 2 * 2304 + 2 * 128) + 2304
        + 2304 * 98304)


def test_decode_step_bytes_is_the_hand_sum():
    """48 slots at ~4,650 tokens each: 223,200 live rows; every one of the
    8 x 64 experts hit."""
    c = _config()
    rows = 48 * 4650
    full, seen = 2 * rows, 6 * 48 * 1024
    want = (flops_windowed.shared_weight_bytes(c)
            + 512 * 3 * 2304 * 896 * 2          # 512 experts' kernels
            + 48 * 2304 * 2                     # the embedding rows gathered
            + 2048 * (full + seen)              # the rows read, by kind
            + 2048 * 48 * 8)                    # the step's rows written
    assert flops_windowed.decode_step_bytes(c, full, seen, 512, 48) == want
    # the issue's floor: ~7.1 GB of weights + ~1.5 GB of K/V, ~10.6 ms
    assert want / 819e9 == pytest.approx(0.0106, rel=0.03)
    assert flops_windowed.window_attention_bytes(c, seen) == 2048 * seen
    # as full layers the six would read 6 x 223,200 rows: 3.7 GB in all
    assert 2048 * 8 * rows == pytest.approx(3.66e9, rel=0.01)


# -- the readers on a fabricated run ---------------------------------------------

def _fabricated(bare=False):
    """``fabricated_runs.fabricated`` of this cell with a ``window`` group
    and the window kernel's scope in it (the shared module knows neither)."""
    cell = next(c for c in MANIFEST["workloads"] if c["name"] == CELL)
    run = fabricated(cell, bare)
    assert run["runner"] == "windowed"
    if bare:
        return run
    for n, which in ((1, "before"), (2, "after")):
        run["stats"][which]["window"] = {
            "window": 1024, "ring_pages": 10, "window_layers": 6,
            "window_pool_bytes": 756_547_584,
            "full_pool_bytes": 2_243_428_352,
            "window_rows": n * 2_359_296, "window_rows_unwindowed":
            n * 10_713_600, "full_rows": n * 3_571_200,
            "ring_wraps": n * 41}
    run["trace"]["decode_scope_s"] = dict(
        run["trace"]["decode_scope_s"],
        window_attention=(DISPATCHES * STEPS * 6, 0.0573))
    run["serve_cfg"]["max_batch_size"] = 48
    return run


def _rows(run):
    """(live rows, rows a window layer sees) of the stamps over the traced
    stretch [10, 15]: every one of the 64 fabricated requests streams across
    it, each holding its prompt and the tokens streamed by 12.5 s: 600 to
    1,100 rows, of which a window layer sees at most 1,024."""
    live = [600 + 7 * i + (12.5 - (9.0 - 0.01 * i)) / (7.0 + 0.03 * i)
            * (30 + i) for i in range(64)]
    assert facts.live_kv_tokens(run, 10.0, 15.0) == pytest.approx(sum(live))
    assert max(live) > 1024 > min(live)
    return sum(live), sum(min(n, 1024) for n in live)


def test_the_new_readers_read_a_fabricated_run():
    run, steps = _fabricated(), DISPATCHES * STEPS
    read = {name: layer_metrics.load(name).read(run) for name in NEW_METRICS}
    kernel_ms = 1e3 * 0.0573 / steps
    assert read["kernels.window_attention_ms_per_decode_step"] == \
        pytest.approx(kernel_ms)
    live, seen = _rows(run)
    peak = flops.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert read["kernels.window_attention_hbm_roofline_share"] == \
        pytest.approx(100 * 2048 * 6 * seen / peak / (kernel_ms * 1e-3))
    assert read["kv.window_live_row_share"] == pytest.approx(
        100 * 2_359_296 / 10_713_600)
    moved = windowed_counters.decode_step_bytes(run)
    assert read["kv.window_share_of_decode_bytes"] == pytest.approx(
        100 * 2048 * 6 * seen / moved)
    for value in read.values():
        assert 0 < value < 100


def test_a_program_without_a_window_group_reads_nothing():
    for bare in (False, True):
        cell = next(c for c in MANIFEST["workloads"] if c["name"] == CELL)
        run = fabricated(cell, bare)
        for name in NEW_METRICS:
            assert layer_metrics.load(name).read(run) is None, name


def test_the_shared_readers_take_this_familys_bytes():
    run = _fabricated()
    family = families.of(run)
    assert family is families.load("windowed")
    live, seen = _rows(run)
    assert family.live_kv_tokens(run) == pytest.approx(live)
    assert family.kv_bytes_per_token(run) == 4096        # the full layers'
    hit = 29_213 / (DISPATCHES * STEPS * 6 / 8)
    # every request streams across the stretch: 64 live "slots"
    assert family.decode_step_bytes(run) == pytest.approx(
        flops_windowed.decode_step_bytes(run["config"], 2 * live, 6 * seen,
                                         hit, 64))
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
            100 * 4096 * live / peak / (kernel_ms * 1e-3))
    assert family.moe_gmm_ms_per_decode_step(run) == pytest.approx(
        1e3 * 0.7717 / (DISPATCHES * STEPS))
    assert layer_metrics.load(
        "kernels.moe_gmm_hbm_roofline_share").read(run) == pytest.approx(
            100 * hit * 3 * 2304 * 896 * 2 / peak / 0.7717
            * (DISPATCHES * STEPS))


# -- the runner, rehearsed on the CPU at a tiny size ----------------------------

def test_a_program_that_cannot_build_the_window_leaves_at_once():
    """What the parent commit does with this cell: it reads neither
    ``layer_types`` nor ``rope_parameters`` and would build a stack of full
    layers. The runner leaves with one line before JAX starts."""
    stale = {k: v for k, v in TINY.items()}
    stale["layer_types"] = ["full_attention"] * 4
    with pytest.raises(SystemExit, match="cannot run this cell"):
        runner.require_windowed_support(
            dict(stale, sliding_window=16, layer_types=TINY["layer_types"],
                 use_sliding_window=False))
    runner.require_windowed_support(TINY)
    runner.require_windowed_support(_config())


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    from distributed_llm_training_and_inference_system_tpu.utils import platform
    held = platform.enable_compile_cache
    platform.enable_compile_cache = lambda: None
    path = tmp_path_factory.mktemp("windowed") / "mix.json"
    path.write_text(json.dumps(TINY_TRAFFIC))
    try:
        run = runner.run(
            {"name": "tiny.mix", "chips": 1}, TINY, str(path), 3000000019,
            8.0, False, time.monotonic(), require_tpu=False)
        return dict(run, runner="windowed")       # as run.py stamps it
    finally:
        platform.enable_compile_cache = held
        facts.window_requests = runner.linear._plain_window_requests


def test_windowed_runner_rehearsal(rehearsal):
    run = rehearsal
    assert run["kind"] == "serve" and run["stamps"]["kind"] == "serve-closed"
    assert run["judged"] == runner.linear.ENDED_IN_WINDOW
    check = run["check"]
    assert check["ok"] and run["compiled_in_window"] == 0
    assert check["requests"] == runner.CHECK_REQUESTS
    assert check["tokens"] >= 32 * runner.CHECK_REQUESTS
    assert set(check["near_miss_further_std"]) == set(runner.NEAR_MISSES)
    assert check["contexts_past_4_windows"] >= 2
    assert check["ring_wrapped_while_decoding"] >= 1
    assert check["preemptions_in_window"] == 0
    assert check["tol"] == pytest.approx(
        runner.CHECK_TOLERANCE_STD * check["logit_std"])
    # the route every attention program took is in the run's record (the
    # gather baseline here: a CPU; on the chip that is not correct)
    assert {i.split("=")[0] for i in check["attention_impls"]} >= {
        "paged_attention", "window_attention"}
    # (the record is the PROCESS's: a worker that compiled a kernel for the
    # described TPU earlier holds that program's line too)
    assert "window_attention=gather" in check["attention_impls"]
    assert harness.model_dict is runner._plain_model_dict       # put back
    assert runner.parallel.SCOPES != runner.SCOPES
    facts.window_requests = runner.linear.window_requests
    try:
        line = result_line(run, load_cell(CELL, MANIFEST)["end_to_end"],
                           end_to_end.load, traced=False)
        traced = result_line(run, load_cell(CELL, MANIFEST)["per_layer"],
                             layer_metrics.load, traced=True)
    finally:
        facts.window_requests = runner.linear._plain_window_requests
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 6
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s",
                                    "serve_tokens_per_s"}
    assert line["device"]["platform"] == "cpu"      # and so never a result
    assert {"kv.window_live_row_share", "engine.decode_slot_utilization",
            "kernels.paged_attention_live_page_share",
            "moe.experts_hit_share", "engine.prefill_ride_token_share"} <= set(
                traced["metrics"])
    assert 0 < traced["metrics"]["kv.window_live_row_share"]["value"] < 60
    w = run["stats"]["after"]["window"]
    assert (w["window"], w["ring_pages"], w["window_layers"]) == (16, 4, 3)
    # 8 callers over 4 slots: prompts rode the residents' decode steps
    assert run["stats"]["after"]["prefill_ride_tokens"] > 0


@pytest.fixture(scope="module")
def greedy_sample():
    """(params, sample): the tiny model on the runner's seeded weights and
    six prompts with the 30 tokens the REFERENCE decodes greedily behind
    each: what a right server serves."""
    import jax
    import jax.numpy as jnp
    from distributed_llm_training_and_inference_system_tpu.config import schema
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    cfg = schema.ModelConfig.from_dict(runner.model_dict(TINY))
    params = runner.seeded_windowed_params(
        gpt.init(cfg, jax.random.PRNGKey(3), jnp.float32), 3)
    rng, sample = np.random.default_rng(3), []
    with jax.default_matmul_precision("highest"):
        for slot, n in enumerate((21, 40, 33, 60, 25, 50)):
            prompt, served = rng.integers(258, 512, n).tolist(), []
            for _ in range(30):
                lg = windowed_decoder.logits(params, prompt + served, TINY,
                                             positions=[n + len(served) - 1],
                                             round_to=256)
                served.append(int(np.argmax(np.asarray(lg)[-1])))
            sample.append((slot, prompt, served))
    return params, sample


@pytest.mark.parametrize("wrong", [None, *windowed_decoder.WRONG])
def test_the_check_fails_each_wrong_reference(greedy_sample, wrong):
    """Tokens the right reference decodes pass its own check; each wrong
    reference explains them worse than the limits allow, but the two that
    move the window by ONE key (``runner.UNSEEN_BY_TOKENS``): a key more or
    fewer at the window's far edge moves a logit by less than the gap
    between the two largest, so the served token is both models' argmax and
    no check on tokens separates them. They are held on LOGITS
    (tests/test_mellum.py, 1e-4) and in float32 on the chip
    (chip_smoke.py)."""
    import types
    served = runner.Served.__new__(runner.Served)
    served.params, sample = greedy_sample
    served.config, served.require_streaming = TINY, False
    served.ring_rows, served._gaps = 32, {}
    served.serve_cfg = types.SimpleNamespace(max_seq_len=256)
    import jax
    with jax.default_matmul_precision("highest"):
        check = served.check_served(sample, wrong=wrong)
    assert check["requests"] == 6 and check["ring_wrapped_while_decoding"] > 0
    if wrong in runner.UNSEEN_BY_TOKENS:
        # (at a window of 16 keys in float32 one of the two is caught and
        # one is not; at 1,024 keys in bfloat16 neither can be)
        assert check["mean_gap_std"] < 0.1 < 0.3 < 0.5254   # all_full's
        return
    assert check["ok"] == (wrong is None), check
