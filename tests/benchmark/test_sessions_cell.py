"""The sessions cell (``solar-open2-250b-4l-ep8.sessions-64``): its
configuration, traffic, generator, counts, readers and runner. The cell and
its metrics are found by NAME, never by their place in ``BENCHMARK.json``."""

import json
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import (end_to_end, flops, flops_sessions, layer_metrics,
                       loadgen_sessions, sessions_counters)
from benchmark.run import load_cell, result_line
from benchmark.runners import sessions as runner
from manifest_pins import assert_lists

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "solar-open2-250b-4l-ep8.sessions-64"
CONFIG = json.loads(
    (ROOT / "benchmark/configs/solar-open2-250b-4l-ep8.json").read_text())
TRAFFIC = json.loads(
    (ROOT / "benchmark/traffic/sessions-64.json").read_text())
# (128 callers over 64 slots: a saturated cell, whose first end-to-end
# metric is the tokens a second; every metric this PR adds moves that one)
NEW_METRICS = {
    "kv.state_snapshot_token_share": "%",
    "kv.state_snapshot_miss_share": "%",
    "kv.state_snapshot_copy_ms_per_decode_step": "ms",
    "kernels.kda_decode_ms_per_decode_step": "ms",
    "kernels.kda_decode_hbm_roofline_share": "%",
    "kernels.paged_attention_ms_per_decode_step": "ms",
    "kernels.paged_attention_roofline_share": "%",
    "kernels.moe_gmm_hbm_roofline_share": "%",
    "moe.held_experts_hit_share": "%",
    "serve_programs.decode_hbm_roofline_share": "%",
    "engine.prefill_ride_token_share": "%"}
LISTED = (
    "engine.decode_slot_utilization", "serve_programs.decode_step_device_ms",
    "device_idle.serve", "engine.host_ms_per_decode_step",
    "engine.prefill_stall_ms_per_decode_step", "engine.device_starved_share",
    "moe.held_choice_share", "moe.held_expert_load_imbalance",
    "startup.import_s", "startup.program_lowering_s",
    "startup.program_compile_s", "startup.programs", "startup.cache_misses",
    "startup.engine_work_s", "startup.unattributed_s")


# -- the manifest ----------------------------------------------------------------

def test_the_cell_and_its_configuration_are_in_the_manifest_by_name():
    cell = {c["name"]: c for c in MANIFEST["workloads"]}[CELL]
    assert cell["config"] == "solar-open2-250b-4l-ep8"
    assert cell["traffic"] == "sessions-64" and cell["chips"] == 1
    assert len(cell["why"]) <= 200
    entry = {c["name"]: c for c in MANIFEST["configs"]}[cell["config"]]
    assert set(entry["reduced"]) == {"num_hidden_layers", "gqa_layers",
                                     "n_routed_experts", "vocab_size"}
    assert set(entry["reduced"]) == set(CONFIG["reduced"])
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/solar-open2-250b-4l-ep8.json"
    spec = load_cell(CELL, MANIFEST)
    assert [m["name"] for m in spec["end_to_end"]
            if m["name"] != "setup_s"] == ["tpot_p95_ms",
                                           "serve_tokens_per_s"]
    assert "setup_s" in {m["name"] for m in spec["end_to_end"]}


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_metric_lists_this_cell_and_has_a_reader(name):
    metric = assert_lists(name, CELL, unit=NEW_METRICS[name])
    if name.startswith("kv.state_snapshot"):    # the mechanism's own
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "serve_tokens_per_s"
    assert callable(layer_metrics.load(name).read)
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in NEW_METRICS}
    assert metric["layer"] in layers        # a layer PERF.md 3 has


@pytest.mark.parametrize("name", LISTED)
def test_the_accepted_readers_that_read_this_program_rightly_list_the_cell(
        name):
    assert_lists(name, CELL)


def test_what_the_cell_lists_moves_a_metric_the_cell_reports():
    reported = {m["name"] for m in load_cell(CELL, MANIFEST)["end_to_end"]}
    for metric in MANIFEST["per_layer"]:
        if CELL in metric.get("workloads", []):
            assert metric["moves"] in reported, metric["name"]


def test_accepted_readers_that_would_miscount_this_model_do_not_list_it():
    """``serve_programs.prefill_device_ms_per_ktok`` reads nothing in a
    riding cell (the ledger's standing note since PR 45); the ``mla``
    readers want a latent pool, the ``ssm`` ones a state-space mixer, and
    the window-wide routing shares divide by ``num_hidden_layers``: none of
    their metrics may list this cell. What the un-prefixed entries measure
    is read for this cell through ``families/sessions.py`` since PR 59: the
    page kernel by scope (the ten longest operations do not hold the one
    softmax layer's kernel), the state update with its operands' bytes."""
    for metric in MANIFEST["per_layer"]:
        if metric["name"].startswith((
                "serve_programs.prefill_device", "kernels.moe_gmm_ms",
                "kernels.ssm", "ssm.", "kda.", "kernels.mla_", "kv.latent",
                "kv.prefix", "residual.", "moe.experts", "moe.expert_load",
                "diffusion.")):
            assert CELL not in metric.get("workloads", []), metric["name"]
    from benchmark import families
    sessions, linear = families.load("sessions"), families.load("linear")
    assert (sessions.kda_decode_hbm_roofline_share
            is not linear.kda_decode_hbm_roofline_share)
    assert (sessions.paged_attention_ms_per_decode_step is not families.load(
        "serve").paged_attention_ms_per_decode_step)


# -- the configuration -----------------------------------------------------------

def test_the_configuration_holds_the_published_widths_and_its_cuts():
    published = {
        "model_type": "solar_open2", "hidden_size": 4096,
        "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
        "intermediate_size": 10240, "moe_intermediate_size": 1280,
        "rms_norm_eps": 1e-05, "tie_word_embeddings": False,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_interval": 3, "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert {k: (v["published"], v["here"]) for k, v in
            CONFIG["reduced"].items()} == {
        "num_hidden_layers": (48, 4),
        "gqa_layers": (list(range(0, 48, 4)), [0]),
        "n_routed_experts": (320, 40), "vocab_size": (196608, 24576)}
    assert all("bytes_bf16" in CONFIG["reduced"][k] for k in (
        "num_hidden_layers", "n_routed_experts", "vocab_size"))
    assert (CONFIG["router_experts"], CONFIG["first_expert"]) == (320, 0)
    assert "8 chips share each layer" in CONFIG["deployment"]
    assert "8 pipeline stages" in CONFIG["deployment"]
    assert len(CONFIG["assumed"]) >= 10
    serve = CONFIG["serve"]
    assert {k: serve[k] for k in (
        "dtype", "max_batch_size", "max_seq_len", "kv_block_size",
        "chunked_prefill_tokens", "prefix_caching")} == {
        "dtype": "bfloat16", "max_batch_size": 64, "max_seq_len": 16384,
        "kv_block_size": 256, "chunked_prefill_tokens": 1024,
        "prefix_caching": True}
    # a snapshot a session and room for the turns in flight
    assert serve["state_snapshot_entries"] >= TRAFFIC["clients"] + 16
    assert set(CONFIG["serve_why"]) >= {
        "max_batch_size", "kv_hbm_budget_gb", "state_snapshot_entries"}


def test_the_configuration_differs_from_the_catalog_row_in_its_cuts_alone():
    rows = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not rows.exists():
        pytest.skip("no model-configs catalog here")
    row = next(r for r in map(json.loads, rows.read_text().splitlines())
               if r["name"] == "Solar-Open2-250B")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == {"num_hidden_layers", "gqa_layers",
                       "n_routed_experts", "vocab_size"}


def test_the_traffic_is_the_issues_letter_for_letter():
    assert TRAFFIC["kind"] == "sessions-closed"
    assert TRAFFIC["first_history_tokens"] == {
        "dist": "lognormal", "median": 4096, "sigma": 0.5, "min": 2048,
        "max": 8192}
    assert TRAFFIC["message_tokens"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.8, "min": 32,
        "max": 1024}
    assert TRAFFIC["output_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.4, "min": 128,
        "max": 512}
    assert (TRAFFIC["clients"], TRAFFIC["session_max_tokens"],
            TRAFFIC["warmup_s"], TRAFFIC["drain_s"],
            TRAFFIC["shape_seed"]) == (128, 15360, 10.0, 20.0, 0)
    assert TRAFFIC["clients"] == 2 * CONFIG["serve"]["max_batch_size"]
    assert (TRAFFIC["session_max_tokens"] + TRAFFIC["output_tokens"]["max"]
            <= CONFIG["serve"]["max_seq_len"])
    # greedy; a reply runs to the length the traffic drew for it
    assert TRAFFIC["sampling"] == {"temperature": 0.0, "ignore_eos": True}


# -- the generator ---------------------------------------------------------------

def test_every_seed_has_the_same_sessions_and_the_same_work():
    vocab = CONFIG["vocab_size"]
    histories = loadgen_sessions.first_histories(TRAFFIC, vocab)
    assert histories == loadgen_sessions.first_histories(TRAFFIC, vocab)
    assert len(histories) == 128
    assert all(2048 <= len(h) <= 8192 for h in histories)
    assert all(min(h) >= 258 and max(h) < vocab for h in histories)
    assert 4000 < np.median([len(h) for h in histories]) < 4200
    a = loadgen_sessions.turn_shapes(TRAFFIC, 3000000019)
    b = loadgen_sessions.turn_shapes(TRAFFIC, 7)
    assert len(a) == len(b) == 128 and all(len(row) == 64 for row in a)
    assert sorted(map(tuple, a)) == sorted(map(tuple, b))   # the same rows
    assert a != b                                           # other sessions
    for row in a:
        assert all(32 <= m <= 1024 and 128 <= o <= 512 for m, o in row)
    # a turn adds ~260 + ~275 tokens to a session of ~4.4k
    assert 230 < np.mean([m for row in a for m, _ in row]) < 290
    assert 250 < np.mean([o for row in a for _, o in row]) < 300


# -- counts by hand --------------------------------------------------------------

def test_parameters_and_bytes_by_hand_at_the_published_sizes():
    C = CONFIG
    # a K mixer: q, k, v 100.66 M; o 33.55 M; the two low-rank pairs 3.15 M;
    # W_b 0.26 M; convs 0.10 M; A_log, dt_bias, the two norms
    kda = (3 * 4096 * 8192 + 8192 * 4096 + 2 * (4096 * 128 + 128 * 8192)
           + 4096 * 64 + 4 * 3 * 8192 + 64 + 8192 + 128 + 4096)
    assert flops_sessions.kda_layer_params(C) == kda
    assert round(kda / 1e6, 1) == 137.7
    # the gated softmax mixer: q, o, gate 33.55 M each; k, v 4.19 M each
    attn = 3 * 4096 * 8192 + 2 * 4096 * 1024 + 4096
    assert flops_sessions.attention_layer_params(C) == attn
    assert round(attn / 1e6, 1) == 109.1
    assert flops_sessions.expert_params(C) == 3 * 4096 * 1280 == 15_728_640
    assert flops_sessions.router_params(C) == 4096 * 320 + 320
    assert (flops_sessions.layers(C, "K"), flops_sessions.layers(C, "*"),
            flops_sessions.layers(C, "E")) == (3, 1, 4)
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    total = flops_sessions.total_params(C)
    assert total == ModelConfig.from_published(C).param_count
    assert round(total * 2 / 1e9, 2) == 6.62                 # bfloat16
    full = dict(C, num_hidden_layers=48, n_routed_experts=320,
                vocab_size=196608, gqa_layers=list(range(0, 48, 4)))
    assert round(flops_sessions.total_params(full) / 1e9, 1) == 250.3
    # a slot's state in one K layer: 64 x 128 x 128 float32 and the last 3
    # inputs of the three convs in bfloat16; a snapshot entry is three
    assert flops_sessions.state_bytes_per_slot(C) == 4_194_304 + 147_456
    assert flops_sessions.state_step_bytes(C, 64) == 2 * 3 * 64 * 4_341_760
    assert flops_sessions.kda_operand_bytes(C, 64) == 3 * 64 * 6 * 8192 * 4
    assert flops_sessions.kv_bytes_per_token(C) == 2 * 8 * 128 * 2 == 4096
    once = 2 * (3 * kda + attn
                + 4 * (4096 + 4096 * 320 + 320 + 15_728_640)
                + 4096 + 4096 * 24576)
    assert flops_sessions.once_a_step_weight_bytes(C) == once
    assert 1.38e9 < once < 1.40e9
    assert flops_sessions.expert_bytes(C, 128.0) == 128 * 31_457_280
    # a step of 64 live slots that hits 128 of the 160 (layer, held expert)
    # pairs and reads 400k K/V rows: experts 4.03 GB, state 1.67 GB, rows
    # 1.64 GB, weights 1.39 GB: ISSUE 46's reckoning (8.4 GB) by hand
    step = flops_sessions.decode_step_bytes(C, 400_000, 128, 64)
    assert step == once + 128 * 31_457_280 + 1_667_235_840 + 400_000 * 4096
    assert 8.6e9 < step < 8.8e9


def _run(**trace):
    """A run's dict as the readers see it, by hand: 8 decode dispatches of
    8 steps in the traced stretch, 60 live slots a step, 1,500 live pages
    of 256 at each dispatch, 120 (layer, held expert) pairs hit a step; in
    the window 90 admissions armed from a snapshot and 10 that found none,
    55,000 prompt tokens skipped and 7,500 prefilled."""
    kv = {"kind": "kv", "page_size": 256, "bytes_per_token": 4096}
    moe0 = {"choices": [0] * 40, "decode_experts_hit": 0,
            "decode_layer_steps": 0, "experts_hit": 0, "layer_steps": 0,
            "held_choices": 0, "all_choices": 0}
    kda0 = {"slot_steps": 500, "snapshots_taken": 128, "snapshot_hits": 3,
            "snapshot_misses": 0, "snapshot_evictions": 0,
            "snapshot_tokens_skipped": 1000, "snapshot_entries_live": 128}
    before = {"kv": {**kv, "live_pages": 1000, "table_pages": 10000},
              "decode_steps": 100, "prefill_tokens": 2000,
              "prefill_ride_tokens": 0, "moe": moe0, "kda": kda0}
    after = {"kv": {**kv, "live_pages": 1000 + 8 * 1500,
                    "table_pages": 10000 + 8 * 64 * 64},
             "decode_steps": 164, "prefill_tokens": 2000 + 7500,
             "prefill_ride_tokens": 6000,
             "moe": {**moe0, "decode_experts_hit": 64 * 120,
                     "decode_layer_steps": 64 * 4,
                     "experts_hit": 64 * 4 * 30, "layer_steps": 64 * 4},
             "kda": {**kda0, "slot_steps": 500 + 64 * 60,
                     "snapshot_hits": 93, "snapshot_misses": 10,
                     "snapshot_tokens_skipped": 1000 + 55_000}}
    stats = {"before": before, "after": after}
    return {"config": CONFIG, "device": {"kind": "TPU v5e"},
            "runner": "sessions",
            "serve_cfg": {"decode_steps_per_dispatch": 8,
                          "max_batch_size": 64},
            "stats": stats, "trace_stats": stats,
            "trace": {"programs": {"decode": (8, 8 * 8 * 0.016)}, **trace}}


def test_the_readers_compute_what_they_say_by_hand():
    run = _run(scope_s={"kda_decode": (192, 64 * 0.0030),
                        "kda_chunk_prefill": (100, 0.5),
                        "moe_gmm": (512, 64 * 0.0060),
                        "moe_gmm_prefill": (44, 9.9),
                        "paged_attention": (64, 64 * 0.0025),
                        "paged_attention_mq": (6, 9.9),
                        "kda_snapshot_take": (12, 64 * 0.00002),
                        "kda_snapshot_arm": (10, 64 * 0.00001)})
    read = lambda name: layer_metrics.load(name).read(run)
    assert np.isclose(read("kv.state_snapshot_token_share"),
                      100 * 55_000 / 62_500)
    assert np.isclose(read("kv.state_snapshot_miss_share"), 10.0)
    assert np.isclose(read("kv.state_snapshot_copy_ms_per_decode_step"), 0.03)
    assert np.isclose(read("kernels.kda_decode_ms_per_decode_step"),
                      3.0)
    assert sessions_counters.live_slots_per_step(run) == 60
    assert sessions_counters.decode_experts_hit_per_step(run) == 120
    assert sessions_counters.live_kv_tokens(run) == 1500 * 256
    # 2 x 3 layers x 60 slots x 4,341,760 B and 3 x 60 x 6 x 32 KB of
    # operands = 1.60 GB at 819 GB/s = 1.95 ms
    state = 2 * 3 * 60 * 4_341_760
    operands = 3 * 60 * 6 * 8192 * 4
    assert np.isclose(read("kernels.kda_decode_hbm_roofline_share"),
                      100 * ((state + operands) / 819e9) / 0.0030)
    # the one softmax layer: 384,000 live rows x 4,096 B = 1.92 ms
    assert np.isclose(
        read("kernels.paged_attention_ms_per_decode_step"), 2.5)
    assert np.isclose(read("kernels.paged_attention_roofline_share"),
                      100 * (384_000 * 4096 / 819e9) / 0.0025)
    # the grouped matmuls: 120 hit (layer, expert) pairs x 31.46 MB a step
    assert np.isclose(read("kernels.moe_gmm_hbm_roofline_share"),
                      100 * (120 * 31_457_280 / 819e9) / 0.0060)
    # 30 of the 40 held experts hit a layer a step
    assert np.isclose(read("moe.held_experts_hit_share"), 75.0)
    total = (flops_sessions.once_a_step_weight_bytes(CONFIG)
             + 120 * 31_457_280 + state + 384_000 * 4096)
    assert np.isclose(
        read("serve_programs.decode_hbm_roofline_share"),
        100 * (total / 819e9) / 0.016)
    assert np.isclose(read("engine.prefill_ride_token_share"), 80.0)
    assert flops.peaks("TPU v5e")["hbm_bytes_per_s"] == 819e9
    for name in NEW_METRICS:
        if NEW_METRICS[name] == "%":
            assert 0 < read(name) <= 100, name


def test_a_program_without_the_spans_or_counters_reads_nothing():
    """A commit without the snapshot pool has no such counter and no such
    scope: every new reader that needs one returns None and raises
    nothing."""
    run = _run()
    for side in ("before", "after"):
        run["stats"][side] = {"kv": {"page_size": 64},
                              "decode_steps": 5, "prefill_tokens": 1}
    run["trace_stats"] = run["stats"]
    for name in NEW_METRICS:
        assert layer_metrics.load(name).read(run) is None, name
    run = _run()                              # counters, but no trace
    for name in NEW_METRICS:
        if name.startswith(("kernels.", "kv.state_snapshot_copy")):
            assert layer_metrics.load(name).read(run) is None, name
    run["trace"] = {}                         # an untraced run
    assert layer_metrics.load(
        "serve_programs.decode_hbm_roofline_share").read(run) is None
    del run["stats"]["after"]["kda"]["snapshot_hits"]   # a K model, no pool
    assert layer_metrics.load(
        "kv.state_snapshot_miss_share").read(run) is None


# -- the runner ------------------------------------------------------------------

def _tiny():
    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        SOLAR_OPEN2_TEST_PUBLISHED)
    return dict(SOLAR_OPEN2_TEST_PUBLISHED, name="tiny", vocab_size=512,
                n_routed_experts=4, router_experts=16, first_expert=0,
                serve={"dtype": "float32", "max_batch_size": 4,
                       "max_seq_len": 256, "kv_hbm_budget_gb": 0.001,
                       "kv_block_size": 8, "chunked_prefill_tokens": 32,
                       "prefill_chunk": 16, "prefix_caching": True,
                       "state_snapshot_entries": 64,
                       "decode_steps_per_dispatch": 4})


TINY_TRAFFIC = {
    "kind": "sessions-closed",
    "first_history_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.3,
                             "min": 20, "max": 70},
    "message_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                       "min": 4, "max": 24},
    "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.3,
                      "min": 4, "max": 12},
    "session_max_tokens": 200, "turns_drawn_per_session": 16,
    "sampling": {"temperature": 0.0, "ignore_eos": True}, "shape_seed": 0,
    "warmup_s": 1.0, "drain_s": 5.0, "clients": 8}


def test_sessions_runner_rehearsal(tmp_path, monkeypatch):
    from distributed_llm_training_and_inference_system_tpu.ops import kda
    from distributed_llm_training_and_inference_system_tpu.utils import platform
    monkeypatch.setattr(platform, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(kda, "CHUNK", 8)
    monkeypatch.setattr(runner, "CHECK_REQUESTS", 4)            # 4 slots
    monkeypatch.setattr(runner, "PROBES", 2)
    monkeypatch.setattr(runner, "PROBE_TOKENS", 6)
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(TINY_TRAFFIC))
    run = runner.run({"name": "tiny.mix", "chips": 1}, _tiny(), str(path),
                     3000000019, 4.0, False, time.monotonic(),
                     require_tpu=False)
    run["runner"] = "sessions"      # as run.py stamps it
    spec = load_cell(CELL, MANIFEST)
    line = result_line(run, spec["end_to_end"], end_to_end.load, False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 8
    assert line["compiled_in_window"] == 0
    assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                    "tpot_p95_ms"}
    assert line["device"]["platform"] == "cpu"      # and so never a result
    check = run["check"]
    assert check["requests"] == check["slots"] == check["snapshot_hits"] == 4
    assert check["tokens_off_the_reference_argmax"] == 0
    assert "gaps" not in check and "probe_gaps" not in check
    # two probes behind their own snapshots: the reference's tokens, and
    # the entries' rows are the reference's states (float32 both sides)
    assert (check["probes"], check["probe_tokens"]) == (2, 12)
    assert check["probe_tokens_under_tol"] == 0
    assert check["state_rel_err"] < 1e-4
    # every turn carried the ids of the reply before it
    records = run["stamps"]["records"]
    assert not any(r.get("reply_ids_drawn") for r in records)
    assert max(r["turn"] for r in records) >= 2
    traced = result_line(run, spec["per_layer"], layer_metrics.load, True)
    assert {"kv.state_snapshot_token_share", "kv.state_snapshot_miss_share",
            "moe.held_experts_hit_share",
            "engine.prefill_ride_token_share",
            "engine.decode_slot_utilization", "moe.held_choice_share"
            } <= set(traced["metrics"])
    assert not {"kernels.kda_decode_ms_per_decode_step",
                "kv.state_snapshot_copy_ms_per_decode_step",
                "device_idle.serve"} & set(traced["metrics"])
    assert traced["metrics"]["kv.state_snapshot_token_share"]["value"] > 50
    # (sessions of 200 tokens begin again every few turns, and the snapshot
    # of a first history is the first to go once a longer one stands)
    assert traced["metrics"]["kv.state_snapshot_miss_share"]["value"] < 30
    after = run["stats"]["after"]
    assert after["kv"]["kind"] == "kv" and after["kv"]["snapshot_bytes"] > 0
    assert after["kda"]["snapshot_hits"] > 8
    programs = after["compiled_programs"]
    assert programs["snapshot"] == 2 and programs["prefill_dense_buckets"] == 0


def _held(readings: dict):
    """A ``Served`` whose reference passes are given: CHECK_REQUESTS turns,
    each from its own slot, each armed at token 4,096, and PROBES probes."""
    served = runner.Served.__new__(runner.Served)
    served._gaps = {None: dict({"margins": [1.0] * 100, "std": 1.0},
                               **readings)}
    served.state_dtypes = ["float32"]
    sample = [(slot, [7] * 5000, [1, 2], 4096)
              for slot in range(runner.CHECK_REQUESTS)]
    probes = [([7] * 4865, [1, 2], 4864, None)] * runner.PROBES
    return served, sample, probes


RIGHT = {"gaps": [0.0] * 96 + [0.4] * 4, "probe_gaps": [0.0] * 120 + [0.4] * 8,
         "state_err": [0.06, 0.07, 0.05, 0.06]}


@pytest.mark.parametrize("change, ok", [
    ({}, True),         # 4 % and 6 % of the tokens past 0.25 std, 0.6 %
    ({"gaps": [0.0] * 94 + [0.4] * 6}, False),          # the first limit
    ({"probe_gaps": [0.0] * 100 + [0.4] * 28}, False),  # armed wrongly
    ({"state_err": [0.06, 0.3, 0.05, 0.06]}, False),    # one entry off
], ids=["right", "a gross fault", "a probe off", "an entry off"])
def test_the_three_limits_of_the_check(change, ok):
    served, sample, probes = _held(dict(RIGHT, **change))
    out = served.check_served(sample, probes)
    assert out["ok"] is ok
    assert out["requests"] == runner.CHECK_REQUESTS
    served, sample, probes = _held(dict(RIGHT, **change))
    assert not served.check_served(sample[:-1], probes)["ok"]   # a slot short
    assert not served.check_served(sample, probes[:-1])["ok"]   # a probe short
    served.state_dtypes = ["bfloat16", "float32"]   # a pool in bfloat16
    assert not served.check_served(sample, probes)["ok"]


def test_the_window_sample_is_of_snapshot_hits_one_a_slot_the_longest_first():
    served = runner.Served.__new__(runner.Served)
    n = 40
    served.served = {f"r{i}": (i % 16, [7] * (3000 + 100 * i), [1, 2, 3])
                     for i in range(n)}
    served.served["r5"] = (5, [7] * 9000, [1, 2, 3])      # the longest
    served.armed_at = {f"r{i}": 2560 for i in range(n)}
    served.armed_at["r0"] = 0                   # prefilled from zero
    served.armed_at["r39"] = 0
    recs = [{"id": f"r{i}", "sent": 10.0 + i, "done": 20.0 + i,
             "error": None, "status": 200, "chunks": [15.0 + i]}
            for i in range(n)]
    recs[3]["done"] = 99.0                      # ended after the window
    raw = {"window": (5.0, 70.0), "stamps": {"kind": "serve-closed",
                                             "records": recs}}
    sample = served.window_sample(raw)
    assert len(sample) == runner.CHECK_REQUESTS
    assert len({s[0] for s in sample}) == len(sample)
    assert len(sample[0][1]) == 9000 and all(s[3] == 2560 for s in sample)
    assert all(len(s[1]) != 3000 and len(s[1]) != 3300 for s in sample)


def test_a_program_without_a_snapshot_pool_is_refused_with_one_line(
        monkeypatch):
    from distributed_llm_training_and_inference_system_tpu.config import schema
    fields = dict(schema.ServeConfig.__dataclass_fields__)
    del fields["state_snapshot_entries"]
    monkeypatch.setattr(schema.ServeConfig, "__dataclass_fields__", fields)
    with pytest.raises(SystemExit, match="keeps no snapshot of a recurrent "
                                         "state"):
        runner.run({"name": "x", "chips": 1}, CONFIG, "unused", 1, 1.0,
                   False, time.monotonic(), require_tpu=False)


def test_a_program_that_builds_another_model_is_refused_with_one_line(
        monkeypatch):
    monkeypatch.setattr(runner, "model_dict", lambda config: dict(
        runner._plain_model_dict(config),
        linear_attn_config=config["linear_attn_config"],
        gqa_layers=config["gqa_layers"], use_gqa_gate=False))
    with pytest.raises(SystemExit, match="gated attention"):
        runner.require_sessions_support(CONFIG)
    monkeypatch.undo()
    runner.require_sessions_support(CONFIG)             # this program: fine


def test_the_seeded_weights_make_every_departure_visible():
    import jax
    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    plain = gpt.init(get_model_config("solar-open2-test"),
                     jax.random.PRNGKey(0))
    seeded = runner.seeded_sessions_params(plain, 3000000019)
    for at in (("kda", "gate_norm", "scale"), ("moe", "router", "bias")):
        a, b = plain["blocks"], seeded["blocks"]
        for k in at:
            a, b = a[k], b[k]
        assert not np.asarray(a).any() and np.asarray(b).std() > 1e-3
    assert seeded["blocks"]["attn"]["gate"]["kernel"] is \
        plain["blocks"]["attn"]["gate"]["kernel"]
    assert np.asarray(plain["blocks"]["attn"]["gate"]["kernel"]).std() > 0.01


def test_a_program_named_for_a_scope_goes_under_it():
    """The two snapshot copies are programs of their own, of which the
    engine's ``program_texts`` has none: every operation of
    ``jit_kda_snapshot_take`` counts under ``kda_snapshot_take``; a Pallas
    kernel of the decode program under its own name."""
    op_s = {"jit_kda_snapshot_take": {"fusion.1": (3, 0.002),
                                      "fusion.2": (3, 0.001)},
            "jit_kda_snapshot_arm": {"fusion.1": (2, 0.004)},
            "decode": {"kda_decode.7": (24, 0.5), "fusion.9": (8, 0.1),
                       "paged_attention_mq.3": (2, 0.3),
                       "paged_attention.4": (8, 0.2)}}
    got = runner.scope_seconds(op_s, {})
    assert got["kda_snapshot_take"] == (6, 0.003)
    assert got["kda_snapshot_arm"] == (2, 0.004)
    assert got["kda_decode"] == (24, 0.5)
    assert got["paged_attention"] == (8, 0.2)
    assert got["paged_attention_mq"] == (2, 0.3)
    assert set(got) == {"kda_snapshot_take", "kda_snapshot_arm",
                        "kda_decode", "paged_attention",
                        "paged_attention_mq"}
