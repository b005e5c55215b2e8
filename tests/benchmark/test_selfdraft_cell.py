"""The self-drafting cell (``joyai-llm-flash-8l-ep2.agent-turns-64``): its
configuration, traffic, counts, readers and runner. What the issue names is
held to be PRESENT (a subset, never an equality or a place in
``BENCHMARK.json``), so that a later cell or metric breaks nothing here."""

import json
import time
from pathlib import Path

import pytest

from benchmark import (end_to_end, flops_selfdraft, layer_metrics,
                       loadgen_docqa)
from benchmark.run import load_cell, result_line
from benchmark.runners import selfdraft as runner
from manifest_pins import assert_lists

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "joyai-llm-flash-8l-ep2.agent-turns-64"
CONFIG = json.loads(
    (ROOT / "benchmark/configs/joyai-llm-flash-8l-ep2.json").read_text())
TRAFFIC = json.loads(
    (ROOT / "benchmark/traffic/agent-turns-64.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW_METRICS = (
    "selfdraft.accepted_draft_share", "selfdraft.tokens_per_slot_step",
    "serve_programs.selfdraft_step_device_ms",
    "serve_programs.selfdraft_draft_share_of_step",
    "serve_programs.decode_hbm_roofline_share",
    "kernels.selfdraft_mla_attention_ms_per_step",
    "kernels.mla_attention_roofline_share",
    "kernels.mla_live_page_share",
    "kernels.selfdraft_moe_gmm_ms_per_step",
    "kernels.moe_gmm_hbm_roofline_share",
    "moe.held_experts_hit_share",
    "kv.prefix_cached_token_share")
# (NOT the nine of PR 51, ``engine.slot_steps.*``, ``engine.wall_ms_...``,
# ``engine.ledger_tokens_per_s``, ``engine.seat_to_first_token_mean_ms``,
# ``engine.starved_ms_per_decode_step.*``: ``test_slot_step_metrics.py`` pins
# their lists by equality, so an append fails nine cases that pass today;
# a ``benchmark`` PR lifts the pins and lists this cell)
SHARED_METRICS = (
    "engine.decode_slot_utilization", "engine.host_ms_per_decode_step",
    "engine.prefill_stall_ms_per_decode_step", "engine.device_starved_share",
    "device_idle.serve", "startup.import_s", "startup.programs",
    "startup.engine_work_s", "moe.held_choice_share",
    "moe.held_expert_load_imbalance")


# -- the manifest ----------------------------------------------------------------

def test_the_cell_and_its_configuration_are_in_the_manifest_by_name():
    cell = {c["name"]: c for c in MANIFEST["workloads"]}[CELL]
    assert cell["config"] == "joyai-llm-flash-8l-ep2"
    assert cell["traffic"] == "agent-turns-64" and cell["chips"] == 1
    entry = {c["name"]: c for c in MANIFEST["configs"]}[cell["config"]]
    assert set(entry["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                     "vocab_size"} == set(CONFIG["reduced"])
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/joyai-llm-flash-8l-ep2.json"
    spec = load_cell(CELL, MANIFEST)
    assert {"tpot_p95_ms", "serve_tokens_per_s", "setup_s"} <= {
        m["name"] for m in spec["end_to_end"]}
    assert "serve_programs.decode_step_device_ms" not in {
        m["name"] for m in spec["per_layer"]}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_new_metric_lists_the_cell_and_has_a_reader(name):
    metric = assert_lists(name, CELL)
    # (the whole step's share moves ``tpot_p95_ms`` since PR 59: the chat
    # cell, which reports no tokens per second, is in its list)
    assert metric["moves"] == ("serve_tokens_per_s" if "mistral-7b-16l.chat"
                               not in metric["workloads"] else "tpot_p95_ms")
    if "roofline" in name:
        assert metric["unit"] == "%" and metric["source"] == "device_trace"
    assert callable(layer_metrics.load(name).read)


@pytest.mark.parametrize("name", SHARED_METRICS)
def test_the_serving_cells_shared_metrics_list_the_cell(name):
    assert_lists(name, CELL)


# -- the configuration -----------------------------------------------------------

@pytest.mark.skipif(not CATALOG.is_file(), reason="no catalog here")
def test_the_file_holds_the_catalog_row_but_for_what_reduced_lists():
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
               if json.loads(line)["name"] == "JoyAI-LLM-Flash")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k, "-") != v}
    assert differs == set(CONFIG["reduced"])
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (8, 128, 64640)
    assert (CONFIG["router_experts"], CONFIG["first_expert"]) == (256, 0)
    assert CONFIG["num_nextn_predict_layers"] == 1


def test_the_file_says_how_it_was_cut_and_what_it_assumed():
    assert "2 chips share each layer" in CONFIG["deployment"]
    for key, cut in CONFIG["reduced"].items():
        assert {"published", "here", "bytes_bf16", "why"} <= set(cut), key
    assumed = " ".join(CONFIG["assumed"])
    for words in ("BEFORE the main model's final norm", "embedding FIRST",
                  "row i by position i", "pairs HALVES"):
        assert words in assumed
    serve = CONFIG["serve"]
    assert (serve["speculative"], serve["speculative_min_acceptance"]) == (
        "mtp", 0.0)
    assert serve["max_seq_len"] == 10240 + 1024 + 1024 + 256
    assert set(serve) - {"dtype"} <= set(CONFIG["serve_why"])


def test_the_program_builds_the_configuration_with_its_module():
    runner.require_selfdraft_support(CONFIG)
    with pytest.raises(SystemExit, match="cannot read"):
        runner.require_selfdraft_support(
            dict(CONFIG, num_nextn_predict_layers=2))


def test_the_weights_are_the_issues_arithmetic():
    gb = 2 * flops_selfdraft.total_params(CONFIG) / 1e9
    assert 10.8 < gb < 10.95                 # the issue: 10.86 GB
    assert flops_selfdraft.cached_layers(CONFIG) == 9
    assert flops_selfdraft.latent_bytes_per_token(CONFIG) == 11_520
    assert flops_selfdraft.expert_layers(CONFIG) == 8
    # both rows of a window walk a page once: bytes do not double, operations do
    assert flops_selfdraft.kernel_flops(CONFIG, 1000.0) == 2 * (
        flops_selfdraft.kernel_flops(CONFIG, 1000.0, rows=1))


# -- the traffic -----------------------------------------------------------------

def test_the_traffic_is_the_issues_letter_for_letter():
    t = TRAFFIC
    assert t["kind"] == "selfdraft-closed"
    assert (t["clients"], t["pool_per_client"]) == (128, 1)
    assert t["documents"] == {"count": 8, "tokens": {
        "dist": "lognormal", "median": 8192, "sigma": 0.2, "min": 6144,
        "max": 10240}}
    assert t["questions_per_document"] == 16
    assert t["question_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.6, "min": 64, "max": 1024}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 512,
                                  "sigma": 0.4, "min": 256, "max": 1024}
    assert t["sampling"] == {"temperature": 0.0, "ignore_eos": True,
                             "return_draft_tokens": True}
    assert (t["shape_seed"], t["warmup_s"], t["drain_s"]) == (0, 10.0, 20.0)


def test_the_pool_is_the_same_work_for_every_seed_and_fits_a_slot():
    vocab = CONFIG["vocab_size"]
    a = loadgen_docqa.requests(TRAFFIC, 1, 61.0, vocab)
    b = loadgen_docqa.requests(TRAFFIC, 3300005301, 61.0, vocab)
    assert len(a) == len(b) == 128
    assert sorted(len(r["prompt"]) for r in a) == sorted(
        len(r["prompt"]) for r in b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert max(len(r["prompt"]) + r["max_tokens"] for r in a) + 256 <= (
        CONFIG["serve"]["max_seq_len"])
    assert max(max(r["prompt"]) for r in a) < vocab


# -- the readers -----------------------------------------------------------------

def _run(mtp=True):
    stats = {side: {
        "kv": {"kind": "latent", "page_size": 256, "live_pages": 1000 * n,
               "table_pages": 3136 * n},
        "moe": {"choices": [n] * 128, "experts_hit": 800 * 40 * n,
                "layer_steps": 8 * 40 * n, "decode_experts_hit": 800 * 40 * n,
                "decode_layer_steps": 8 * 40 * n, "all_choices": 256 * n,
                "held_choices": 128 * n},
        "decode_steps": 40 * n, "prefix_cached_tokens": 9000 * n,
        "prefill_tokens": 300 * n,
        "mtp_drafts": 100 * n * mtp, "mtp_accepted": 25 * n * mtp,
        "mtp_slot_steps": 100 * n * mtp, "mtp_tokens": 125 * n * mtp,
        **({"mtp": {"refused": {"riding": 0}}} if mtp else {}),
    } for n, side in ((1, "before"), (2, "after"))}
    return {"stats": stats, "trace_stats": stats, "config": CONFIG,
            "device": {"kind": "TPU v5 lite"}, "runner": "selfdraft",
            "serve_cfg": {"decode_steps_per_dispatch": 8},
            "trace": {"programs": {"decode": (5, 1.2)}, "decode_scope_s": {
                "mla_paged_attention_mq": (360, 0.4), "moe_gmm": (960, 0.48),
                "mtp_layer": (400, 0.1), "mtp_head": (40, 0.04),
                "mtp_embed_proj": (40, 0.02)}}}


def test_the_readers_read_the_counters_and_the_decode_programs_scopes():
    run = _run()
    read = {n: layer_metrics.load(n).read(run) for n in NEW_METRICS}
    assert read["selfdraft.accepted_draft_share"] == 25.0
    assert read["selfdraft.tokens_per_slot_step"] == 1.25
    assert read["serve_programs.selfdraft_step_device_ms"] == 30.0
    assert read["kernels.selfdraft_mla_attention_ms_per_step"] == 10.0
    assert read["kernels.selfdraft_moe_gmm_ms_per_step"] == 12.0
    assert abs(read["serve_programs.selfdraft_draft_share_of_step"]
               - 100 * 0.16 / 1.2) < 1e-9
    assert abs(read["kv.prefix_cached_token_share"]
               - 100 * 9000 / 9300) < 1e-9
    # 1,000 live pages a dispatch of 8 steps... the counter is a DISPATCH's
    pages = 1000 / (40 / 8)
    floor = 9 * pages * 256 * 640 * 2 / 819e9
    assert abs(read["kernels.mla_attention_roofline_share"]
               - 100 * floor / 0.010) < 1e-6
    for name in NEW_METRICS:
        if "roofline" in name:
            assert 0 < read[name] <= 100, name


def test_on_a_program_that_does_not_draft_every_reader_is_silent():
    """The parent commit: no module, so no ``mtp`` group in its stats and no
    ``mtp_*`` scope. Each new reader returns None and raises nothing."""
    run = _run(mtp=False)
    for name in NEW_METRICS:
        assert layer_metrics.load(name).read(run) is None, name
    run = _run()
    run["trace"] = {}                          # counters, but no trace
    for name in NEW_METRICS:
        if "_ms_per_step" in name or "roofline" in name or "device_ms" in (
                name) or "share_of_step" in name:
            assert layer_metrics.load(name).read(run) is None, name


# -- the runner ------------------------------------------------------------------

def _tiny():
    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        JOYAI_TEST_PUBLISHED)
    return dict(JOYAI_TEST_PUBLISHED, name="tiny", vocab_size=512,
                n_routed_experts=4, router_experts=8, first_expert=0, serve={
                    "dtype": "float32", "max_batch_size": 8,
                    "max_seq_len": 256, "kv_hbm_budget_gb": 0.004,
                    "kv_block_size": 8, "chunked_prefill_tokens": 32,
                    "prefill_chunk": 16, "prefix_caching": True,
                    "speculative": "mtp", "speculative_min_acceptance": 0.0})


TINY_TRAFFIC = {
    "kind": "selfdraft-closed",
    "documents": {"count": 8, "tokens": {
        "dist": "lognormal", "median": 100, "sigma": 0.25, "min": 60,
        "max": 140}},
    "questions_per_document": 1,
    "question_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                        "min": 4, "max": 40},
    "output_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 4, "max": 24},
    "sampling": {"temperature": 0.0, "ignore_eos": True,
                 "return_draft_tokens": True},
    "shape_seed": 0, "warmup_s": 1.0, "drain_s": 5.0, "clients": 8,
    "pool_per_client": 1}


def test_selfdraft_runner_rehearsal(tmp_path, monkeypatch):
    from distributed_llm_training_and_inference_system_tpu.utils import platform
    monkeypatch.setattr(platform, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(runner, "CHECK_ROUND_TO", 256)
    monkeypatch.setattr(runner, "CHECK_REQUESTS", 6)
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(TINY_TRAFFIC))
    run = runner.run({"name": "tiny.mix", "chips": 1}, _tiny(), str(path),
                     3000000019, 4.0, False, time.monotonic(),
                     require_tpu=False)
    run["runner"] = "selfdraft"     # as run.py stamps it
    spec = load_cell(CELL, MANIFEST)
    line = result_line(run, spec["end_to_end"], end_to_end.load, False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert line["compiled_in_window"] == 0
    assert {"tpot_p95_ms", "setup_s", "serve_tokens_per_s"} <= set(
        line["metrics"])
    assert line["device"]["platform"] == "cpu"      # and so never a result
    check = run["check"]
    assert check["requests"] == runner.CHECK_REQUESTS == check["slots"]
    assert check["drafts"] > 0 and check["tokens"] > check["drafts"]
    assert "token_gaps" not in check
    traced = result_line(run, spec["per_layer"], layer_metrics.load, True)
    assert {"selfdraft.accepted_draft_share",
            "selfdraft.tokens_per_slot_step",
            "kernels.mla_live_page_share",
            "moe.held_experts_hit_share",
            "kv.prefix_cached_token_share",
            "engine.decode_slot_utilization",
            "engine.device_starved_share"} <= set(traced["metrics"])
    assert traced["metrics"]["kv.prefix_cached_token_share"][
        "value"] > 60
    assert 1.0 <= traced["metrics"]["selfdraft.tokens_per_slot_step"][
        "value"] <= 2.0
    after = run["stats"]["after"]
    assert after["mtp_slot_steps"] > 0 and "mtp" in after
    assert after["compiled_programs"]["prefill_dense_buckets"] == 0


def test_a_program_that_refuses_the_module_is_refused_with_one_line(
        monkeypatch):
    """The parent commit refuses ``num_nextn_predict_layers`` 1 by name: the
    runner leaves with that reason before it touches a device."""
    from distributed_llm_training_and_inference_system_tpu.config import schema

    def refuse(d):
        raise schema.ConfigError("num_nextn_predict_layers = 1: the "
                                 "next-token prediction module is not served")
    monkeypatch.setattr(schema.ModelConfig, "from_dict", refuse)
    with pytest.raises(SystemExit, match="is not served"):
        runner.run({"name": "tiny.mix", "chips": 1}, _tiny(), "unused", 1,
                   1.0, False, time.monotonic(), require_tpu=False)


def test_the_seeded_weights_make_the_modules_norms_visible():
    import jax

    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    plain = gpt.init(get_model_config("joyai-test"), jax.random.PRNGKey(0))
    seeded = runner.seeded_selfdraft_params(plain, 3000000019)
    for norm in ("enorm", "hnorm", "final_norm"):
        assert float(abs(seeded["mtp"][norm]["scale"]).max()) > 0.1
    assert float(abs(seeded["final_norm"]["scale"]).max()) > 0.1
    assert float(abs(seeded["blocks"]["attn"]["kv_norm"]["scale"]).max()) > 0.1
    bias = seeded["blocks"]["moe"]["router"]["bias"]
    assert 0 < float(abs(bias).max()) <= 0.01
    assert seeded["blocks"]["moe"]["gate"] is plain["blocks"]["moe"]["gate"]
