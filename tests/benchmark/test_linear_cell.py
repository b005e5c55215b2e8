"""The linear-attention cell
(``kimi-linear-48b-a3b-12l-ep8.reason-docs-128``): its configuration,
traffic, generator, counts, readers and runner. The cell and its metrics are
found by NAME, never by their place in ``BENCHMARK.json``."""

import json
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import (end_to_end, facts, flops, flops_linear, layer_metrics,
                       linear_counters, loadgen_linear)
from benchmark.run import load_cell, result_line
from benchmark.runners import linear as linear_runner
from manifest_pins import assert_lists

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "kimi-linear-48b-a3b-12l-ep8.reason-docs-128"
CONFIG = json.loads(
    (ROOT / "benchmark/configs/kimi-linear-48b-a3b-12l-ep8.json").read_text())
TRAFFIC = json.loads(
    (ROOT / "benchmark/traffic/reason-docs-128.json").read_text())
# (256 callers over 128 slots: a saturated cell, whose first end-to-end
# metric is the tokens a second; every metric this PR adds moves that one)
NEW_METRICS = {
    "kernels.kda_decode_ms_per_decode_step": "ms",
    "kernels.kda_decode_hbm_roofline_share": "%",
    "kernels.kda_prefill_roofline_share": "%",
    "kda.state_share_of_decode_bytes": "%",
    "serve_programs.decode_hbm_roofline_share": "%",
    "engine.prefill_state_carry_token_share": "%",
    "kernels.moe_gmm_ms_per_decode_step": "ms",
    "kernels.moe_gmm_hbm_roofline_share": "%",
    "moe.held_experts_hit_share": "%",
    "kernels.mla_attention_ms_per_decode_step": "ms",
    "kernels.mla_attention_roofline_share": "%",
    "kernels.mla_live_page_share": "%"}
LISTED = (
    "engine.decode_slot_utilization", "startup.import_s",
    "startup.program_lowering_s", "startup.program_compile_s",
    "startup.programs", "startup.cache_misses", "startup.engine_work_s",
    "startup.unattributed_s",
    # what moves ``tpot_p95_ms``, read over the replies that ended in the
    # window (``runners/linear.py ended_in_window``)
    "serve_programs.decode_step_device_ms", "device_idle.serve",
    "engine.host_ms_per_decode_step",
    "engine.prefill_stall_ms_per_decode_step", "engine.device_starved_share",
    "moe.held_choice_share", "moe.held_expert_load_imbalance")


# -- the manifest ----------------------------------------------------------------

def test_the_cell_and_its_configuration_are_in_the_manifest_by_name():
    cell = {c["name"]: c for c in MANIFEST["workloads"]}[CELL]
    assert cell["config"] == "kimi-linear-48b-a3b-12l-ep8"
    assert cell["traffic"] == "reason-docs-128" and cell["chips"] == 1
    assert len(cell["why"]) <= 200
    entry = {c["name"]: c for c in MANIFEST["configs"]}[cell["config"]]
    assert set(entry["reduced"]) == {"num_hidden_layers", "num_experts",
                                     "vocab_size", "linear_attn_config"}
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/kimi-linear-48b-a3b-12l-ep8.json"
    spec = load_cell(CELL, MANIFEST)
    # the three ISSUE 40 names (``tpot_p95_ms`` over the replies that ended
    # in the window: PERF.md 6, PR 40, refusal round)
    assert {m["name"] for m in spec["end_to_end"]} == {
        "tpot_p95_ms", "serve_tokens_per_s", "setup_s"}


# this mechanism's alone; the others are entries other cells list too
OWN = ("kernels.kda_prefill_roofline_share", "kda.state_share_of_decode_bytes",
       "engine.prefill_state_carry_token_share")


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_metric_lists_this_cell_and_has_a_reader(name):
    metric = assert_lists(name, CELL, unit=NEW_METRICS[name])
    if name in OWN:
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "serve_tokens_per_s"
    assert callable(layer_metrics.load(name).read)


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
    """``flops_hybrid`` reads the ``nemotron_h`` keys and ``paged_attention``
    in a kernel's name is the K/V kernel's: none of their metrics may list
    this cell, nor may the window-wide routing shares that divide by
    ``num_hidden_layers``. What the un-prefixed entries measure is read for
    this cell through ``families/linear.py`` since PR 59: the experts held
    are this file's ``num_experts`` (not ``n_routed_experts``), the latent
    layers its 3 ``*`` layers (not all 12), and no ``q_lora_rank`` is
    read."""
    for metric in MANIFEST["per_layer"]:
        if metric["name"].startswith((
                "kernels.paged_attention", "kernels.ssm", "ssm.", "kv.",
                "residual.", "moe.experts", "moe.expert_load")):
            assert CELL not in metric.get("workloads", []), metric["name"]
    from benchmark import families
    linear, hybrid = families.load("linear"), families.load("hybrid")
    assert linear.held_experts_hit_share is not hybrid.held_experts_hit_share
    assert linear.mla_attention_roofline_share is not families.load(
        "latent").mla_attention_roofline_share


# -- the configuration -----------------------------------------------------------

def test_the_configuration_holds_the_published_widths_and_its_three_cuts():
    published = {
        "hidden_size": 2304, "num_attention_heads": 32,
        "num_key_value_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "mla_use_nope": True, "intermediate_size": 9216,
        "moe_intermediate_size": 1024, "num_experts_per_token": 8,
        "num_shared_experts": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid",
        "routed_scaling_factor": 2.446, "num_expert_group": 1,
        "topk_group": 1, "first_k_dense_replace": 1, "rms_norm_eps": 1e-05,
        "model_type": "kimi_linear", "tie_word_embeddings": False}
    assert {k: CONFIG[k] for k in published} == published
    la = CONFIG["linear_attn_config"]
    assert (la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]) \
        == (32, 128, 4)
    assert la["kda_layers"] == [1, 2, 3, 5, 6, 7, 9, 10, 11]
    assert la["full_attn_layers"] == [4, 8, 12]
    assert {k: (v["published"], v["here"]) for k, v in
            CONFIG["reduced"].items() if k != "linear_attn_config"} == {
        "num_hidden_layers": (27, 12), "num_experts": (256, 32),
        "vocab_size": (163840, 20480)}
    assert all("bytes_bf16" in CONFIG["reduced"][k] for k in (
        "num_hidden_layers", "num_experts", "vocab_size"))
    assert (CONFIG["router_experts"], CONFIG["first_expert"]) == (256, 0)
    assert "8 chips share each layer" in CONFIG["deployment"]
    assert "2 pipeline stages" in CONFIG["deployment"]
    assert len(CONFIG["assumed"]) >= 10
    assert CONFIG["serve"] == {
        "dtype": "bfloat16", "max_batch_size": 128, "max_seq_len": 16384,
        "kv_block_size": 256, "kv_hbm_budget_gb": 1.5,
        "chunked_prefill_tokens": 1024, "prefix_caching": False}


def test_the_configuration_differs_from_the_catalog_row_in_its_cuts_alone():
    rows = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not rows.exists():
        pytest.skip("no model-configs catalog here")
    row = next(r for r in map(json.loads, rows.read_text().splitlines())
               if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size",
                       "linear_attn_config"}


def test_the_traffic_is_the_issues_letter_for_letter():
    assert TRAFFIC["kind"] == "linear-closed"
    assert TRAFFIC["question_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.8, "min": 64,
        "max": 1024}
    assert TRAFFIC["documents"] == {"every": 16, "tokens": {
        "dist": "lognormal", "median": 8192, "sigma": 0.25, "min": 6144,
        "max": 12288}}
    assert TRAFFIC["output_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.4, "min": 512,
        "max": 2048}
    assert (TRAFFIC["clients"], TRAFFIC["pool_per_client"],
            TRAFFIC["warmup_s"], TRAFFIC["drain_s"],
            TRAFFIC["shape_seed"]) == (256, 1, 10.0, 20.0, 0)
    # greedy; a reply runs to the length the traffic drew for it
    assert TRAFFIC["sampling"] == {"temperature": 0.0, "ignore_eos": True}


# -- the generator ---------------------------------------------------------------

def test_every_seed_sends_the_same_pool_with_a_document_at_every_16th_place():
    vocab = CONFIG["vocab_size"]
    a = loadgen_linear.requests(TRAFFIC, 3000000019, 61.0, vocab)
    b = loadgen_linear.requests(TRAFFIC, 7, 61.0, vocab)
    assert len(a) == len(b) == 256

    def sizes(reqs):
        return sorted((len(r["prompt"]), r["max_tokens"]) for r in reqs)
    assert sizes(a) == sizes(b)                 # the same multiset of work
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    for reqs in (a, b):
        for place, r in enumerate(reqs):
            n = len(r["prompt"])
            if place % 16 == 0:
                assert 6144 + 64 <= n <= 12288 + 1024, place
            else:
                assert 64 <= n <= 1024, place
            assert 512 <= r["max_tokens"] <= 2048
            assert n + r["max_tokens"] <= CONFIG["serve"]["max_seq_len"]
            assert min(r["prompt"]) >= 258 and max(r["prompt"]) < vocab
    assert a[1]["prompt"] != b[1]["prompt"]
    docs, plain = loadgen_linear.shapes(TRAFFIC)
    assert len(docs) == 16 and len(plain) == 240
    # ~885 prompt tokens and ~1,078 output tokens a request
    assert 800 < sum(len(r["prompt"]) for r in a) / 256 < 950


# -- counts by hand --------------------------------------------------------------

def test_parameters_and_bytes_by_hand_at_the_published_sizes():
    C = CONFIG
    # a K mixer: q, k, v 28.31 M; o 9.44 M; the two low-rank pairs 1.64 M;
    # W_b 0.07 M; convs 0.05 M; A_log, dt_bias, the two norms
    kda = (3 * 2304 * 4096 + 4096 * 2304 + 2 * (2304 * 128 + 128 * 4096)
           + 2304 * 32 + 4 * 3 * 4096 + 32 + 4096 + 128 + 2304)
    assert flops_linear.kda_layer_params(C) == kda
    assert round(kda / 1e6, 1) == 39.5
    # a * mixer: q 14.16 M; kv_a 1.33 M; kv_b 4.19 M; o 9.44 M; two norms
    attn = (2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 32 * 128 * 2304
            + 512 + 2304)
    assert flops_linear.attention_layer_params(C) == attn
    assert round(attn / 1e6, 1) == 29.1
    assert flops_linear.expert_params(C) == 3 * 2304 * 1024 == 7_077_888
    assert flops_linear.router_params(C) == 2304 * 256 + 256
    assert flops_linear.dense_layer_params(C) == 2304 + 3 * 2304 * 9216
    assert (flops_linear.layers(C, "K"), flops_linear.layers(C, "*"),
            flops_linear.layers(C, "D"), flops_linear.layers(C, "E")) == (
        9, 3, 1, 11)
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    assert flops_linear.total_params(C) == ModelConfig.from_published(
        C).param_count == 3_176_867_744
    full = dict(C, num_hidden_layers=27, num_experts=256, vocab_size=163840,
                linear_attn_config=dict(
                    C["linear_attn_config"],
                    kda_layers=C["reduced"]["linear_attn_config"][
                        "published"]["kda_layers"],
                    full_attn_layers=C["reduced"]["linear_attn_config"][
                        "published"]["full_attn_layers"]))
    assert round(flops_linear.total_params(full) / 1e9, 1) == 49.1
    # a slot's state in one K layer: 32 x 128 x 128 float32 and the last 3
    # inputs of the three convs in bfloat16
    assert flops_linear.state_bytes_per_slot(C) == 2_097_152 + 73_728
    assert flops_linear.state_step_bytes(C, 128) == 2 * 9 * 128 * 2_170_880
    assert flops_linear.latent_row_width(C) == 640
    assert flops_linear.latent_bytes_per_token(C) == 3 * 640 * 2 == 3840
    once = 2 * (9 * kda + 3 * attn + (2304 + 3 * 2304 * 9216)
                + 11 * (2304 + 2304 * 256 + 256 + 7_077_888)
                + 2304 + 2304 * 20480)
    assert flops_linear.once_a_step_weight_bytes(C) == once
    assert 1.27e9 < once < 1.29e9
    assert flops_linear.expert_bytes(C, 352.0) == 352 * 14_155_776
    # a step of 128 live slots that hits every held expert and reads 180k
    # latent rows: state 5.0 GB, experts 4.98 GB, weights 1.28 GB, rows 0.69
    step = flops_linear.decode_step_bytes(C, 180_000, 352, 128)
    assert step == once + 352 * 14_155_776 + 5_001_707_520 + 180_000 * 3840
    assert 0.40 < 5_001_707_520 / step < 0.43
    # the chunked form a token, a K layer: 32 heads x (5 x 64 x 128 +
    # 6 x 128 x 128) operations; q, k, v, the decay's input and o in bf16
    assert flops_linear.chunk_flops_per_token(C) == 32 * (40_960 + 98_304)
    assert flops_linear.chunk_bytes_per_token(C) == 5 * 4096 * 2


def _run(**trace):
    """A run's dict as the readers see it, by hand: 8 decode dispatches of
    8 steps in the traced stretch, 120 live slots a step, 700 live pages of
    256 at each dispatch, 330 (layer, held expert) pairs hit a step."""
    kv = {"kind": "latent", "page_size": 256, "bytes_per_token": 3840}
    moe0 = {"choices": [0] * 32, "decode_experts_hit": 0,
            "decode_layer_steps": 0, "experts_hit": 0, "layer_steps": 0,
            "held_choices": 0, "all_choices": 0}
    kda0 = {"slot_steps": 500, "state_carry_tokens": 1000,
            "state_carry_chunks": 1, "state_bytes": 2_500_853_760}
    before = {"kv": {**kv, "live_pages": 1000, "table_pages": 10000},
              "decode_steps": 100, "prefill_tokens": 2000,
              "prefill_padded_tokens": 3000, "moe": moe0, "kda": kda0}
    after = {"kv": {**kv, "live_pages": 1000 + 8 * 700,
                    "table_pages": 10000 + 8 * 128 * 64},
             "decode_steps": 164, "prefill_tokens": 2000 + 10000,
             "prefill_padded_tokens": 3000 + 12288,
             "moe": {**moe0, "decode_experts_hit": 64 * 330,
                     "decode_layer_steps": 64 * 11,
                     "experts_hit": 64 * 11 * 28, "layer_steps": 64 * 11},
             "kda": {**kda0, "slot_steps": 500 + 64 * 120,
                     "state_carry_tokens": 1000 + 4000}}
    stats = {"before": before, "after": after}
    return {"config": CONFIG, "device": {"kind": "TPU v5e"},
            "runner": "linear",
            "serve_cfg": {"decode_steps_per_dispatch": 8,
                          "max_batch_size": 128},
            "stats": stats, "trace_stats": stats,
            "trace": {"programs": {"decode": (8, 8 * 8 * 0.020)}, **trace}}


def test_the_readers_compute_what_they_say_by_hand():
    run = _run(scope_s={"kda_decode": (576, 64 * 0.0070),
                        "kda_chunk_prefill": (100, 0.5),
                        "kda_conv": (576, 1.0),
                        "moe_gmm": (1408, 64 * 0.0064),
                        "moe_gmm_prefill": (44, 9.9),
                        "mla_paged_attention": (192, 64 * 0.0012),
                        "mla_paged_attention_mq": (6, 9.9)})
    read = lambda name: layer_metrics.load(name).read(run)
    assert np.isclose(read("kernels.kda_decode_ms_per_decode_step"), 7.0)
    assert linear_counters.live_slots_per_step(run) == 120
    assert linear_counters.decode_experts_hit_per_step(run) == 330
    assert linear_counters.live_latent_tokens(run) == 700 * 256
    # 2 x 9 layers x 120 slots x 2,170,880 B = 4.69 GB at 819 GB/s = 5.73 ms
    state = 2 * 9 * 120 * 2_170_880
    assert np.isclose(read("kernels.kda_decode_hbm_roofline_share"),
                      100 * (state / 819e9) / 0.0070)
    total = (flops_linear.once_a_step_weight_bytes(CONFIG)
             + 330 * 14_155_776 + state + 700 * 256 * 3840)
    assert np.isclose(read("kda.state_share_of_decode_bytes"),
                      100 * state / total)
    assert np.isclose(read("serve_programs.decode_hbm_roofline_share"),
                      100 * (total / 819e9) / 0.020)
    # 12,288 rows x 9 layers: bytes 40,960 B a row (50 ns) over FLOPs
    # 4.46 M a row (22.6 ns)
    per_row = max(32 * 139_264 / 197e12, 40_960 / 819e9)
    assert per_row == 40_960 / 819e9
    assert np.isclose(read("kernels.kda_prefill_roofline_share"),
                      100 * 12288 * 9 * per_row / 0.5)
    assert np.isclose(read("engine.prefill_state_carry_token_share"), 40.0)
    # the grouped matmuls: 330 hit (layer, expert) pairs x 14.16 MB a step
    assert np.isclose(read("kernels.moe_gmm_ms_per_decode_step"), 6.4)
    assert np.isclose(read("kernels.moe_gmm_hbm_roofline_share"),
                      100 * (330 * 14_155_776 / 819e9) / 0.0064)
    # 28 of the 32 held experts hit a layer a step
    assert np.isclose(read("moe.held_experts_hit_share"), 87.5)
    # the latent walk: 179,200 live rows x 640 x 2 B in each of THREE
    # layers (0.28 ms each; the absorbed form's 12.5 GFLOP take 0.06)
    assert np.isclose(read("kernels.mla_attention_ms_per_decode_step"),
                      1.2)
    rows = 700 * 256
    assert flops_linear.mla_kernel_bytes(CONFIG, rows) == rows * 1280
    assert flops_linear.mla_kernel_flops(CONFIG, rows) == (
        2 * 32 * rows * (576 + 512))
    assert rows * 1280 / 819e9 > 2 * 32 * rows * 1088 / 197e12
    assert np.isclose(read("kernels.mla_attention_roofline_share"),
                      100 * 3 * (rows * 1280 / 819e9) / 0.0012)
    assert np.isclose(read("kernels.mla_live_page_share"),
                      100 * 8 * 700 / (8 * 128 * 64))
    assert flops.peaks("TPU v5e")["hbm_bytes_per_s"] == 819e9
    for name in NEW_METRICS:
        if NEW_METRICS[name][0] == "%":
            assert 0 < read(name) <= 100, name


def test_a_program_without_the_spans_or_counters_reads_nothing():
    """The parent commit has no ``K`` layer, no such scope and no ``kda``
    counters: every new reader returns None and raises nothing."""
    run = _run()
    for side in ("before", "after"):
        run["stats"][side] = {"kv": {"page_size": 64},
                              "decode_steps": 5, "prefill_tokens": 1}
    run["trace_stats"] = run["stats"]
    for name in NEW_METRICS:
        assert layer_metrics.load(name).read(run) is None, name
    run = _run()                              # counters, but no trace
    for name in ("kernels.kda_decode_ms_per_decode_step",
                 "kernels.kda_decode_hbm_roofline_share",
                 "kernels.kda_prefill_roofline_share",
                 "kernels.moe_gmm_ms_per_decode_step",
                 "kernels.moe_gmm_hbm_roofline_share",
                 "kernels.mla_attention_ms_per_decode_step",
                 "kernels.mla_attention_roofline_share"):
        assert layer_metrics.load(name).read(run) is None, name
    run["trace"] = {}                         # an untraced run
    assert layer_metrics.load(
        "serve_programs.decode_hbm_roofline_share").read(run) is None


# -- the runner ------------------------------------------------------------------

def _tiny():
    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        KIMI_LINEAR_TEST_PUBLISHED)
    return dict(KIMI_LINEAR_TEST_PUBLISHED, name="tiny", vocab_size=512,
                position_embedding="none", serve={
                    "dtype": "float32", "max_batch_size": 4,
                    "max_seq_len": 256, "kv_hbm_budget_gb": 0.001,
                    "kv_block_size": 8, "chunked_prefill_tokens": 32,
                    "prefill_chunk": 16, "prefix_caching": False})


TINY_TRAFFIC = {
    "kind": "linear-closed",
    "question_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                        "min": 4, "max": 32},
    "documents": {"every": 4, "tokens": {
        "dist": "lognormal", "median": 80, "sigma": 0.2, "min": 50,
        "max": 120}},
    "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.3,
                      "min": 4, "max": 12},
    "sampling": {"temperature": 0.0, "ignore_eos": True}, "shape_seed": 0,
    "warmup_s": 1.0, "drain_s": 5.0, "clients": 8, "pool_per_client": 1}


def test_linear_runner_rehearsal(tmp_path, monkeypatch):
    from distributed_llm_training_and_inference_system_tpu.utils import platform
    monkeypatch.setattr(platform, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(linear_runner, "CHECK_PAD_SHORT", 64)
    monkeypatch.setattr(linear_runner, "CHECK_PAD_LONG", 192)
    monkeypatch.setattr(linear_runner, "CHECK_REQUESTS", 4)     # 4 slots
    monkeypatch.setattr(linear_runner, "CHECK_DOCUMENTS", 1)
    monkeypatch.setattr(linear_runner, "CHECK_NEW_TOKENS", 4)
    monkeypatch.setattr(linear_runner, "CHECK_NEW_TOKENS_DOCUMENT", 4)
    # (16 float32 tokens: a near miss moves none of them off its argmax)
    monkeypatch.setattr(linear_runner, "CHECK_NEAR_MISS_STD", -1e-9)
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(TINY_TRAFFIC))
    # (``run`` puts its ``window_requests`` in ``facts``: taken back after)
    monkeypatch.setattr(facts, "window_requests", facts.window_requests)
    run = linear_runner.run({"name": "tiny.mix", "chips": 1}, _tiny(),
                            str(path), 3000000019, 5.0, False,
                            time.monotonic(), require_tpu=False)
    run["runner"] = "linear"        # as run.py stamps it
    spec = load_cell(CELL, MANIFEST)
    line = result_line(run, spec["end_to_end"], end_to_end.load, False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 4
    assert line["attempted"] == len(linear_runner.ended_in_window(run))
    assert line["compiled_in_window"] == 0
    assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                    "tpot_p95_ms"}
    assert line["device"]["platform"] == "cpu"      # and so never a result
    check = run["check"]
    assert check["requests"] == check["slots"] == 4
    assert check["documents"] >= 1               # chunk-carried state held
    assert "gaps" not in check
    assert set(check["near_miss_further_std"]) == set(
        linear_runner.NEAR_MISSES)
    traced = result_line(run, spec["per_layer"], layer_metrics.load, True)
    assert {"engine.prefill_state_carry_token_share",
            "kda.state_share_of_decode_bytes",
            "moe.held_experts_hit_share",
            "kernels.mla_live_page_share",
            "engine.decode_slot_utilization"} <= set(traced["metrics"])
    assert not {"kernels.kda_decode_ms_per_decode_step",
                "kernels.kda_prefill_roofline_share",
                "kernels.moe_gmm_ms_per_decode_step",
                "device_idle.serve"} & set(traced["metrics"])
    share = traced["metrics"]["engine.prefill_state_carry_token_share"]
    assert 30 < share["value"] < 100
    after = run["stats"]["after"]
    assert after["kv"]["kind"] == "latent" and "kda" in after
    assert after["kda"]["state_carry_chunks"] > 0
    programs = after["compiled_programs"]
    assert programs["prefill_chunk_buckets"] == 1
    assert programs["prefill_dense_buckets"] >= 1   # the questions go cold


def _held(gaps_by_reference: dict):
    """A ``Served`` whose reference passes are given: CHECK_REQUESTS
    requests, each from its own slot, 2 of them documents."""
    served = linear_runner.Served.__new__(linear_runner.Served)
    served.traffic = {"question_tokens": {"max": 1024}}
    served._gaps = {k: {"gaps": g, "margins": [1.0] * len(g), "std": 1.0}
                    for k, g in gaps_by_reference.items()}
    sample = [(slot, [7] * (5000 if slot < 2 else 100), [1, 2])
              for slot in range(linear_runner.CHECK_REQUESTS)]
    return served, sample


@pytest.mark.parametrize("wrong, ok", [
    (None, True), ("rotated_pe", False), ("bf16_state", False),
    ("no_beta", False)])
def test_the_check_fails_a_server_with_a_near_miss_or_a_gross_fault(wrong,
                                                                    ok):
    """The two limits by hand. Tokens served by the RIGHT model: the right
    reference reads a mean gap of 0.01 std, each near miss 0.014 on the same
    tokens, both 0.018, a gross fault 2.0. The check passes the right
    reference; given a near miss AS the reference (a server that differs
    from its reference by that fault) it fails by the second limit alone:
    the reference with the fault toggled off explains the tokens better."""
    def gaps(mean):
        return [0.0] * 90 + [10 * mean] * 10
    served, sample = _held({
        None: gaps(0.01), "bf16_state": gaps(0.014),
        "rotated_pe": gaps(0.014), "bf16_state+rotated_pe": gaps(0.018),
        "no_beta": [2.0] * 100, "bf16_state+no_beta": [2.0] * 100,
        "no_beta+rotated_pe": [2.1] * 100})
    check = served.check_served(sample, wrong=wrong)
    assert check["ok"] is ok
    assert check["documents"] == 2
    assert check["slots"] == linear_runner.CHECK_REQUESTS == 16
    if wrong in linear_runner.NEAR_MISSES:
        assert check["tokens_under_tol"] == 0        # the first limit passes
        assert np.isclose(check["near_miss_further_std"][wrong], -0.004)
    if wrong is None:
        assert np.allclose(list(check["near_miss_further_std"].values()),
                           0.004)


def test_the_window_sample_is_of_replies_that_ended_in_the_window(
        monkeypatch):
    """256 callers over 128 slots send nothing inside the window that also
    ends in it (the accepted ``facts.window_requests`` is empty, and
    ``attempted`` 0 is no result): the cell is judged on replies that
    ENDED inside it and on every failure, and the check holds of them the
    documents first, a slot once."""
    served, _ = _held({})
    doc, plain = [7] * 5000, [7] * 100
    served.served = {"a": (0, plain, [1, 2]), "b": (1, doc, [1, 2]),
                     "c": (1, plain, [1, 2]), "d": (2, plain, [1]),
                     "e": (3, plain, [1, 2]), "f": (4, doc, [1, 2])}

    def rec(i, sent, done):
        return {"id": i, "sent": sent, "done": done, "error": None,
                "status": 200, "chunks": [done]}
    raw = {"window": (10.0, 61.0), "stamps": {"kind": "serve-closed",
           "records": [rec("a", 0.0, 30.0), rec("b", 0.0, 50.0),
                       rec("c", 0.0, 55.0), rec("d", 0.0, 20.0),
                       rec("e", 0.0, 9.0), rec("f", 0.0, 70.0)]}}
    assert facts.window_requests(raw) == []
    # b (the document) first, then a; c shares b's slot, d has one token,
    # e ended before the window and f after it
    assert [s[0] for s in served.window_sample(raw)] == [1, 0]
    # what ``benchmark/run.py`` counts once ``run`` has marked the run: the
    # four that ended in the window and the one that failed (whenever it
    # was sent), never what was in flight at the close; an unmarked run,
    # any other cell's, is counted as ever
    raw["stamps"]["records"] += [
        dict(rec("g", 5.0, None), error="ClientError", chunks=[]),
        dict(rec("h", 40.0, None), error="in flight when the window closed",
             chunks=[], in_flight=True)]
    monkeypatch.setattr(facts, "window_requests",
                        linear_runner.window_requests)
    assert facts.window_requests(raw) == []
    raw["judged"] = linear_runner.ENDED_IN_WINDOW
    assert [r["id"] for r in facts.window_requests(raw)] == list("abcdg")
    assert [r["id"] for r in facts.failed_requests(raw)] == ["g"]


def test_a_program_without_the_k_kind_is_refused_with_one_line(monkeypatch):
    """The parent commit reads neither ``q_lora_rank: null`` nor
    ``linear_attn_config``: the runner leaves with a reason before it
    touches a device."""
    from distributed_llm_training_and_inference_system_tpu.config import schema
    monkeypatch.delattr(schema, "KDAConfig")
    with pytest.raises(SystemExit, match="has no delta-rule"):
        linear_runner.run({"name": "tiny.mix", "chips": 1}, _tiny(), "unused",
                          1, 1.0, False, time.monotonic(), require_tpu=False)


def test_the_seeded_weights_make_every_departure_visible():
    """What ``gpt.init`` leaves trivial is seeded: the K head norm, the kv
    latent's norm and the selection bias; the experts keep their scale."""
    import jax

    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    plain = gpt.init(get_model_config("kimi-linear-test"),
                     jax.random.PRNGKey(0))
    seeded = linear_runner.seeded_linear_params(plain, 3000000019)
    b = seeded["blocks"]
    assert float(abs(b["kda"]["gate_norm"]["scale"]).max()) > 0.1
    assert float(abs(b["attn"]["kv_norm"]["scale"]).max()) > 0.1
    assert float(abs(b["moe"]["router"]["bias"]).max()) > 0
    assert b["moe"]["gate"] is plain["blocks"]["moe"]["gate"]
    assert b["kda"]["A_log"] is plain["blocks"]["kda"]["A_log"]


def test_the_scopes_of_a_chunk_program_go_under_suffix_prefill():
    text = ('  %fusion.7 = f32[4]{0} fusion(%p), kind=kLoop, metadata={'
            'op_name="jit(extend_chunk)/kda_chunk_prefill/mul"}\n'
            '  %fusion.9 = f32[4]{0} fusion(%p), kind=kLoop, metadata={'
            'op_name="jit(extend_chunk)/kda_conv/add"}\n')
    decode = ('  %fusion.7 = f32[4]{0} fusion(%p), kind=kLoop, metadata={'
              'op_name="jit(_decode_impl_n)/kda_gated_norm/mul"}\n')
    op_s = {"suffix_prefill": {"fusion.7": (3, 0.3), "fusion.9": (3, 0.1),
                               "mla_paged_attention_mq.3": (2, 0.2)},
            "decode": {"fusion.7": (8, 0.08), "kda_decode.12": (72, 0.5)}}
    got = linear_runner.scope_seconds(op_s, {
        "prefill chunk 1024": text, "_decode_impl_n": decode})
    assert got == {"kda_chunk_prefill": (3, 0.3), "kda_conv": (3, 0.1),
                   "mla_paged_attention_mq": (2, 0.2),
                   "kda_gated_norm": (8, 0.08), "kda_decode": (72, 0.5)}
