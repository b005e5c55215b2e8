"""The latent-attention cell (``xing4.0-29b-a4b-7l.doc-qa-64``): its
configuration, traffic, generator, counts, readers and runner. The cell and
its metrics are found by NAME, never by their place in ``BENCHMARK.json``."""

import json
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import (end_to_end, flops, flops_latent, latent_counters,
                       layer_metrics, loadgen_docqa)
from benchmark.run import load_cell, result_line
from benchmark.runners import latent as latent_runner
from manifest_pins import assert_lists

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "xing4.0-29b-a4b-7l.doc-qa-64"
CONFIG = json.loads(
    (ROOT / "benchmark/configs/xing4.0-29b-a4b-7l.json").read_text())
TRAFFIC = json.loads((ROOT / "benchmark/traffic/doc-qa-64.json").read_text())
NEW_METRICS = (
    "kernels.mla_attention_ms_per_decode_step",
    "kernels.mla_attention_roofline_share", "kernels.mla_live_page_share",
    "serve_programs.decode_hbm_roofline_share",
    "kv.latent_share_of_decode_bytes", "kv.prefix_cached_token_share",
    "residual.hc_ms_per_decode_step",
    "kernels.moe_gmm_hbm_roofline_share")


# -- the manifest ----------------------------------------------------------------

def test_the_cell_and_its_configuration_are_in_the_manifest_by_name():
    cell = {c["name"]: c for c in MANIFEST["workloads"]}[CELL]
    assert cell["config"] == "xing4.0-29b-a4b-7l"
    assert cell["traffic"] == "doc-qa-64" and cell["chips"] == 1
    entry = {c["name"]: c for c in MANIFEST["configs"]}[cell["config"]]
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                "num_nextn_predict_layers"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/xing4.0-29b-a4b-7l.json"
    spec = load_cell(CELL, MANIFEST)
    assert {m["name"] for m in spec["end_to_end"]} == {
        "tpot_p95_ms", "serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_new_metric_lists_this_cell_and_has_a_reader(name):
    metric = assert_lists(
        name, CELL, unit="ms" if name.endswith("_step") else "%")
    if name in ("kv.latent_share_of_decode_bytes",
                "residual.hc_ms_per_decode_step"):
        assert metric["workloads"] == [CELL]        # this model's alone
    assert callable(layer_metrics.load(name).read)


@pytest.mark.parametrize("name", [
    "engine.decode_slot_utilization", "serve_programs.decode_step_device_ms",
    "device_idle.serve", "engine.host_ms_per_decode_step",
    "engine.prefill_stall_ms_per_decode_step", "engine.device_starved_share"])
def test_the_accepted_readers_that_read_this_program_rightly_list_the_cell(
        name):
    assert_lists(name, CELL)


def test_accepted_readers_that_would_miscount_this_model_do_not_list_it():
    """``moe_counters.decode_experts_hit_per_step`` divides by
    ``num_hidden_layers`` (7 here, 6 expert layers) and ``paged_attention``
    in a kernel's name is the K/V kernel's: no metric that reads those may
    list this cell. (The whole step's and the grouped matmuls' shares of
    their rooflines list it since PR 59: they take the bytes and the hits a
    step from ``families/latent.py``, which divides by the EXPERT layers.)"""
    for metric in MANIFEST["per_layer"]:
        if metric["name"].startswith((
                "kernels.paged_attention", "kernels.moe_gmm_ms",
                "kernels.ssm", "moe.", "ssm.")):
            assert CELL not in metric["workloads"], metric["name"]
    from benchmark import families
    run = _run()
    assert families.read(run, "expert_bytes") == flops_latent.expert_bytes(
        CONFIG, 330) != 0       # 64 x 330 hits over 64 x 6 layer-steps


# -- the configuration -----------------------------------------------------------

def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    published = {
        "hidden_size": 3584, "num_attention_heads": 32, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "intermediate_size": 9216,
        "moe_intermediate_size": 1024, "n_routed_experts": 64,
        "num_experts_per_tok": 4, "n_shared_experts": 1,
        "routed_scaling_factor": 2, "vocab_size": 131072, "hc_mult": 4,
        "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid"}
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["num_nextn_predict_layers"]) == (7, 1, 0)
    assert {k: (v["published"], v["here"])
            for k, v in CONFIG["reduced"].items()} == {
        "num_hidden_layers": (40, 7), "first_k_dense_replace": (2, 1),
        "num_nextn_predict_layers": (1, 0)}
    assert len(CONFIG["assumed"]) >= 10
    serve = CONFIG["serve"]
    assert (serve["max_batch_size"], serve["max_seq_len"],
            serve["kv_hbm_budget_gb"], serve["prefix_caching"]) == (
        64, 17408, 3.0, True)
    assert serve["kv_block_size"] in (64, 128, 256)


def test_the_traffic_is_the_issues_letter_for_letter():
    assert TRAFFIC["kind"] == "latent-closed"
    assert TRAFFIC["documents"] == {"count": 16, "tokens": {
        "dist": "lognormal", "median": 12288, "sigma": 0.25, "min": 8192,
        "max": 16384}}
    assert TRAFFIC["questions_per_document"] == 8
    assert TRAFFIC["question_tokens"] == {
        "dist": "lognormal", "median": 96, "sigma": 0.5, "min": 32,
        "max": 256}
    assert TRAFFIC["output_tokens"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.5, "min": 64,
        "max": 384}
    assert (TRAFFIC["clients"], TRAFFIC["pool_per_client"],
            TRAFFIC["warmup_s"], TRAFFIC["drain_s"],
            TRAFFIC["shape_seed"]) == (128, 1, 10.0, 20.0, 0)
    assert TRAFFIC["sampling"] == {"temperature": 0.0}


# -- the generator ---------------------------------------------------------------

def test_every_seed_sends_the_same_documents_and_sizes_in_its_own_order():
    vocab = CONFIG["vocab_size"]
    a = loadgen_docqa.requests(TRAFFIC, 3000000019, 61.0, vocab)
    b = loadgen_docqa.requests(TRAFFIC, 7, 61.0, vocab)
    docs = loadgen_docqa.documents(TRAFFIC, vocab)
    assert len(a) == len(b) == 128 and len(docs) == 16
    assert all(8192 <= len(d) <= 16384 for d in docs)
    heads = {tuple(d[:32]): i for i, d in enumerate(docs)}

    def sizes(reqs):
        return sorted((heads[tuple(r["prompt"][:32])], len(r["prompt"]),
                       r["max_tokens"]) for r in reqs)
    assert sizes(a) == sizes(b)                 # the same work
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    per_doc = np.bincount([s[0] for s in sizes(a)])
    assert per_doc.tolist() == [8] * 16
    for r in a:
        doc = docs[heads[tuple(r["prompt"][:32])]]
        assert r["prompt"][:len(doc)] == doc
        assert 32 <= len(r["prompt"]) - len(doc) <= 256
        assert 64 <= r["max_tokens"] <= 384
        assert len(r["prompt"]) + r["max_tokens"] <= 17408
        assert min(r["prompt"]) >= 258 and max(r["prompt"]) < vocab
    # a request differs between seeds in its question's ids
    assert a[0]["prompt"] != b[0]["prompt"]


# -- counts by hand --------------------------------------------------------------

def test_parameters_and_bytes_by_hand_at_the_published_sizes():
    full = dict(CONFIG, num_hidden_layers=40, first_k_dense_replace=2)
    assert flops_latent.attention_params(CONFIG) == (
        2_752_512 + 4_718_592 + 2_064_384 + 4_194_304 + 14_680_064)
    assert flops_latent.hyper_connection_params(CONFIG) == (
        14336 + 14336 * 24 + 3 + 4 + 4 + 16)
    assert flops_latent.expert_params(CONFIG) == 11_010_048
    assert flops_latent.dense_ffn_params(CONFIG) == 99_090_432
    assert round(flops_latent.total_params(full) / 1e9, 1) == 29.5
    assert round(flops_latent.total_params(CONFIG) * 2 / 1e9, 2) == 11.08
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    assert flops_latent.total_params(CONFIG) == ModelConfig.from_published(
        CONFIG).param_count
    assert flops_latent.latent_row_width(CONFIG) == 640
    assert flops_latent.latent_row_width(CONFIG, padded=False) == 576
    assert flops_latent.latent_bytes_per_token(CONFIG) == 8960
    assert flops_latent.latent_bytes_per_token(CONFIG, padded=False) == 8064
    # once a step, bf16: 7 attention sub-layers, the dense FFN, 6 routers
    # and shared experts, the head, the norms; float32: 14 maps
    once = 2 * (7 * (28_409_856 + 1280 + 2 * 3584) + 99_090_432
                + 6 * (3584 * 64 + 64 + 11_010_048) + 3584
                + 3584 * 131072) + 4 * 14 * 358_427
    assert flops_latent.once_a_step_weight_bytes(CONFIG) == once
    assert 1.68e9 < once < 1.70e9
    assert flops_latent.expert_bytes(CONFIG, 330.0) == 330 * 22_020_096
    # the kernel: a page of 256 rows is 327,680 B; a head multiplies 1,088
    # values a key, and the expanded form 320
    assert flops_latent.kernel_bytes(CONFIG, 10, 256) == 10 * 327_680
    assert flops_latent.kernel_flops(CONFIG, 1000.0) == 2 * 32 * 1000 * 1088
    assert flops_latent.expanded_over_absorbed(CONFIG) == 320 / 1088


def _run(**trace):
    """A run's dict as the readers see it, by hand: 8 decode dispatches of
    8 steps in the traced stretch, 3,300 live pages of 256 at each, 330
    (layer, expert) pairs hit a step."""
    kv = {"kind": "latent", "page_size": 256, "bytes_per_token": 8960}
    before = {"kv": {**kv, "live_pages": 1000, "table_pages": 10000},
              "decode_steps": 100, "prefix_cached_tokens": 1000,
              "prefill_tokens": 100,
              "moe": {"choices": [0] * 64, "decode_experts_hit": 0,
                      "decode_layer_steps": 0, "experts_hit": 0,
                      "layer_steps": 0}}
    after = {"kv": {**kv, "live_pages": 1000 + 8 * 3300,
                    "table_pages": 10000 + 8 * 64 * 68},
             "decode_steps": 164, "prefix_cached_tokens": 1000 + 99000,
             "prefill_tokens": 100 + 1000,
             "moe": {"choices": [0] * 64, "decode_experts_hit": 64 * 330,
                     "decode_layer_steps": 64 * 6, "experts_hit": 0,
                     "layer_steps": 0}}
    stats = {"before": before, "after": after}
    return {"config": CONFIG, "device": {"kind": "TPU v5e"},
            "runner": "latent",
            "serve_cfg": {"decode_steps_per_dispatch": 8,
                          "max_batch_size": 64},
            "stats": stats, "trace_stats": stats,
            "trace": {"programs": {"decode": (8, 8 * 8 * 0.030)}, **trace}}


def test_the_readers_compute_what_they_say_by_hand():
    run = _run(scope_s={"mla_paged_attention": (448, 64 * 0.0140),
                        "mla_paged_attention_mq": (10, 5.0),
                        "hc_maps": (900, 64 * 0.0010),
                        "hc_mix": (900, 64 * 0.0005),
                        "moe_gmm": (1152, 64 * 0.0120),
                        "moe_gmm_prefill": (18, 3.0)})
    read = lambda name: layer_metrics.load(name).read(run)
    assert np.isclose(read("kernels.mla_attention_ms_per_decode_step"), 14.0)
    assert np.isclose(read("residual.hc_ms_per_decode_step"), 1.5)
    assert latent_counters.live_pages_per_step(run) == 3300
    # 7 layers x 3,300 pages x 327,680 B = 7.57 GB at 819 GB/s = 9.24 ms
    floor_ms = 7 * 3300 * 327_680 / 819e9 * 1e3
    assert np.isclose(read("kernels.mla_attention_roofline_share"),
                      100 * floor_ms / 14.0)
    # (the FLOP floor is under it: 7 x 2 x 32 x 844,800 x 1,088 / 197e12)
    assert 7 * flops_latent.kernel_flops(CONFIG, 3300 * 256) / 197e12 \
        < floor_ms * 1e-3
    assert np.isclose(read("kernels.mla_live_page_share"),
                      100 * 3300 / (64 * 68))
    latent = 3300 * 256 * 8960
    total = flops_latent.once_a_step_weight_bytes(CONFIG) \
        + 330 * 22_020_096 + latent
    assert np.isclose(read("kv.latent_share_of_decode_bytes"),
                      100 * latent / total)
    assert np.isclose(read("serve_programs.decode_hbm_roofline_share"),
                      100 * (total / 819e9) / 0.030)
    assert np.isclose(read("kernels.moe_gmm_hbm_roofline_share"),
                      100 * (330 * 22_020_096 / 819e9) / 0.0120)
    assert np.isclose(read("kv.prefix_cached_token_share"),
                      100 * 99000 / 100000)
    assert flops.peaks("TPU v5e")["hbm_bytes_per_s"] == 819e9


def test_a_program_without_the_spans_or_counters_reads_nothing():
    """The parent commit has no latent pool, no such kernel and no such
    scope: every new reader returns None and raises nothing."""
    run = _run()
    for side in ("before", "after"):
        run["stats"][side] = {"kv": {"page_size": 64},
                              "decode_steps": 5, "prefill_tokens": 1}
    run["trace_stats"] = run["stats"]
    for name in NEW_METRICS:
        assert layer_metrics.load(name).read(run) is None, name
    run = _run()                              # counters, but no trace
    for name in ("kernels.mla_attention_ms_per_decode_step",
                 "kernels.mla_attention_roofline_share",
                 "residual.hc_ms_per_decode_step",
                 "kernels.moe_gmm_hbm_roofline_share"):
        assert layer_metrics.load(name).read(run) is None, name


# -- the runner ------------------------------------------------------------------

def _tiny():
    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        XING_TEST_PUBLISHED)
    return dict(XING_TEST_PUBLISHED, name="tiny", vocab_size=512, serve={
        "dtype": "float32", "max_batch_size": 8, "max_seq_len": 256,
        "kv_hbm_budget_gb": 0.004, "kv_block_size": 8,
        "chunked_prefill_tokens": 32, "prefill_chunk": 16,
        "prefix_caching": True})


TINY_TRAFFIC = {
    "kind": "latent-closed",
    "documents": {"count": 8, "tokens": {
        "dist": "lognormal", "median": 100, "sigma": 0.25, "min": 60,
        "max": 140}},
    "questions_per_document": 1,
    "question_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                        "min": 4, "max": 20},
    "output_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 4, "max": 24},
    "sampling": {"temperature": 0.0}, "shape_seed": 0, "warmup_s": 1.0,
    "drain_s": 5.0, "clients": 8, "pool_per_client": 1}


def test_latent_runner_rehearsal(tmp_path, monkeypatch):
    from distributed_llm_training_and_inference_system_tpu.utils import platform
    monkeypatch.setattr(platform, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(latent_runner, "CHECK_ROUND_TO", 256)
    monkeypatch.setattr(latent_runner, "CHECK_REQUESTS", 6)    # 8 documents
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(TINY_TRAFFIC))
    run = latent_runner.run({"name": "tiny.mix", "chips": 1}, _tiny(),
                            str(path), 3000000019, 4.0, False,
                            time.monotonic(), require_tpu=False)
    run["runner"] = "latent"        # as run.py stamps it
    spec = load_cell(CELL, MANIFEST)
    line = result_line(run, spec["end_to_end"], end_to_end.load, False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert line["compiled_in_window"] == 0
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s",
                                    "serve_tokens_per_s"}
    assert line["device"]["platform"] == "cpu"      # and so never a result
    check = run["check"]
    assert check["requests"] == latent_runner.CHECK_REQUESTS == check["slots"]
    assert check["tokens_kept"] >= latent_runner.CHECK_MIN_KEPT * check[
        "tokens"]
    assert "gaps" not in check
    traced = result_line(run, spec["per_layer"], layer_metrics.load, True)
    assert {"kv.prefix_cached_token_share", "kernels.mla_live_page_share",
            "kv.latent_share_of_decode_bytes",
            "engine.decode_slot_utilization"} <= set(traced["metrics"])
    assert not {"kernels.mla_attention_ms_per_decode_step",
                "residual.hc_ms_per_decode_step"} & set(traced["metrics"])
    assert traced["metrics"]["kv.prefix_cached_token_share"]["value"] > 80
    after = run["stats"]["after"]
    assert after["kv"]["kind"] == "latent"
    programs = after["compiled_programs"]
    assert programs["prefill_dense_buckets"] == 0   # nothing went cold
    assert programs["prefill_chunk_buckets"] == 1


def test_a_program_without_latent_attention_is_refused_with_one_line(
        monkeypatch):
    """The parent commit reads none of the latent keys: the runner leaves
    with a reason before it touches a device."""
    from distributed_llm_training_and_inference_system_tpu.config import schema
    monkeypatch.delattr(schema, "MLAConfig")
    with pytest.raises(SystemExit, match="has no latent attention"):
        latent_runner.run({"name": "tiny.mix", "chips": 1}, _tiny(), "unused",
                          1, 1.0, False, time.monotonic(), require_tpu=False)


def test_the_seeded_weights_make_every_departure_visible():
    """What ``gpt.init`` leaves trivial is seeded: the latents' norms, the
    selection bias and the hyper-connections' biases."""
    import jax

    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    plain = gpt.init(get_model_config("xing-test"), jax.random.PRNGKey(0))
    seeded = latent_runner.seeded_latent_params(plain, 3000000019)
    for stack in ("attn", "mlp", "moe"):
        hc = seeded["blocks"][stack]["hc"]
        assert float(abs(hc["b_pre"]).max()) > 0.1
        assert float(abs(hc["b_res"] - plain["blocks"][stack]["hc"][
            "b_res"]).max()) > 0.1
        assert float(abs(hc["norm"]["scale"]).max()) > 0.1
    assert float(abs(seeded["blocks"]["attn"]["kv_norm"]["scale"]).max()) > 0.1
    assert float(abs(seeded["blocks"]["moe"]["router"]["bias"]).max()) > 0
    assert seeded["blocks"]["moe"]["gate"] is plain["blocks"]["moe"]["gate"]
