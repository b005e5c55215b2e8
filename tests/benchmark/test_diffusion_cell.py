"""The block-diffusion cell (``sdar-30b-a3b-7l.diffusion-batch-64``): its
configuration, traffic, counts, readers and runner. The cell and its
metrics are found by NAME, never by their place in ``BENCHMARK.json``."""

import json
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import (diffusion_counters, end_to_end, flops,
                       flops_diffusion, layer_metrics, traffic as traffic_mod)
from benchmark.reference import diffusion_decoder
from benchmark.run import load_cell, result_line
from benchmark.runners import diffusion as runner
from manifest_pins import assert_lists, entry, listed_by

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "sdar-30b-a3b-7l.diffusion-batch-64"
CONFIG = json.loads(
    (ROOT / "benchmark/configs/sdar-30b-a3b-7l.json").read_text())
TRAFFIC = json.loads(
    (ROOT / "benchmark/traffic/diffusion-batch-64.json").read_text())
CATALOG = {     # the catalog row's ``config``, key for key
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
NEW_METRICS = {
    "diffusion.tokens_per_slot_forward": "tokens/forward",
    "diffusion.commit_forward_share": "%",
    "diffusion.masked_row_share": "%",
    "serve_programs.diffusion_forward_device_ms": "ms",
    "serve_programs.diffusion_forward_hbm_roofline_share": "%",
    "kernels.block_attention_ms_per_forward": "ms",
    "kernels.block_attention_hbm_roofline_share": "%",
    "kernels.diffusion_moe_gmm_ms_per_forward": "ms",
    "kernels.moe_gmm_hbm_roofline_share": "%",
    "kernels.block_attention_live_page_share": "%"}
LISTED = (
    "engine.decode_slot_utilization", "device_idle.serve",
    "engine.host_ms_per_decode_step", "engine.device_starved_share",
    "moe.experts_hit_share", "moe.expert_load_imbalance",
    "serve_programs.prefill_device_ms_per_ktok",
    "serve_programs.prefill_live_row_share", "startup.import_s",
    "startup.program_lowering_s", "startup.program_compile_s",
    "startup.programs", "startup.cache_misses", "startup.engine_work_s",
    "startup.unattributed_s")

TINY = {"name": "tiny-sdar", "model_type": "sdar_moe", "hidden_size": 64,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
        "max_position_embeddings": 512, "rope_theta": 1000000,
        "rms_norm_eps": 1e-6, "hidden_act": "silu",
        "tie_word_embeddings": False, "num_experts": 8,
        "num_experts_per_tok": 2, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [], "qk_norm": "head",
        "block_length": 4, "denoising_steps": 4, "mask_token_id": 300,
        "remasking_strategy": "low_confidence_dynamic",
        "confidence_threshold": 0.9,
        # the page is STATED: the rehearsal counts on a prompt of the pool
        # coming round again with one whole page cached, and the default page
        # follows the K/V row's bytes since PR 58 (128 tokens here, longer
        # than any of these prompts: no hit, no suffix program)
        "serve": {"dtype": "float32", "max_batch_size": 4,
                  "max_seq_len": 256, "kv_hbm_budget_gb": 0.01,
                  "kv_block_size": 64, "prefill_chunk": 64}}
TINY_TRAFFIC = {
    "kind": "diffusion-closed", "clients": 6, "pool_per_client": 50,
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 8, "max": 120},
    "output_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                      "min": 2, "max": 24},
    "sampling": {"temperature": 0.0, "ignore_eos": True}, "warmup_s": 0.5,
    "drain_s": 10.0, "shape_seed": 0}


# -- the manifest ----------------------------------------------------------------

def test_the_cell_and_its_configuration_are_in_the_manifest_by_name():
    cell = {c["name"]: c for c in MANIFEST["workloads"]}[CELL]
    assert cell["config"] == "sdar-30b-a3b-7l"
    assert cell["traffic"] == "diffusion-batch-64" and cell["chips"] == 1
    assert len(cell["why"]) <= 200
    entry = {c["name"]: c for c in MANIFEST["configs"]}[cell["config"]]
    assert entry["reduced"] == ["num_hidden_layers"] == list(CONFIG["reduced"])
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/sdar-30b-a3b-7l.json"
    spec = load_cell(CELL, MANIFEST)
    assert {m["name"] for m in spec["end_to_end"]} == {
        "tpot_p95_ms", "serve_tokens_per_s", "setup_s"}
    # the cell is judged by the accepted ``facts.window_requests``
    assert TRAFFIC["kind"].split("-")[0] == "diffusion"


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_metric_lists_this_cell_and_has_a_reader(name):
    metric = assert_lists(name, CELL, unit=NEW_METRICS[name],
                          moves="serve_tokens_per_s")
    if name.startswith(("diffusion.", "serve_programs.diffusion",
                        "kernels.block_attention", "kernels.diffusion")):
        assert metric["workloads"] == [CELL]    # the mechanism's own
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert callable(layer_metrics.load(metric["name"]).read)


@pytest.mark.parametrize("name", LISTED)
def test_the_accepted_readers_that_read_this_program_rightly_list_the_cell(
        name):
    assert_lists(name, CELL)


def test_what_the_cell_lists_moves_a_metric_the_cell_reports():
    reported = {m["name"] for m in load_cell(CELL, MANIFEST)["end_to_end"]}
    assert set(NEW_METRICS) | set(LISTED) <= listed_by(CELL)
    for name in listed_by(CELL):
        assert entry(name)["moves"] in reported, name


def test_accepted_readers_that_would_miscount_this_model_do_not_list_it():
    """A forward is a decode step in ``stats()``, but its kernels run
    under other names and its bytes are counted otherwise: the decode-step
    kernel times and the whole step's roofline share (no family function
    for them in ``families/diffusion.py``), the riding shares (this model
    refuses to ride) and the prefill stall (no
    ``llmctl.engine.prefill.wait`` span: nothing is fetched at a prefill,
    so a 0 there would say nothing of what queues behind one) may not list
    the cell. The routing counters' readers read this program as they
    read the others, and do (``LISTED``); the grouped matmuls' share of
    their roofline is one entry since PR 59, read through the family's own
    time a FORWARD and hits a forward."""
    for metric in MANIFEST["per_layer"]:
        if metric["name"].startswith((
                "kernels.paged_attention", "kernels.moe_gmm_ms",
                "kernels.ssm", "kernels.mla_", "kernels.kda", "ssm.", "kda.",
                "kv.", "residual.", "moe.held", "serve_programs.decode_",
                "engine.prefill_ride", "engine.prefill_state",
                "engine.prefill_stall")):
            assert CELL not in metric.get("workloads", []), metric["name"]


# -- the configuration and the traffic -------------------------------------------

def test_only_depth_is_cut_from_the_catalog_row():
    for key, value in CATALOG.items():
        if key == "num_hidden_layers":
            assert CONFIG[key] == 7
            assert CONFIG["reduced"][key]["published"] == value == 48
        else:
            assert CONFIG[key] == value, key
    assumed = {"qk_norm": "head", "block_length": 4, "denoising_steps": 4,
               "mask_token_id": 151669,
               "remasking_strategy": "low_confidence_dynamic",
               "confidence_threshold": 0.9}
    assert {k: CONFIG[k] for k in assumed} == assumed and CONFIG["assumed"]
    assert "pipeline of whole layers" in CONFIG["deployment"]
    assert CONFIG["serve"] == {"dtype": "bfloat16", "max_batch_size": 64,
                               "max_seq_len": 2048, "kv_hbm_budget_gb": 2.0}


def test_the_counts_of_the_configuration():
    assert flops_diffusion.layer_params(CONFIG) == 623_120_640
    assert flops_diffusion.attention_params(CONFIG) == 18_874_368
    assert flops_diffusion.expert_params(CONFIG) == 4_718_592
    assert flops_diffusion.total_params(CONFIG) == 4_984_176_384
    assert flops_diffusion.kv_bytes_per_token(CONFIG) == 14_336
    assert flops_diffusion.page_bytes(CONFIG, 1, 64) == 917_504
    # the program counts the same model
    from benchmark import harness
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    model = ModelConfig.from_dict(harness.model_dict(CONFIG))
    assert model.param_count == 4_984_176_384
    assert model.kv_bytes_per_token() == 14_336
    assert model.is_diffusion and model.qk_norm == "head"
    # a forward that hits every expert streams the issue's 9.35 GB
    moved = flops_diffusion.forward_bytes(CONFIG, 0, 64, 7 * 128)
    assert moved == pytest.approx(9.35e9, rel=0.01)
    assert flops_diffusion.forward_flops(CONFIG, 256) == pytest.approx(
        2 * 256 * (flops_diffusion.shared_matmul_params(CONFIG)
                   + 7 * 8 * 4_718_592))


def test_the_traffic_is_the_issues():
    assert TRAFFIC["clients"] == 128 and TRAFFIC["pool_per_client"] == 1
    assert TRAFFIC["prompt_tokens"] == {"dist": "lognormal", "median": 128,
                                        "sigma": 0.8, "min": 32, "max": 512}
    assert TRAFFIC["output_tokens"] == {"dist": "lognormal", "median": 384,
                                        "sigma": 0.4, "min": 128, "max": 768}
    assert TRAFFIC["sampling"] == {"temperature": 0.0, "ignore_eos": True}
    assert (TRAFFIC["warmup_s"], TRAFFIC["drain_s"]) == (10.0, 20.0)
    assert TRAFFIC["shared_prefix_tokens"] == 0 and TRAFFIC["shape_seed"] == 0
    closed = dict(TRAFFIC, kind="serve-closed")
    a, b = (traffic_mod.requests(closed, seed, 61.0, CONFIG["vocab_size"])
            for seed in (1, 2 ** 31 + 5))
    assert len(a) == 128
    assert sorted(r["max_tokens"] for r in a) == \
        sorted(r["max_tokens"] for r in b)
    assert max(len(r["prompt"]) + r["max_tokens"] for r in a) <= \
        CONFIG["serve"]["max_seq_len"]


# -- the readers on hand-made runs -----------------------------------------------

def _stats(**diffusion):
    return {"decode_steps": diffusion.get("forwards", 0),
            "padded_slot_steps": 0, "kv": {"page_size": 64},
            "diffusion": {"block_length": 4, **diffusion},
            "moe": {"choices": [0] * 128, "experts_hit": 0, "layer_steps": 0,
                    "decode_experts_hit": 0, "decode_layer_steps": 0}}


def _run():
    before = _stats(forwards=0, slot_forwards=0, commit_slot_forwards=0,
                    masked_rows=0, tokens_fixed=0, live_pages=0)
    after = _stats(forwards=400, slot_forwards=25_000,
                   commit_slot_forwards=5_000, masked_rows=50_000,
                   tokens_fixed=20_000, live_pages=80_000)
    after["moe"].update(experts_hit=7 * 400 * 120, layer_steps=7 * 400,
                        decode_experts_hit=7 * 400 * 120,
                        decode_layer_steps=7 * 400)
    return {"config": CONFIG, "device": {"kind": "TPU v5e"},
            "runner": "diffusion",
            "serve_cfg": {"decode_steps_per_dispatch": 8,
                          "max_batch_size": 64},
            "stats": {"before": before, "after": after},
            "trace_stats": {"before": before, "after": after},
            "trace": {"programs": {"decode": (50, 5.6), "prefill": (20, 0.4)},
                      "program_scope_s": {
                          "decode": {"paged_attention_blk": (2800, 0.28),
                                     "moe_gmm_prefill": (8400, 4.4)},
                          "prefill": {"moe_gmm_prefill": (420, 0.2)}}}}


def test_the_readers_on_a_hand_made_run():
    run = _run()

    def read(name):
        return layer_metrics.load(name).read(run)
    assert read("diffusion.tokens_per_slot_forward") == pytest.approx(0.8)
    assert read("diffusion.commit_forward_share") == pytest.approx(20.0)
    assert read("diffusion.masked_row_share") == pytest.approx(50.0)
    # 50 executions x 8 forwards
    assert read("serve_programs.diffusion_forward_device_ms") == \
        pytest.approx(14.0)
    assert read("kernels.block_attention_ms_per_forward") == \
        pytest.approx(0.7)
    # the decode program's grouped matmuls alone, not the prefill's
    assert read("kernels.diffusion_moe_gmm_ms_per_forward") == \
        pytest.approx(11.0)
    # the accepted routing reader on this program's counters
    assert read("moe.experts_hit_share") == pytest.approx(93.75)
    # 200 pages a forward of 64 slots x 32 pages
    assert read("kernels.block_attention_live_page_share") == \
        pytest.approx(100 * 200 / 2048)
    peak = flops.peaks("TPU v5e")["hbm_bytes_per_s"]
    pages = flops_diffusion.page_bytes(CONFIG, 200, 64)
    assert read("kernels.block_attention_hbm_roofline_share") == \
        pytest.approx(100 * pages / peak / 0.7e-3)
    experts = flops_diffusion.expert_bytes(CONFIG, 7 * 120)
    assert read("kernels.moe_gmm_hbm_roofline_share") == \
        pytest.approx(100 * experts / peak / 11e-3)
    whole = flops_diffusion.forward_bytes(CONFIG, 200, 64, 7 * 120)
    share = read("serve_programs.diffusion_forward_hbm_roofline_share")
    assert share == pytest.approx(100 * whole / peak / 14e-3)
    assert 0 < share < 100


def test_a_program_without_the_mechanism_leaves_every_new_metric_out():
    """The parent commit has no ``diffusion`` counters and no such scopes:
    every reader returns None and raises nothing."""
    run = _run()
    for pair in (run["stats"], run["trace_stats"]):
        for snap in pair.values():
            snap.pop("diffusion", None), snap.pop("moe", None)
    run["trace"] = {"programs": {}}
    for name in NEW_METRICS:
        assert layer_metrics.load(name).read(run) is None, name
    run["trace"] = {}
    for name in NEW_METRICS:
        assert layer_metrics.load(name).read(run) is None, name


def test_seconds_by_program_and_scope():
    op_s = {"decode": {"paged_attention_blk.7": (7, 0.07),
                       "moe_gmm_prefill.12": (21, 0.5), "fusion.3": (1, 0.1),
                       "fusion.9": (1, 0.2), "fusion.11": (1, 0.05)},
            "prefill": {"moe_gmm_prefill.40": (3, 0.3)}}
    texts = {"_decode_impl_n": "\n".join([
        '  %fusion.3 = f32[256,151936]{1,0} fusion(%a), kind=kLoop, '
        'metadata={op_name="jit(f)/denoise_step/dot_general"}',
        '  %fusion.9 = s32[64,4]{1,0} fusion(%b), kind=kLoop, '
        'metadata={op_name="jit(f)/unmask/select_n"}',
        '  %fusion.11 = bf16[256,2048]{1,0} fusion(%c), kind=kLoop, '
        'metadata={op_name="jit(f)/denoise_step/mul"}'])}
    got = runner.program_scope_seconds(op_s, texts, 151936)
    assert got["decode"] == {"paged_attention_blk": (7, 0.07),
                             "moe_gmm_prefill": (21, 0.5),
                             "vocab_rows": (1, 0.1), "unmask": (1, 0.2),
                             "other": (1, 0.05)}
    assert got["prefill"] == {"moe_gmm_prefill": (3, 0.3)}


def test_the_trajectory_a_reply_describes():
    prompt, tokens, steps = [11, 12, 13, 14, 15, 16], [21, 22, 23, 24, 25, 26], \
        [1, 0, 2, 0, 3, 1]
    got = list(runner.windows_of(prompt, tokens, steps, 4, 99))
    # the first window holds the prompt's last two tokens as fixed rows
    assert got[0] == ([11, 12, 13, 14, 15, 16, 99, 99], 4, [3], [2, 3])
    assert got[1] == ([11, 12, 13, 14, 15, 16, 99, 22], 4, [2], [2])
    # the second block starts from masks over the finished first
    assert got[2] == ([11, 12, 13, 14, 15, 16, 21, 22, 99, 99, 99, 99], 8,
                      [1], [0, 1, 2, 3])
    assert [fixed for _, _, fixed, _ in got[2:]] == [[1], [3], [0], [2]]
    assert len(got) == 6
    with pytest.raises(AssertionError, match="end on a block"):
        list(runner.windows_of(prompt, tokens[:5], steps[:5], 4, 99))
    # a reply cut inside its last block: the blocks asked for alone
    cut = list(runner.windows_of(prompt, tokens[:5], steps[:5], 4, 99,
                                 starts=[4]))
    assert cut == got[:2]


@pytest.mark.parametrize("prompt_len,reply_len,count,want", [
    (6, 6, 4, [4, 8]),              # two blocks: both, once
    (6, 5, 4, [4]),                 # cut at max_tokens inside the second
    (8, 40, 4, [8, 20, 32, 44]),    # first, last whole, two between
    (9, 40, 4, [8, 20, 32, 44]),    # the block the prompt ends in is first
    (10, 41, 1, [8]), (3, 0, 4, []), (64, 768, 4, [64, 320, 572, 828])])
def test_the_blocks_the_check_follows(prompt_len, reply_len, count, want):
    assert runner.block_starts(prompt_len, reply_len, 4, count) == want


def _window_of_records(monkeypatch, records):
    """A ``Served`` that never served, holding hand-made ended requests
    (slot, prompt tokens, reply tokens, cached), all inside the window in
    the order given."""
    monkeypatch.setattr(runner, "CHECK_REQUESTS", 4)
    monkeypatch.setattr(runner, "CHECK_LONGEST", 1)
    served = object.__new__(runner.Served)
    served.config = TINY
    served.served = {
        i: (slot, [7] * n, [8] * m, [0] * m, cached)
        for i, (slot, n, m, cached) in enumerate(records)}
    raw = {"window": (0.0, 100.0), "stamps": {"kind": "serve-closed", "records": [
        {"id": i, "sent": 1.0, "done": 2.0 + i, "error": None, "status": 200,
         "chunks": [1.5]} for i in served.served]}}
    raw["stamps"]["records"].append(       # ended after the window's close
        {"id": 0, "sent": 1.0, "done": 101.0, "error": None, "status": 200,
         "chunks": [1.5]})
    return served, raw


def test_the_sample_is_one_request_a_slot_the_longest_and_hits_first(
        monkeypatch):
    served, raw = _window_of_records(monkeypatch, [
        (0, 40, 16, 0), (0, 70, 16, 64), (1, 40, 16, 0), (2, 70, 16, 64),
        (2, 70, 16, 64), (3, 90, 16, 64), (4, 30, 16, 0), (5, 30, 2, 0)])
    sample = served.window_sample(raw)
    # the longest, then a hit, then cold requests, each of another slot;
    # a reply with no whole block (slot 5) is never sampled
    assert [(s[0], s[4]) for s in sample] == [(3, 64), (0, 64), (1, 0),
                                              (4, 0)]
    # too few slots: a second request of a slot rather than a short sample
    served, raw = _window_of_records(monkeypatch, [
        (0, 40, 16, 0), (0, 70, 16, 64), (1, 40, 16, 0), (1, 41, 16, 0),
        (1, 42, 16, 0)])
    sample = served.window_sample(raw)
    assert [(s[0], len(s[1])) for s in sample] == [(0, 70), (1, 40), (0, 40),
                                                   (1, 41)]


# -- the reference and the runner, small, on the CPU -----------------------------

def test_the_benchmarks_reference_is_the_programs_model():
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    cfg = ModelConfig.from_dict(dict(harness.model_dict(TINY),
                                     dtype="float32"))
    params = gpt.init(cfg, jax.random.PRNGKey(3))
    tokens = np.random.default_rng(0).integers(258, 512, 32)
    tokens[29:] = TINY["mask_token_id"]
    want = np.asarray(diffusion_decoder.logits(params, tokens, TINY))
    got = np.asarray(gpt.forward(params, jnp.asarray(tokens[None]), cfg))[0]
    assert np.abs(got - want).max() < 1e-4
    some = np.asarray(diffusion_decoder.logits(params, tokens, TINY,
                                               positions=[5, 31]))
    np.testing.assert_allclose(some, want[[5, 31]], atol=1e-6)
    # masks appended after a block's end change nothing before it
    padded = np.concatenate([tokens, [TINY["mask_token_id"]] * 8])
    more = np.asarray(diffusion_decoder.logits(params, padded, TINY,
                                               positions=range(32)))
    np.testing.assert_allclose(more, want, atol=1e-6)
    for wrong in ({"mask_block": 1}, {"operand_bits": runner.FLOAT8}):
        other = np.asarray(diffusion_decoder.logits(params, tokens, TINY,
                                                    **wrong))
        assert np.abs(other - want).max() > 1e-3, wrong


def test_diffusion_runner_rehearsal(tmp_path, monkeypatch):
    from distributed_llm_training_and_inference_system_tpu.utils import platform
    monkeypatch.setattr(platform, "enable_compile_cache", lambda: None)
    # 4 slots, a window of seconds, prompts that never come round again
    monkeypatch.setattr(runner, "CHECK_REQUESTS", 4)
    monkeypatch.setattr(runner, "CHECK_LONGEST", 1)
    monkeypatch.setattr(runner, "CHECK_BLOCKS", 2)
    monkeypatch.setattr(runner, "CHECK_PREFIX_HITS", 0)
    monkeypatch.setattr(runner, "CHECK_ROUND_TO", 64)
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(TINY_TRAFFIC))
    cell = {"name": "tiny.mix", "chips": 1}
    run = runner.run(cell, TINY, str(path), 3000000019, 4.0, False,
                     time.monotonic(), require_tpu=False)
    run["runner"] = "diffusion"     # as run.py stamps it
    assert run["kind"] == "serve" and run["stamps"]["kind"] == "serve-closed"
    check = run["check"]
    assert check["ok"] and run["compiled_in_window"] == 0
    # what the WINDOW served, each request from another slot
    assert check["requests"] == check["slots"] == 4
    # float32 against float32: the server's tokens ARE the reference's,
    # but for a routing near-tie (``gpt.init``'s router is a coin toss
    # between its 2nd and 3rd expert, and an expert swapped moves a logit
    # by 1e-3 of these logits' 0.16: a runner-up 0.003 std down)
    assert check["tokens_off_the_reference_argmax"] <= 2
    assert check["worst_token_gap_std"] < 0.01
    # (two masked rows of one window differ by position alone: float32
    # sums in another order may flip a tie, by next to nothing)
    assert check["worst_row_gap_std"] < 0.01
    assert 4 <= check["tokens"] <= 4 * 2 * 4 and check["steps"] >= 4
    line = result_line(run, load_cell(CELL, MANIFEST)["end_to_end"],
                       end_to_end.load, traced=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s",
                                    "serve_tokens_per_s"}
    assert line["device"]["platform"] == "cpu"      # and so never a result
    assert json.loads(json.dumps(line))["check"]["ok"]
    # the pool's prompts come round again: the suffix program of one page
    # compiled in the warm-up, not in the window
    assert run["stats"]["before"]["compiled_programs"][
        "prefill_extend_buckets"] == 1
    # the counters are read on the CPU too; the trace's readers say nothing
    traced = result_line(run, load_cell(CELL, MANIFEST)["per_layer"],
                         layer_metrics.load, traced=True)
    assert {"diffusion.tokens_per_slot_forward",
            "diffusion.commit_forward_share", "diffusion.masked_row_share",
            "moe.experts_hit_share", "moe.expert_load_imbalance",
            "kernels.block_attention_live_page_share",
            "engine.decode_slot_utilization",
            "serve_programs.prefill_live_row_share"} <= set(traced["metrics"])
    # (what reads the device trace finds none on the CPU)
    assert not {n for n in NEW_METRICS if n.endswith((
        "_ms_per_forward", "_device_ms", "_roofline_share"))} \
        & set(traced["metrics"])
    # ``block_length`` tokens in ``denoising_steps`` forwards since PR 47
    # (a block's commit rides the next block's first forward): 1.0 at 4 and
    # 4, where the schedule with a forward for the commit alone gave 0.8
    assert 0 < traced["metrics"]["diffusion.tokens_per_slot_forward"][
        "value"] <= 1.0
    assert diffusion_counters.block_length(run) == 4


def test_a_program_that_cannot_build_the_model_is_refused(monkeypatch):
    """The parent commit refuses ``qk_norm: head`` by name, and a program
    that read the file as an autoregressive MoE would be measured as
    something it is not: both leave at once with a reason."""
    runner.require_diffusion_support(TINY)
    from benchmark import harness
    from distributed_llm_training_and_inference_system_tpu.config import schema

    def old(d):
        raise schema.ConfigError("qk_norm must be none|projection")
    monkeypatch.setattr(schema.ModelConfig, "from_dict", staticmethod(old))
    with pytest.raises(SystemExit, match="cannot read tiny-sdar"):
        runner.require_diffusion_support(TINY)
    monkeypatch.undo()
    real = schema.ModelConfig.from_dict

    def autoregressive(d):
        return real({k: v for k, v in d.items()
                     if k not in ("model_type", "block_length")})
    monkeypatch.setattr(schema.ModelConfig, "from_dict",
                        staticmethod(autoregressive))
    with pytest.raises(SystemExit, match="cannot run this cell"):
        runner.require_diffusion_support(TINY)
    assert harness.model_dict(TINY)["name"] == "tiny-sdar"
