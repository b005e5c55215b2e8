"""Tests of the benchmark itself (BENCHMARK.json, benchmark/): the manifest
against its files, the traffic generator, the metric arithmetic on hand-made
cases, the trace reducer on a synthetic trace, the plain reference against
the program at a tiny size, and a tiny-size CPU rehearsal of each runner's
control flow. Nothing here describes a topology or loads libtpu; no number
from these tests is a device metric.
"""

import json
import re
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import (end_to_end, facts, flops, harness, layer_metrics,
                       stats, trace_reduce, traffic)
from benchmark.reference import dense_decoder
from benchmark.run import load_cell, result_line
from benchmark.runners import serve as serve_runner
from benchmark.runners import train as train_runner

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [c["name"] for c in MANIFEST["workloads"]]

TINY = {"name": "tiny", "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
        "max_position_embeddings": 512, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5, "hidden_act": "silu",
        "tie_word_embeddings": False,
        "serve": {"dtype": "float32", "max_batch_size": 4,
                  "max_seq_len": 256, "kv_hbm_budget_gb": 0.01,
                  "prefill_chunk": 64},
        "train": {"optimizer": {"type": "adamw", "moment_dtype": "bfloat16",
                                "nu_dtype": "bfloat16"},
                  "parallel": {"activation_checkpoint": "selective"}}}
TINY_TRAFFIC = {
    "arrivals": {"rate_per_s": 5.0, "cv": 1.0}, "clients": 3,
    # a pool that never cycles: a repeated prompt would hit the prefix cache
    "pool_per_client": 200,
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 8, "max": 120},
    "output_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                      "min": 2, "max": 24},
    "sampling": {"temperature": 0.0}, "warmup_s": 0.5, "drain_s": 10.0,
    "shape_seed": 0}


# -- the manifest against its files -------------------------------------------

def test_manifest_names_units_and_files():
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = ([m["name"] for m in metrics] + CELLS
             + [c["name"] for c in MANIFEST["configs"]])
    assert len(set(names)) == len(names)
    for n in names + [c["traffic"] for c in MANIFEST["workloads"]]:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in (
            "host_clock", "device_trace")
        end_to_end.load(m["name"])          # its reader exists
    for m in MANIFEST["per_layer"]:
        layer_metrics.load(m["name"])
    for cfg in MANIFEST["configs"]:
        assert (ROOT / cfg["file"]).is_file()
        assert any(w["config"] == cfg["name"] for w in MANIFEST["workloads"])
    for cell in CELLS:
        assert Path(load_cell(cell, MANIFEST)["traffic_path"]).is_file()


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_what_its_layer_metrics_move(cell):
    spec = load_cell(cell, MANIFEST)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and spec["per_layer"]
    for m in spec["per_layer"]:
        assert m["moves"] in e2e, (cell, m["name"], m["moves"])


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [c for c in MANIFEST["workloads"] if c["chips"] == 4]
    assert all(c["chips"] in (1, 4) for c in MANIFEST["workloads"])
    assert len(four) <= max(len(CELLS) // 4, 1)


@pytest.mark.parametrize("name,published", [
    ("mistral-7b-16l", dict(hidden_size=4096, intermediate_size=14336,
                            num_attention_heads=32, num_key_value_heads=8,
                            head_dim=128, vocab_size=32000,
                            rope_theta=1e6, rms_norm_eps=1e-5)),
    ("internlm2-1.8b-6l", dict(hidden_size=2048, intermediate_size=8192,
                               num_attention_heads=16, num_key_value_heads=8,
                               head_dim=128, vocab_size=92544,
                               rope_theta=1e6, rms_norm_eps=1e-5)),
])
def test_only_depth_is_cut(name, published):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == name)
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert entry["reduced"] == ["num_hidden_layers"] == list(cfg["reduced"])
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["source"] == entry["source"] and cfg["assumed"]


# -- traffic ---------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["serve-open", "serve-closed"])
def test_traffic_is_the_same_set_in_another_order_for_every_seed(kind):
    mix = dict(TINY_TRAFFIC, kind=kind)
    a = traffic.requests(mix, 3000000019, 30.0, 512)
    again = traffic.requests(mix, 3000000019, 30.0, 512)
    b = traffic.requests(mix, 7, 30.0, 512)
    assert a == again and a != b
    assert sorted(len(r["prompt"]) for r in a) == sorted(
        len(r["prompt"]) for r in b)
    assert sorted(r["max_tokens"] for r in a) == sorted(
        r["max_tokens"] for r in b)
    if kind == "serve-open":
        gaps = lambda reqs: sorted(np.round(np.diff(
            [0.0] + [r["due"] for r in reqs]), 5))
        assert gaps(a) == gaps(b) and len(a) == 150
        assert 0 < a[0]["due"] and a[-1]["due"] < 30.0
    assert all(258 <= t < 512 for r in a for t in r["prompt"])


def test_traffic_reports_the_drawn_distribution():
    mix = json.loads((ROOT / "benchmark/traffic/chat.json").read_text())
    d = traffic.describe(traffic.requests(mix, 1, 45.0, 32000))
    assert d["prompt_tokens"]["min"] >= 32 and d["prompt_tokens"]["max"] <= 1024
    assert 180 <= d["prompt_tokens"]["p50"] <= 340
    assert 16 <= d["output_tokens"]["min"] and d["output_tokens"]["max"] <= 384
    assert abs(d["gap_s"]["sum"] - 45.0) < 1.0
    bursty = dict(mix, arrivals=dict(mix["arrivals"], cv=3.0))
    g = np.diff([r["due"] for r in traffic.requests(bursty, 1, 45.0, 32000)])
    assert g.std() / g.mean() > 1.8


# -- arithmetic on hand-made cases ------------------------------------------------

@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 95, 95.05), ([7.0], 95, 7.0),
    ([1, 2, float("inf")], 95, float("inf")),
])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)
    if np.isfinite(want):
        assert want == pytest.approx(np.percentile(values, q))


def test_spread_is_the_contracts():
    vals = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q = np.percentile(vals, [25, 75], method="weibull")
    assert stats.spread(vals) == pytest.approx((q[1] - q[0]) / 10.05)


def test_interval_union_and_subtraction():
    iv = [(0, 2), (1, 3), (5, 6), (6, 6), (5.5, 5.8)]
    assert stats.merge_intervals(iv) == [(0, 3), (5, 6)]
    assert stats.union_length(iv) == pytest.approx(4.0)
    assert stats.subtract_length([(0, 10)], [(1, 2), (4, 6), (9, 12)]
                                 ) == pytest.approx(6.0)
    assert stats.subtract_length([(0, 1), (2, 3)], []) == pytest.approx(2.0)


def _stamped_run():
    def rec(i, due, sent, chunks, tokens, sizes, error=None, max_tokens=None):
        return {"i": i, "due": due, "sent": sent, "status": 200, "id": str(i),
                "chunks": chunks, "done": chunks[-1] + 0.001 if chunks else
                None, "finish_reason": "length", "error": error,
                "prompt_tokens": 100, "max_tokens": max_tokens or tokens,
                "tokens": tokens, "batch_sizes": sizes,
                "engine_finish_reason": "length"}
    records = [
        rec(0, 9.0, 9.0, [9.1, 9.2], 9, [1, 8]),            # before the window
        rec(1, 10.0, 10.002, [10.1, 10.3, 10.5], 17, [1, 8, 8]),
        rec(2, 11.0, 11.004, [11.2, 11.6], 9, [1, 8]),
        rec(3, 12.0, 12.05, [], None, [], error="refused"),  # failed
        rec(4, 19.5, 19.5, [19.9, 20.4], 9, [1, 8]),         # ends after it
    ]
    return {"window": (10.0, 20.0), "traffic": {"drain_s": 20.0},
            "stamps": {"kind": "serve-open", "records": records},
            "check": {"ok": True}}


def test_ttft_tpot_lateness_and_tokens_from_hand_made_stamps():
    run = _stamped_run()
    assert [r["i"] for r in facts.window_requests(run)] == [1, 2, 3, 4]
    assert len(facts.failed_requests(run)) == 1
    assert facts.ttft_ms(run) == pytest.approx([100, 200, 28000, 400])
    # (10.5-10.1)/16, (11.6-11.2)/8, the failed one at its worst, (20.4-19.9)/8
    assert facts.tpot_ms(run) == pytest.approx([25, 50, 28000, 62.5])
    late = layer_metrics.load("loadgen.lateness_p95_ms").read(run)
    assert late == pytest.approx(stats.percentile([2, 4, 50, 0], 95))
    # the chunk of request 4 stamped at 20.4 lies outside the window
    assert facts.tokens_in_window(run) == 17 + 9 + 1
    assert end_to_end.load("serve_tokens_per_s").read(run) == pytest.approx(2.7)
    assert facts.serve_correct(run)
    run["stamps"]["records"][1]["tokens"] = 12      # short, no stop reason
    run["stamps"]["records"][1]["max_tokens"] = 17
    assert not facts.serve_correct(run)
    run["stamps"]["records"][1]["engine_finish_reason"] = "stop"
    assert facts.serve_correct(run)
    # request 2 alone is live over [11.2, 11.6]: 100 prompt tokens + half of 9
    assert facts.live_kv_tokens(run, 11.2, 11.6) == pytest.approx(104.5)


def test_trace_reducer_on_a_synthetic_trace():
    names = trace_reduce.NAMES
    modules = [("jit_prefill(1)", 0.0, 1.0), ("jit__decode_impl_n(2)", 1.5, 3.5),
               ("jit__decode_impl_n(2)", 4.0, 6.0), ("jit_other(9)", 6.0, 6.5)]
    ops = [("fusion.1", 0.0, 1.0),
           ("while.2", 1.5, 3.5), ("fusion.3", 1.5, 2.5),
           ("all-gather.4", 2.5, 3.25), ("fusion.5", 3.25, 3.5),
           ("while.2", 4.0, 6.0), ("fusion.3", 4.0, 6.0),
           ("copy.7", 6.0, 6.5)]
    red = trace_reduce.reduce({"/device:TPU:0": {"modules": modules,
                                                 "ops": ops}}, window_s=8.0)
    assert red["programs"]["decode"] == (2, pytest.approx(4.0))
    assert red["programs"]["prefill"] == (1, pytest.approx(1.0))
    assert red["programs"]["jit_other"] == (1, pytest.approx(0.5))
    assert red["busy_s"] == pytest.approx(5.5)       # union, not the sum
    assert red["trace_span_s"] == pytest.approx(6.5)
    # the while is a container: its body's operations are what ran
    assert red["device_ops"][0] == ["fusion.3", pytest.approx(3.0)]
    assert "while.2" not in [n for n, _ in red["device_ops"]]
    assert dict(map(tuple, red["idle_gaps"])) == {
        "prefill->decode": pytest.approx(0.5),
        "decode->decode": pytest.approx(0.5)}
    # the core runs one operation at a time: while it sits in the
    # all-gather nothing else does
    assert red["exposed_collective_s"] == pytest.approx(0.75)
    assert trace_reduce.reduce({}, 1.0) == {}
    run = {"trace": red, "serve_cfg": {"decode_steps_per_dispatch": 8},
           "chips": 1}
    step = layer_metrics.load("serve_programs.decode_step_device_ms").read(run)
    assert step == pytest.approx(4000.0 / 16)
    assert layer_metrics.load("device_idle.serve").read(run) == pytest.approx(
        100 * (1 - 5.5 / 8.0))
    assert layer_metrics.load("device_idle.serve").read({"trace": {}}) is None
    assert names["programs"]["decode"]


def test_operations_and_bytes_from_shapes():
    cfg = json.loads((ROOT / "benchmark/configs/mistral-7b-16l.json").read_text())
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert flops.matmul_params(cfg) == 16 * per_layer + 4096 * 32000
    assert flops.total_params(cfg) == (16 * per_layer + 2 * 4096 * 32000
                                       + 33 * 4096)
    assert flops.kv_bytes_per_token(cfg) == 64 * 1024
    assert flops.decode_step_bytes(cfg, 10000) == (
        2 * flops.matmul_params(cfg) + 10000 * 65536)
    assert flops.train_flops_per_token(cfg, 4096) == (
        6.0 * flops.matmul_params(cfg) + 12.0 * 16 * 32 * 128 * 4096)
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v9")


# -- the plain reference against the program ------------------------------------

def test_reference_agrees_with_the_program_on_logits_and_loss():
    import jax
    import jax.numpy as jnp
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    from distributed_llm_training_and_inference_system_tpu.models.loss import (
        next_token_loss)
    cfg = ModelConfig.from_dict(dict(harness.model_dict(TINY),
                                     dtype="float32"))
    params = gpt.init(cfg, jax.random.PRNGKey(3))
    # non-trivial norm weights, so that a dropped (1 + scale) would show
    params["blocks"]["attn_norm"]["scale"] += 0.3
    params["final_norm"]["scale"] -= 0.2
    tokens = np.random.default_rng(0).integers(1, 512, (2, 48))
    with jax.default_matmul_precision("highest"):
        want = gpt.forward(params, jnp.asarray(tokens), cfg)
        want_loss, _ = next_token_loss(want, jnp.asarray(tokens))
    got = np.stack([dense_decoder.logits(params, row, TINY)
                    for row in tokens])
    # float32 against float32: only the order of sums differs
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)
    picked = dense_decoder.logits(params, tokens[0], TINY, positions=[5, 47])
    np.testing.assert_allclose(picked, got[0][[5, 47]], atol=1e-6)
    total, count = zip(*(dense_decoder.next_token_loss(params, row, TINY,
                                                       block=16)
                         for row in tokens))
    assert sum(total) / sum(count) == pytest.approx(float(want_loss), abs=1e-5)
    # tight enough that a wrong rope base fails
    wrong = np.asarray(dense_decoder.logits(
        params, tokens[0], dict(TINY, rope_theta=1e6)))
    assert np.abs(wrong - got[0]).max() > 1e-3


# -- rehearsals of the runners' control flow, tiny, on the CPU ----------------

def _no_compile_cache(monkeypatch):
    from distributed_llm_training_and_inference_system_tpu.utils import platform
    monkeypatch.setattr(platform, "enable_compile_cache", lambda: None)


def test_no_accelerator_is_an_error_not_a_cpu_number(monkeypatch):
    _no_compile_cache(monkeypatch)
    with pytest.raises(harness.NoAccelerator):
        harness.start(1, require_tpu=True)


@pytest.fixture(scope="module")
def tiny_server():
    """One tiny server for both serving rehearsals: its programs compile
    once, and the test file stays light beside five other workers."""
    device = harness.start(1, require_tpu=False)
    served = serve_runner.Served(TINY, 3000000019)
    yield served, device
    served.close()


@pytest.mark.parametrize("kind,seconds", [("serve-open", 2.0),
                                          # a closed loop counts requests that
                                          # start AND end inside the window
                                          ("serve-closed", 4.0)])
def test_serve_runner_rehearsal(tmp_path, tiny_server, kind, seconds):
    served, device = tiny_server
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(dict(TINY_TRAFFIC, kind=kind)))
    cell = {"name": "tiny.mix", "chips": 1}
    run = serve_runner.measure(served, cell, str(path), 3000000019, seconds,
                               False, time.monotonic(), device)
    assert run["check"]["ok"] and run["compiled_in_window"] == 0
    line = result_line(run, [m for m in MANIFEST["end_to_end"]
                             if m["name"] in ("tpot_p95_ms", "setup_s",
                                              "serve_tokens_per_s")],
                       end_to_end.load, traced=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s",
                                    "serve_tokens_per_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"      # and so never a result
    # with no device trace the trace readers find nothing and say nothing
    traced = result_line(run, [m for m in MANIFEST["per_layer"] if m["name"]
                               in ("loadgen.lateness_p95_ms",
                                   "engine.ttft_p95_ms",
                                   "serve_programs.decode_step_device_ms",
                                   "device_idle.serve")],
                         layer_metrics.load, traced=True)
    assert set(traced["metrics"]) == {"engine.ttft_p95_ms"} | (
        # a closed loop has no due times
        {"loadgen.lateness_p95_ms"} if kind == "serve-open" else set())
    assert "breakdown" not in traced
    util = layer_metrics.load("engine.decode_slot_utilization").read(run)
    assert 0 < util <= 100


def test_train_runner_rehearsal(tmp_path):
    path = tmp_path / "job.json"
    path.write_text(json.dumps({"kind": "train", "seq_len": 128,
                                "micro_batch": 2, "accumulation": 2,
                                "data_shards": 1, "fence_every": 2}))
    cell = {"name": "tiny.job", "chips": 1}
    run = train_runner.run(cell, TINY, str(path), 3000000019, 1.5, False,
                           time.monotonic(), require_tpu=False)
    assert run["check"]["ok"] and run["all_finite"]
    assert abs(run["check"]["first_loss"] - np.log(512)) < 0.2
    assert run["tokens_per_step"] == 4 * 128 and len(run["blocks"]) >= 2
    assert len(run["losses"]) == 2 * len(run["blocks"])
    line = result_line(run, [m for m in MANIFEST["end_to_end"]
                             if m["name"] in ("setup_s",
                                              "train_tokens_per_s_per_chip")],
                       end_to_end.load, traced=False)
    assert line["correct"] and line["attempted"] == len(run["losses"])
    assert line["metrics"]["train_tokens_per_s_per_chip"]["value"] > 0
    assert layer_metrics.load("input.data_wait_ms").read(run) >= 0
    assert layer_metrics.load("collectives.exposed_share").read(run) is None
