"""The program's own spans and counters (metrics/spans.py): the recorder by
itself, through a tiny engine, in a CPU profile, and as `llmctl trace
summarize` reduces them. No number here is a device metric."""

import json
import re
import time

import jax
import jax.numpy as jnp
import pytest

from benchmark import trace_reduce
from distributed_llm_training_and_inference_system_tpu.cli.commands import (
    trace as trace_cli)
from distributed_llm_training_and_inference_system_tpu.config.presets import (
    get_model_config)
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ServeConfig)
from distributed_llm_training_and_inference_system_tpu.metrics.spans import (
    QUEUE_WAIT_LE_MS, QueueWaitHistogram, SpanRecorder)
from distributed_llm_training_and_inference_system_tpu.serve.engine import (
    InferenceEngine)
from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
    SamplingParams)

TICK = 0.02


def _nested(rec):
    with rec.phase("outer"):
        time.sleep(TICK)
        with rec.phase("inner", request_id="r1"):
            time.sleep(2 * TICK)
        time.sleep(TICK)
    return {"outer": (2 * TICK, 1), "inner": (2 * TICK, 1)}


def _siblings(rec):
    with rec.phase("outer"):
        for _ in range(3):
            with rec.phase("inner"):
                time.sleep(TICK)
    return {"outer": (0.0, 1), "inner": (3 * TICK, 3)}


def _raising(rec):
    with pytest.raises(ValueError):
        with rec.phase("outer"):
            with rec.phase("inner"):
                time.sleep(TICK)
                raise ValueError("inside")
    with rec.phase("outer"):           # the stack is empty again
        time.sleep(TICK)
    return {"outer": (TICK, 2), "inner": (TICK, 1)}


@pytest.mark.parametrize("case", [_nested, _siblings, _raising])
def test_a_spans_counted_time_is_its_self_time(case):
    rec = SpanRecorder()
    want = case(rec)
    assert not rec._stack
    got = rec.snapshot()["phases"]
    for name, (seconds, calls) in want.items():
        assert got[name]["n"] == calls
        assert seconds <= got[name]["s"] < seconds + TICK, (name, got)


def test_an_open_span_counts_up_to_the_snapshot():
    rec = SpanRecorder()
    with rec.phase("outer"):
        time.sleep(TICK)
        with rec.phase("inner"):
            time.sleep(TICK)
            mid = rec.snapshot()
    end = rec.snapshot()
    for name in ("outer", "inner"):
        assert mid["phases"][name]["n"] == 0
        assert TICK <= mid["phases"][name]["s"] <= end["phases"][name]["s"]
    assert end["clock_s"] >= mid["clock_s"]


def test_a_foreign_thread_gets_the_bare_annotation():
    import threading
    rec = SpanRecorder()

    def other():
        with rec.phase("llmctl.engine.deliver"):
            pass
    t = threading.Thread(target=other)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive() and rec.snapshot()["phases"] == {}


@pytest.mark.parametrize("busy,in_flight,starved", [
    (True, 0, True), (True, 1, False), (False, 0, False), (True, 2, False)])
def test_starved_only_while_busy_and_nothing_in_flight(busy, in_flight,
                                                       starved):
    rec = SpanRecorder()
    rec.set_busy(busy)
    for _ in range(in_flight):
        rec.dispatched()
    time.sleep(TICK)
    for _ in range(in_flight):
        rec.fetched()
    rec.set_busy(False)
    got = rec.snapshot()["starved_s"]
    assert (got >= TICK) if starved else (got < TICK / 4), got
    assert rec.in_flight == 0


def test_starved_stops_at_the_dispatch_and_resumes_at_the_fetch():
    now = [50.0]                          # a clock the test steps: no sleep
    rec = SpanRecorder(clock=lambda: now[0])
    rec.set_busy(True)
    now[0] += TICK                        # starved
    rec.dispatched()
    now[0] += 3 * TICK                    # a program runs
    rec.fetched()
    now[0] += TICK                        # starved again, still open
    snap = rec.snapshot()
    assert snap["starved_s"] == pytest.approx(2 * TICK)
    assert snap["clock_s"] == now[0]
    rec.dispatched()
    rec.reset_in_flight()                 # fail_all: nothing will be fetched
    assert rec.in_flight == 0


@pytest.mark.parametrize("ms,bucket", [(0.5, 0), (1.0, 0), (1.5, 1),
                                       (499.0, 11), (5000.0, 16),
                                       (60000.0, 17)])
def test_queue_wait_histogram_buckets(ms, bucket):
    h = QueueWaitHistogram()
    h.observe(ms)
    snap = h.snapshot()
    assert snap["counts"][bucket] == 1 and sum(snap["counts"]) == 1
    assert snap["n"] == 1 and snap["sum"] == ms
    assert snap["le"][:-1] == list(QUEUE_WAIT_LE_MS)
    assert len(snap["le"]) == len(snap["counts"])
    json.dumps(snap)


# -- through a tiny engine ------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine(
        get_model_config("gpt-test"),
        ServeConfig(model="gpt-test", max_batch_size=4, max_seq_len=128,
                    kv_hbm_budget_gb=0.01, dtype="float32"))
    seen = []
    eng.on_token = lambda req, tokens: seen.append(len(tokens))
    eng.on_finish = lambda req: None
    return eng


PROMPTS = [[5, 6, 7, 8] * 4, [9, 10, 11] * 5, [3] * 20, [4] * 9, [7] * 11]


def _flat(stats):
    """Every cumulative number the PR added to stats(), by a flat key."""
    out = {"clock_s": stats["clock_s"], "starved_s": stats["starved_s"],
           "queue_wait_ms.n": stats["queue_wait_ms"]["n"],
           "queue_wait_ms.sum": stats["queue_wait_ms"]["sum"]}
    for name, cell in stats["phases"].items():
        out[f"{name}.s"], out[f"{name}.n"] = cell["s"], cell["n"]
    return out


def test_engine_phases_add_up_to_its_clock(engine, monkeypatch):
    # a token callback that takes a while, as a stream's does: the steps of
    # this tiny model last a millisecond, and the few lines of step()
    # between two spans would otherwise weigh 3 % of it (0.15 % on the chip)
    monkeypatch.setattr(engine, "on_token",
                        lambda req, tokens: time.sleep(0.002))
    before = engine.stats()
    engine.generate(PROMPTS, SamplingParams(temperature=0.0, max_tokens=20))
    mid = engine.stats()
    engine.generate(PROMPTS[:2], SamplingParams(temperature=0.0,
                                                max_tokens=20))
    after = engine.stats()
    json.dumps(after)                      # what /v1/stats has to survive
    for a, b in ((before, mid), (mid, after)):
        fa, fb = _flat(a), _flat(b)
        assert all(fb[k] >= v for k, v in fa.items()), (fa, fb)
        clock = b["clock_s"] - a["clock_s"]
        phases = sum(fb[k] - fa.get(k, 0.0) for k in fb if k.endswith(".s"))
        assert abs(phases - clock) <= 0.05 * clock, (phases, clock)
    assert after["queue_wait_ms"]["n"] == after["admitted"] == 7
    for name in ("admit", "prefill.host", "prefill.wait", "capacity",
                 "decode.submit", "decode.wait", "apply", "deliver"):
        assert after["phases"][f"llmctl.engine.{name}"]["n"] > 0, name
    # one prefill.host a prefill, and no device wait inside it: the slot's
    # key is made on the host, so the span that named its fetch is gone
    assert after["phases"]["llmctl.engine.prefill.host"]["n"] == 7
    assert "llmctl.engine.prefill.key_wait" not in after["phases"]
    # drained: no slot busy, so no open starved stretch; in flight is at
    # most the pipelined dispatch that outlived its requests, unfetched
    assert engine.spans.in_flight == (engine._pending is not None)
    assert engine.stats()["starved_s"] == after["starved_s"]


@pytest.mark.parametrize("prompt_len,pages_of_its_slot", [(9, 1), (70, 2)])
def test_decode_dispatches_count_the_pages_they_walk(engine, prompt_len,
                                                     pages_of_its_slot):
    """One request among four slots of two 64-token pages: every decode
    dispatch walks the pages its length covers plus the one page an idle
    slot's table names, out of slots x pages a slot."""
    before = engine.stats()
    engine.generate([[7] * prompt_len],
                    SamplingParams(temperature=0.0, max_tokens=20))
    after = engine.stats()
    json.dumps(after["kv"])
    dispatches = (after["phases"]["llmctl.engine.decode.submit"]["n"]
                  - before["phases"]["llmctl.engine.decode.submit"]["n"])
    assert dispatches > 0
    assert engine.kv.block_tables.shape == (4, 2)
    assert (after["kv"]["table_pages"] - before["kv"]["table_pages"]
            == dispatches * 8)
    assert (after["kv"]["live_pages"] - before["kv"]["live_pages"]
            == dispatches * (pages_of_its_slot + 3))


def test_decode_program_is_found_by_the_benchmarks_committed_names(engine):
    engine.generate(PROMPTS[:1], SamplingParams(temperature=0.0,
                                                max_tokens=4))
    lowered = engine._decode_jit._fn.lower(
        engine.params, engine.kv.k_pages, engine.kv.v_pages,
        jnp.asarray(engine.last_tokens), jnp.asarray(engine.positions),
        *engine._shared_decode_args())
    module = lowered.as_text().split("module @", 1)[1].split()[0]
    assert module == "jit_" + engine._decode_jit.name == "jit__decode_impl_n"
    # programs.json as committed: the reducer's file is not this PR's to edit
    assert trace_reduce.program_of(f"{module}(1234567)") == "decode"


def test_a_cpu_profile_holds_the_engines_spans_on_a_host_line(engine,
                                                             tmp_path):
    engine.generate(PROMPTS[:1], SamplingParams(temperature=0.0,
                                                max_tokens=4))   # compiled
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        engine.generate(PROMPTS[:2], SamplingParams(temperature=0.0,
                                                    max_tokens=12))
    finally:
        jax.profiler.stop_trace()
    loaded = trace_cli.load_profile(trace_cli.find_xplane(str(tmp_path)))
    assert not loaded["devices"]           # the CPU has no device plane
    (thread, spans), = loaded["host_spans"].items()
    assert thread.startswith("/host:")
    names = {n for n, _, _ in spans}
    assert {"llmctl.engine.decode.wait", "llmctl.engine.decode.submit",
            "llmctl.engine.prefill.host", "llmctl.engine.apply"} <= names
    totals = trace_cli.host_span_totals(loaded["host_spans"])
    assert totals["llmctl.engine.decode.wait"][0] >= 2
    assert all(sec >= 0 for _, sec in totals.values())


# -- `llmctl trace summarize` on hand-made tuples ------------------------------

SPANS = [("llmctl.engine.apply", 0.0, 1.0),
         ("llmctl.engine.deliver", 0.2, 0.4),
         ("llmctl.engine.deliver", 0.5, 0.6),
         ("llmctl.engine.admit", 1.0, 1.14),
         ("llmctl.engine.decode.wait", 1.2, 3.0)]
PROGRAMS = [("jit_prefill(1)", -1.0, 0.1), ("jit__decode_impl_n(2)", 0.45, 0.9),
            ("jit__decode_impl_n(2)", 1.15, 2.9),
            ("jit__decode_impl_n(2)", 3.5, 4.0)]


def test_self_segments_give_each_instant_to_the_innermost_span():
    segs = trace_cli.self_segments(SPANS)
    assert segs[:5] == [
        ("llmctl.engine.apply", 0.0, 0.2), ("llmctl.engine.deliver", 0.2, 0.4),
        ("llmctl.engine.apply", 0.4, 0.5), ("llmctl.engine.deliver", 0.5, 0.6),
        ("llmctl.engine.apply", 0.6, 1.0)]
    assert sum(e - s for _, s, e in segs) == pytest.approx(1.0 + 0.14 + 1.8)
    # thread by thread: a second thread's spans do not nest in the first's
    both = trace_cli.self_segments({"a": SPANS,
                                    "b": [("llmctl.train.data", 0.1, 0.7)]})
    assert ("llmctl.train.data", 0.1, 0.7) in both and len(both) == 8


@pytest.mark.parametrize("programs,spans,want", [
    # 0.1-0.45: deliver covers 0.2 of it, apply 0.15; 0.9-1.15: admit 0.14,
    # apply 0.1; 2.9-3.5: decode.wait 0.1, nothing else
    (PROGRAMS, SPANS, {"llmctl.engine.deliver": 0.35,
                       "llmctl.engine.admit": 0.25,
                       "llmctl.engine.decode.wait": 0.6}),
    (PROGRAMS, [], {trace_cli.NO_SPAN: 0.35 + 0.25 + 0.6}),
    (PROGRAMS[:1], SPANS, {}),
])
def test_gaps_go_to_the_span_that_covers_most_of_each(programs, spans, want):
    got = trace_cli.attribute_gaps(programs, spans)
    for name, seconds in want.items():
        assert got[name] == pytest.approx(seconds), got
    assert sum(got.values()) == pytest.approx(
        0.0 if len(programs) < 2 else 0.35 + 0.25 + 0.6)


def test_summarize_events_accounts_for_the_whole_window():
    ops = [("fusion.1", -1.0, 0.1), ("paged_attention.3", 0.45, 0.6),
           ("fusion.2", 0.7, 0.9), ("fusion.1", 1.15, 2.9),
           ("fusion.1", 3.5, 4.0)]
    acc = trace_cli.summarize_events(PROGRAMS, ops, SPANS)
    assert acc["window_s"] == pytest.approx(5.0)
    assert acc["busy_s"] == pytest.approx(3.7)
    assert acc["programs"]["jit__decode_impl_n"] == (3, pytest.approx(2.7))
    assert acc["idle_by_span"][trace_cli.INSIDE_PROGRAM] == pytest.approx(0.1)
    assert sum(acc["idle_by_span"].values()) == pytest.approx(acc["idle_s"])
    assert acc["idle_named_share"] == pytest.approx(1.2 / 1.3)
    assert trace_cli.summarize_events([], [], SPANS) == {}


def test_capture_serve_then_summarize_through_the_cli(tmp_path):
    from click.testing import CliRunner
    runner = CliRunner()
    res = runner.invoke(trace_cli.app, [
        "capture", "--serve", "--model", "gpt-test", "--seconds", "1",
        "--out", str(tmp_path)])
    assert res.exit_code == 0, res.output[-2000:]
    res = runner.invoke(trace_cli.app, ["summarize", str(tmp_path)])
    assert res.exit_code == 0, res.output[-2000:]
    assert "llmctl.engine.decode.wait" in res.output
    assert "paged attention walks" in res.output
    assert re.search(r"prefill computed \d+ rows for \d+ tokens, [\d.]+ %",
                     res.output)
    assert runner.invoke(trace_cli.app, ["capture", "--serve"]).exit_code != 0


# -- wiring ---------------------------------------------------------------------

def test_counter_wiring_pass_is_clean_with_the_new_names():
    from distributed_llm_training_and_inference_system_tpu.analysis import (
        run_lint)
    from distributed_llm_training_and_inference_system_tpu.metrics import (
        names)
    report = run_lint(rules=["counter-wiring"])
    assert not report.unsuppressed, report.unsuppressed
    assert names.METRICS["llmctl_engine_phase_seconds_total"].labels == (
        "phase",)
    assert names.scraped_name("llmctl_engine_phase_seconds_total") == \
        "llmctl_engine_phase_seconds_total"
    assert names.METRICS["llmctl_inference_queue_wait_seconds"].kind == \
        names.HISTOGRAM


def test_prometheus_export_of_queue_wait_and_phase_seconds():
    pytest.importorskip("prometheus_client")
    from prometheus_client import REGISTRY
    from distributed_llm_training_and_inference_system_tpu.metrics import (
        observability)
    try:
        exp = observability.PrometheusExporter(port=0)
    except ValueError:
        pytest.skip("another test of this worker holds the registry's names")
    phase = "llmctl.engine.apply"

    def sample(suffix, **labels):
        return REGISTRY.get_sample_value(
            f"llmctl_inference_queue_wait_seconds_{suffix}", labels or None)
    assert sample("count") == 0.0          # before any request finished
    # what server._record_request_metrics hands over: the running totals
    waits = QueueWaitHistogram()
    waits.observe(120.0)
    exp.export_inference({"queue_wait_ms": waits.snapshot(),
                          "phases": {phase: {"s": 1.5, "n": 3}}})
    waits.observe(7000.0)
    waits.observe(120.0)
    exp.export_inference({"queue_wait_ms": waits.snapshot(),
                          "phases": {phase: {"s": 2.0, "n": 4}}})
    assert REGISTRY.get_sample_value(
        "llmctl_engine_phase_seconds_total", {"phase": phase}
    ) == pytest.approx(2.0)
    # the scheduler's histogram as it stands: same buckets, counts and sum
    assert sample("count") == 3.0 and sample("sum") == pytest.approx(7.24)
    assert sample("bucket", le="0.1") == 0.0
    assert sample("bucket", le="0.15") == sample("bucket", le="5.0") == 2.0
    assert sample("bucket", le="+Inf") == 3.0


def test_otlp_endpoint_is_accepted_and_says_it_is_ignored(caplog):
    from click.testing import CliRunner
    from distributed_llm_training_and_inference_system_tpu.cli.main import (
        main)
    import logging
    with caplog.at_level(logging.WARNING, logger="llmctl"):
        res = CliRunner().invoke(main, ["--otlp-endpoint", "http://x:4318",
                                        "trace"])
    assert res.exit_code == 0, res.output
    assert any("not supported" in r.getMessage() for r in caplog.records)
