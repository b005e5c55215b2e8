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
    NO_SPAN, QUEUE_WAIT_LE_MS, QueueWaitHistogram, SpanRecorder)
from distributed_llm_training_and_inference_system_tpu.serve.engine import (
    InferenceEngine)
from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
    Request, SamplingParams)

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


def _starved_between_spans(rec, now):
    rec.set_busy(True)
    now[0] += TICK                        # starved, no span open
    with rec.phase("outer"):
        now[0] += 2 * TICK
    rec.dispatched()                      # ... until here
    with rec.phase("outer"):
        now[0] += 5 * TICK                # a program runs: not starved
    return {NO_SPAN: TICK, "outer": 2 * TICK}


def _starved_goes_to_the_innermost_span(rec, now):
    rec.set_busy(True)
    with rec.phase("outer"):
        now[0] += TICK
        with rec.phase("inner"):
            now[0] += 3 * TICK
            rec.dispatched()              # inside the span: it is cut here
            now[0] += 7 * TICK
            rec.fetched()
            now[0] += TICK
        now[0] += 2 * TICK
    return {"outer": 3 * TICK, "inner": 4 * TICK}


def _starved_stretch_still_open(rec, now):
    with rec.phase("outer"):
        rec.set_busy(True)
        now[0] += TICK
        with rec.phase("inner"):
            now[0] += TICK
            mid = rec.snapshot()          # both spans and the stretch open
            assert mid["starved_by_phase"] == pytest.approx(
                {"outer": TICK, "inner": TICK})
            assert mid["starved_s"] == pytest.approx(2 * TICK)
            now[0] += TICK
    now[0] += 4 * TICK
    rec.set_busy(False)
    now[0] += 9 * TICK                    # idle: nobody is kept waiting
    return {"outer": TICK, "inner": 2 * TICK, NO_SPAN: 4 * TICK}


@pytest.mark.parametrize("case", [_starved_between_spans,
                                  _starved_goes_to_the_innermost_span,
                                  _starved_stretch_still_open])
def test_starved_seconds_go_to_the_innermost_open_span(case):
    now = [50.0]
    rec = SpanRecorder(clock=lambda: now[0])
    want = case(rec, now)
    snap = rec.snapshot()
    # (a stretch that opens at the instant a span does leaves 0.0 behind)
    assert {k: v for k, v in snap["starved_by_phase"].items()
            if v} == pytest.approx(want)
    assert sum(snap["starved_by_phase"].values()) == pytest.approx(
        snap["starved_s"])
    json.dumps(snap)


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
                    kv_block_size=64, kv_hbm_budget_gb=0.01,
                    dtype="float32"))
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
    # the recorder on a clock the token callback alone steps, as a stream's
    # write takes a while: every second of the engine's clock then lies in a
    # ``deliver`` span (inside ``apply``), whatever the machine is busy with,
    # and the spans' self times have to add up to it exactly
    now = [50.0]
    monkeypatch.setattr(engine.spans, "_clock", lambda: now[0])
    monkeypatch.setattr(engine, "on_token", lambda req, tokens: now.__setitem__(
        0, now[0] + 0.002))
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
        assert clock > 0 and phases == pytest.approx(clock), (phases, clock)
        assert sum(b["starved_by_phase"].values()) == pytest.approx(
            b["starved_s"])
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


# -- the slot-step ledger --------------------------------------------------------

def _requests(tag, max_tokens):
    return [Request(f"{tag}{i}", list(PROMPTS[i]),
                    SamplingParams(temperature=0.0, max_tokens=n))
            for i, n in enumerate(max_tokens)]


def _stop_mid_dispatch(engine):
    """Four replies of 5 tokens: the first from the prefill, 4 of the one
    dispatch's 8 steps, and the other 4 steps are nobody's."""
    for r in _requests("stop", [5] * 4):
        assert engine.scheduler.add_request(r)
    engine.run_until_idle()
    return {"useful": 16, "overrun": 16, "prompt_wait": 0, "empty": 0,
            "first_tokens": 4, "decode_steps": 8}


def _cancel_mid_dispatch(engine):
    """Two of four slots decode (a chain forms); one is cancelled while a
    dispatch is in flight and the one chained behind it is submitted: what
    they computed for its slot is credited to nobody."""
    a, b = _requests("cancel", [40, 40])
    for r in (a, b):
        assert engine.scheduler.add_request(r)
    for _ in range(3):
        engine.step()
    assert engine._pending is not None and len(a.generated_tokens) == 17
    with engine.lock:
        assert engine.scheduler.cancel(a.request_id)
    engine.run_until_idle()
    assert len(a.generated_tokens) == 17 and len(b.generated_tokens) == 40
    # b: 39 steps of 5 dispatches (the slot's last step is overrun); a: 16
    # steps of two, then the dispatch in flight at the cancel, whose slot it
    # still held when that was submitted; from the next on nobody sits there
    return {"useful": 39 + 16, "overrun": 1 + 8, "prompt_wait": 0,
            "empty": 5 * 8 * 2 + 2 * 8, "first_tokens": 2,
            "decode_steps": 5 * 8}


def _reseated_behind_a_finished_request(engine):
    """Five requests over four slots, six dispatches of a chain: the first
    request ends in the second (1 + 8 + 3 of its 12 tokens: 5 steps
    overrun), and since it MUST end there and the fifth waits, it gives its
    slot back before the third is submitted (PR 52): the fifth is seated
    there and its 11 tokens ride the third's step 0 (1 step of waiting, 7
    useful in a slot that was not live at the submit), then 2 of the
    fourth's steps end it (6 overrun; nobody waits, so the fifth dispatch
    is chained behind with the slot still live: 8 more); the other three
    get 8 + 8 + 8 + 8 + 7; the sixth dispatch was chained behind the fifth
    before the host knew that every reply ends there, its fourth slot
    empty."""
    reqs = _requests("seat", [12, 40, 40, 40, 10])
    for r in reqs:
        assert engine.scheduler.add_request(r)
    engine.run_until_idle()
    assert [len(r.generated_tokens) for r in reqs] == [12, 40, 40, 40, 10]
    return {"useful": 32 + 27 + 31 + 26 + 21,
            "overrun": 5 + 6 + 11 + 24, "prompt_wait": 1, "empty": 8,
            "first_tokens": 5, "decode_steps": 48, "armed_in_flight": 7,
            "early_handbacks": 1}


def _an_idle_slot(engine):
    [r] = _requests("idle", [9])
    assert engine.scheduler.add_request(r)
    engine.run_until_idle()
    return {"useful": 8, "overrun": 0, "prompt_wait": 0, "empty": 24,
            "first_tokens": 1, "decode_steps": 8}


@pytest.mark.parametrize("case", [
    _stop_mid_dispatch, _cancel_mid_dispatch,
    _reseated_behind_a_finished_request, _an_idle_slot])
def test_every_slot_step_has_one_class_and_the_tokens_close(engine,
                                                            monkeypatch, case):
    seen = []
    monkeypatch.setattr(engine, "on_token",
                        lambda req, tokens: seen.append(len(tokens)))
    engine._drain_pending()     # a dispatch that outlived an earlier case
    before = engine.stats()
    want = case(engine)
    engine._drain_pending()
    after = engine.stats()
    got = {k: after["slot_steps"][k] - before["slot_steps"][k]
           for k in after["slot_steps"]}
    got["decode_steps"] = after["decode_steps"] - before["decode_steps"]
    classes = ("useful", "overrun", "prompt_wait", "empty")
    for stats in (got, {**after["slot_steps"],
                        "decode_steps": after["decode_steps"]}):
        assert sum(stats[k] for k in classes) == 4 * stats["decode_steps"]
    assert got["tokens_credited"] == sum(seen)
    # one token a step, and a prefill program's first token
    assert got["useful"] == got["tokens_credited"] - got["first_tokens"]
    armed_in_flight = want.pop("armed_in_flight", 0)
    assert {k: got[k] for k in want} == want
    # what the accepted counter calls padded: the slots not live at the
    # submit, those the device armed in that very dispatch among them
    assert (after["padded_slot_steps"] - before["padded_slot_steps"]
            == got["empty"] + got["prompt_wait"] + armed_in_flight)


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
        *engine._shared_decode_args(), *engine._decode_tail_args())
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
    # the engine's own account of the stretch, by span, beside the capture
    said = re.search(r"the engine was starved ([\d.]+) s of [\d.]+: (.*)",
                     res.output)
    assert float(said[1]) == pytest.approx(sum(
        float(x.rsplit(" ", 1)[1]) for x in said[2].split(", ")), abs=1e-3)
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
    # the slot-step ledger and starved_s by span, as operators scrape them
    assert names.METRICS["llmctl_slot_steps_total"].labels == ("class",)
    assert names.METRICS["llmctl_starved_seconds_total"].labels == ("span",)
    flows = {f.snapshot_key: f.metric for f in names.COUNTER_FLOW
             if f.owner == "InferenceEngine"}
    assert {flows[k] for k in ("useful", "overrun", "prompt_wait",
                               "empty")} == {"llmctl_slot_steps_total"}
    assert flows["first_tokens"] is flows["tokens_credited"] is None


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
                          "phases": {phase: {"s": 1.5, "n": 3}},
                          "starved_by_phase": {phase: 0.25, NO_SPAN: 0.5},
                          "slot_steps": {"useful": 90, "overrun": 6,
                                         "prompt_wait": 3, "empty": 1,
                                         "first_tokens": 4,
                                         "tokens_credited": 94}})
    waits.observe(7000.0)
    waits.observe(120.0)
    exp.export_inference({"queue_wait_ms": waits.snapshot(),
                          "phases": {phase: {"s": 2.0, "n": 4}},
                          "starved_by_phase": {phase: 0.75, NO_SPAN: 0.5},
                          "slot_steps": {"useful": 180, "overrun": 12,
                                         "prompt_wait": 3, "empty": 5,
                                         "first_tokens": 8,
                                         "tokens_credited": 188}})
    assert REGISTRY.get_sample_value(
        "llmctl_engine_phase_seconds_total", {"phase": phase}
    ) == pytest.approx(2.0)
    # the engine's running totals, by span and by class
    assert REGISTRY.get_sample_value(
        "llmctl_starved_seconds_total", {"span": phase}
    ) == pytest.approx(0.75)
    assert REGISTRY.get_sample_value(
        "llmctl_starved_seconds_total", {"span": NO_SPAN}
    ) == pytest.approx(0.5)
    assert [REGISTRY.get_sample_value("llmctl_slot_steps_total", {"class": c})
            for c in ("useful", "overrun", "prompt_wait", "empty")] == [
                180.0, 12.0, 3.0, 5.0]
    assert REGISTRY.get_sample_value(
        "llmctl_slot_steps_total", {"class": "first_tokens"}) is None
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
