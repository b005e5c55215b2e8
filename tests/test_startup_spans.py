"""The process's start-up recorder (metrics/spans.py StartupRecorder): spans
and the compile ledger on a stepped clock, the jax.monitoring listeners
through real compiles, a tiny engine and a tiny trainer, the operator's
surfaces. Nothing here sleeps, and no number is a device metric."""

import json
import logging
import threading

import jax
import jax.numpy as jnp
import pytest

from distributed_llm_training_and_inference_system_tpu.cli.commands import (
    trace as trace_cli)
from distributed_llm_training_and_inference_system_tpu.config.presets import (
    get_model_config)
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    OptimizerConfig, ParallelConfig, ServeConfig)
from distributed_llm_training_and_inference_system_tpu.metrics import spans
from distributed_llm_training_and_inference_system_tpu.metrics.spans import (
    MAX_EVENTS, PROGRAM, STARTUP, UNSCOPED, SpanRecorder, StartupRecorder)
from distributed_llm_training_and_inference_system_tpu.parallel.api import (
    ShardedTrainer)
from distributed_llm_training_and_inference_system_tpu.serve.engine import (
    InferenceEngine)
from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
    SamplingParams)

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
FOUR = ("trace_s", "lower_s", "compile_s", "cache_read_s")


class Clock:
    """A clock the test steps: spans take exactly what it is told."""

    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, seconds: float) -> None:
        self.t += seconds


@pytest.fixture
def rec():
    clock = Clock()
    recorder = StartupRecorder(clock=clock)
    recorder.clock = clock
    return recorder


def compile_events(rec, at: float, trace=0.0, lower=0.0, compile_=0.0,
                   read=0.0, hit=False) -> float:
    """What jax.monitoring fires for one compile that began at wall time
    ``at``, stepping the recorder's clock by the same seconds; returns the
    wall time it ended."""
    for event, took in ((TRACE, trace), (LOWER, lower)):
        rec.clock.tick(took)
        rec._on_time_span(event, at, at + took, fun_name="f")
        at += took
    if hit:
        rec._on_event(CACHE_HIT)
        rec._on_duration(CACHE_READ, read)
    took = compile_ + read
    rec.clock.tick(took)
    rec._on_time_span(COMPILE, at, at + took, fun_name="f")
    return at + took


# -- spans ---------------------------------------------------------------------

def _nested(rec):
    with rec.phase("llmctl.startup.params"):
        rec.clock.tick(1.0)
        with rec.program("init_state"):
            rec.clock.tick(2.0)
        rec.clock.tick(0.5)
    return {"llmctl.startup.params": (1.5, 1), PROGRAM: (2.0, 1)}


def _siblings(rec):
    with rec.phase("llmctl.startup.pools"):
        for _ in range(3):
            with rec.phase("llmctl.startup.restore", step=7):
                rec.clock.tick(0.25)
    return {"llmctl.startup.pools": (0.0, 1),
            "llmctl.startup.restore": (0.75, 3)}


def _raising(rec):
    with pytest.raises(ValueError):
        with rec.phase("llmctl.startup.params"):
            with rec.program("broken"):
                rec.clock.tick(1.0)
                raise ValueError("the compiler refused it")
    with rec.phase("llmctl.startup.params"):      # the stack is empty again
        rec.clock.tick(0.5)
    return {"llmctl.startup.params": (0.5, 2), PROGRAM: (1.0, 1)}


@pytest.mark.parametrize("case", [_nested, _siblings, _raising])
def test_a_startup_spans_counted_time_is_its_self_time(rec, case):
    want = case(rec)
    assert not rec._thread().stack
    snap = rec.snapshot()
    assert snap["clock_s"] == rec.clock.t and snap["import_t0"] == 100.0
    assert {k: (v["s"], v["n"]) for k, v in snap["phases"].items()} == {
        k: (pytest.approx(s), n) for k, (s, n) in want.items()}
    json.dumps(snap)


def test_a_program_span_leaves_one_ledger_entry_with_its_stamps(rec):
    rec.clock.tick(3.0)
    with rec.program("prefill 256"):
        compile_events(rec, 5000.0, trace=0.25, lower=0.5, compile_=2.0)
        rec.clock.tick(0.125)                    # arguments and the dispatch
    entry, = rec.snapshot()["programs"]
    assert entry == {"name": "prefill 256", "t0": 103.0,
                     "s": pytest.approx(2.875), "trace_s": 0.25,
                     "lower_s": 0.5, "compile_s": 2.0, "cache_read_s": 0.0,
                     "cache_hit": False, "run_s": pytest.approx(0.125)}


def test_a_cache_hit_is_a_read_and_not_a_compile(rec):
    with rec.program("decode"):
        # the backend's event wraps the cache's lookup: 0.3 s of it is the read
        compile_events(rec, 5000.0, trace=0.1, lower=0.4, compile_=0.01,
                       read=0.3, hit=True)
    entry, = rec.snapshot()["programs"]
    assert entry["cache_hit"] is True
    assert entry["cache_read_s"] == pytest.approx(0.3)
    assert entry["compile_s"] == pytest.approx(0.01)
    assert sum(entry[k] for k in FOUR) == pytest.approx(entry["s"])


def test_a_span_that_compiles_twice_hits_only_if_both_did(rec):
    with rec.program("two"):
        end = compile_events(rec, 5000.0, trace=0.1, compile_=0.0, read=0.2,
                             hit=True)
        compile_events(rec, end, trace=0.1, compile_=1.0)
    entry, = rec.snapshot()["programs"]
    assert entry["cache_hit"] is False
    assert entry["trace_s"] == pytest.approx(0.2)
    assert entry["compile_s"] == pytest.approx(1.0)


def test_a_trace_inside_a_trace_is_counted_once(rec):
    """A jitted jnp function traced inside the program's trace fires its own
    event BEFORE the outer one ends; the outer interval holds its time."""
    with rec.program("outer"):
        rec.clock.tick(1.0)
        rec._on_time_span(TRACE, 5000.2, 5000.3, fun_name="tanh")
        rec._on_time_span(TRACE, 5000.4, 5000.7, fun_name="inner")
        rec._on_time_span(TRACE, 5000.0, 5001.0, fun_name="outer")
        # a lowering rule that traces a function of its own
        rec.clock.tick(0.5)
        rec._on_time_span(TRACE, 5001.1, 5001.2, fun_name="rule")
        rec._on_time_span(LOWER, 5001.0, 5001.5, fun_name="jit(outer)")
    entry, = rec.snapshot()["programs"]
    assert entry["trace_s"] == pytest.approx(1.0)
    assert entry["lower_s"] == pytest.approx(0.5)
    assert sum(entry[k] for k in FOUR) <= entry["s"] + 1e-9


def test_events_are_cut_at_an_instant(rec):
    with rec.phase("llmctl.startup.pools"):
        rec.clock.tick(1.0)                      # ends at 101
    with rec.program("before the window"):
        compile_events(rec, 5000.0, trace=1.0, compile_=1.0)    # ends at 103
    window = rec.clock.t
    with rec.program("in the window"):
        compile_events(rec, 6000.0, trace=1.0, compile_=3.0)    # ends at 107
    with rec.phase("llmctl.startup.pools"):
        rec.clock.tick(1.0)
    cut, whole = rec.snapshot(until=window), rec.snapshot()
    assert [p["name"] for p in cut["programs"]] == ["before the window"]
    assert [p["name"] for p in whole["programs"]] == ["before the window",
                                                      "in the window"]
    assert cut["phases"] == {"llmctl.startup.pools": {"s": 1.0, "n": 1},
                             PROGRAM: {"s": 2.0, "n": 1}}
    assert whole["phases"]["llmctl.startup.pools"] == {"s": 2.0, "n": 2}
    assert whole["phases"][PROGRAM] == {"s": 6.0, "n": 2}
    assert rec.snapshot(until=100.5)["phases"] == {}


def test_the_cap_on_events_keeps_the_totals_right(rec):
    for i in range(MAX_EVENTS + 40):
        with rec.program(f"p{i}"):
            rec.clock.tick(0.5)
    snap = rec.snapshot()
    assert len(rec._events) == len(snap["programs"]) == MAX_EVENTS
    assert snap["phases"][PROGRAM] == {"s": 0.5 * (MAX_EVENTS + 40),
                                       "n": MAX_EVENTS + 40}
    # a cut reads the events that kept their stamps, and says so by its count
    assert rec.snapshot(until=rec.clock.t)["phases"][PROGRAM]["n"] == MAX_EVENTS


def test_import_ends_once_and_leaves_out_the_spans_inside_it():
    clock = Clock(50.0)
    rec = StartupRecorder(clock=clock, import_t0=40.0)
    with rec.phase("llmctl.startup.backend"):
        clock.tick(4.0)
    clock.tick(1.0)
    rec.imported()                   # serve/server.py has finished importing
    clock.tick(100.0)
    rec.imported()                   # a second entry module: nothing more
    snap = rec.snapshot()
    assert snap["import_t0"] == 40.0
    assert snap["phases"]["llmctl.startup.import"] == {"s": 11.0, "n": 1}
    assert snap["phases"]["llmctl.startup.backend"] == {"s": 4.0, "n": 1}


def test_ready_is_stamped_once_and_the_summary_names_the_costly(rec):
    assert rec.snapshot()["ready_t"] is None
    with rec.phase("llmctl.startup.pools"):
        rec.clock.tick(0.5)
    for name, seconds in (("prefill 256", 2.0), ("prefill 512", 3.0),
                          ("decode", 9.0), ("prefill 1024", 4.0)):
        with rec.program(name):
            compile_events(rec, 5000.0, lower=seconds)
    compile_events(rec, 7000.0, compile_=30.0)         # an (unscoped) init
    rec.ready()
    ready = rec.clock.t
    rec.clock.tick(60.0)
    rec.ready()
    assert rec.ready_t == rec.snapshot()["ready_t"] == ready
    line = rec.summary()
    assert line.startswith(f"start-up {ready - 100.0:.2f} s")
    assert "program 18.00 x4" in line and "pools 0.50" in line
    costly = line.split("most expensive programs: ")[1]
    assert [c.split(" ")[0] for c in costly.split("; ")] == [
        "decode", "prefill", "prefill"]
    assert "prefill 256" not in costly and UNSCOPED not in costly


def test_a_note_is_kept_by_name_and_the_summary_ends_with_it(rec):
    assert rec.snapshot()["notes"] == {}
    rec.note("chunked_loss_bwd", axis="rows", slices=8)
    rec.note("chunked_loss_bwd", axis="vocabulary", slices=3, width=30848)
    assert rec.snapshot()["notes"] == {"chunked_loss_bwd": {
        "axis": "vocabulary", "slices": 3, "width": 30848}}
    assert rec.summary().endswith(
        " | chunked_loss_bwd: axis vocabulary, slices 3, width 30848")


# -- the ledger's listeners ----------------------------------------------------

def test_an_event_lands_in_the_innermost_program_span_of_its_thread(rec):
    """Two threads compile at once, each inside its own program span; a
    third compiles under none. Every event goes to its own thread's span."""
    opened, fired = threading.Barrier(3), threading.Barrier(3)

    def worker(name, seconds):
        with rec.phase("llmctl.startup.params"), rec.program(name):
            opened.wait(timeout=30)              # all three spans are open
            rec._on_time_span(TRACE, 5000.0, 5000.0 + seconds, fun_name=name)
            rec._on_time_span(COMPILE, 5010.0, 5010.0 + seconds,
                              fun_name=name)
            fired.wait(timeout=30)

    def loose():
        opened.wait(timeout=30)
        rec._on_time_span(TRACE, 5000.0, 5000.5, fun_name="eager")
        rec._on_time_span(COMPILE, 5001.0, 5001.25, fun_name="eager")
        fired.wait(timeout=30)

    threads = [threading.Thread(target=worker, args=("a", 1.0)),
               threading.Thread(target=worker, args=("b", 2.0)),
               threading.Thread(target=loose)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    got = {p["name"]: p for p in rec.snapshot()["programs"]}
    assert sorted(got) == [UNSCOPED, "a", "b"]
    assert (got["a"]["trace_s"], got["a"]["compile_s"]) == (1.0, 1.0)
    assert (got["b"]["trace_s"], got["b"]["compile_s"]) == (2.0, 2.0)
    assert (got[UNSCOPED]["trace_s"], got[UNSCOPED]["compile_s"]) == (0.5,
                                                                      0.25)
    assert rec.snapshot()["phases"][PROGRAM]["n"] == 2


def test_unscoped_compiles_get_one_entry_each_with_their_own_t0(rec):
    rec.clock.tick(5.0)
    compile_events(rec, 5000.0, trace=0.5, lower=0.5, compile_=1.0)
    rec.clock.tick(10.0)
    compile_events(rec, 6000.0, trace=0.25, compile_=0.0, read=0.25, hit=True)
    first, second = rec.snapshot()["programs"]
    assert first["name"] == second["name"] == UNSCOPED
    assert (first["t0"], first["s"]) == (105.0, 2.0)
    assert (second["t0"], second["s"]) == (117.0, 0.5)
    assert (first["cache_hit"], second["cache_hit"]) == (False, True)
    # no span: the totals by phase hold spans alone
    assert rec.snapshot()["phases"] == {}
    assert rec.snapshot(until=110.0)["programs"] == [first]


def test_real_compiles_reach_the_process_recorder_through_jax_monitoring():
    """STARTUP's listeners are registered once, at import: a jit's first
    call inside a program span fills that span's ledger, one outside any
    becomes an (unscoped) entry, and a second call of either leaves none."""
    @jax.jit
    def inner(x):
        return jnp.tanh(x) * 2.0

    def body(x):
        return jnp.sum(jax.nn.softmax(inner(x) + jnp.where(x > 0, x, 0.0)))

    before = len(STARTUP.snapshot()["programs"])
    scoped, loose = jax.jit(body), jax.jit(lambda x: body(x) + 1.0)
    x = jnp.ones((8, 8))
    with STARTUP.program("tests.body"):
        scoped(x).block_until_ready()
    loose(x).block_until_ready()
    new = STARTUP.snapshot()["programs"][before:]
    if len(STARTUP.snapshot()["programs"]) == MAX_EVENTS:
        pytest.skip("this worker's process has filled the ledger's cap")
    named = [p for p in new if p["name"] == "tests.body"]
    assert len(named) == 1 and new[-1]["name"] == UNSCOPED
    for p in (named[0], new[-1]):
        assert p["trace_s"] > 0 and p["lower_s"] > 0 and p["compile_s"] > 0
        assert p["cache_hit"] is False       # the tests run without a cache
        assert sum(p[k] for k in FOUR) <= p["s"] + 1e-6, p
        assert p["run_s"] == pytest.approx(p["s"] - sum(p[k] for k in FOUR))
    count = len(STARTUP.snapshot()["programs"])
    scoped(x).block_until_ready()
    loose(x).block_until_ready()
    assert len(STARTUP.snapshot()["programs"]) == count


# -- through a tiny engine and a tiny trainer ----------------------------------

@pytest.fixture(scope="module")
def engine():
    return InferenceEngine(
        get_model_config("gpt-test"),
        # pages and the ladder's finest step of 16 tokens: prefill programs
        # of 16, 32, 64, 96 and 128 rows
        ServeConfig(model="gpt-test", max_batch_size=4, max_seq_len=128,
                    kv_hbm_budget_gb=0.01, dtype="float32",
                    kv_block_size=16, prefill_chunk=16))


def _programs(name=None):
    return [p for p in STARTUP.snapshot()["programs"]
            if name is None or p["name"] == name]


def test_an_engines_first_prefill_and_first_decode_leave_one_entry_each(
        engine, caplog, monkeypatch):
    if len(_programs()) > MAX_EVENTS - 8:
        pytest.skip("this worker's process has filled the ledger's cap")
    # (a server of another test file in this process may have been ready)
    monkeypatch.setattr(STARTUP, "ready_t", None)
    had = {n: len(_programs(n)) for n in ("prefill 16", "_decode_impl_n")}
    greedy = SamplingParams(temperature=0.0, max_tokens=6)
    with caplog.at_level(logging.WARNING, logger="llmctl.serve.engine"):
        engine.generate([[5, 6, 7, 8] * 4], greedy)
    assert not [r for r in caplog.records if "first ran after" in r.message]
    for name, n in had.items():
        entry, = _programs(name)[n:]
        assert sum(entry[k] for k in FOUR) <= entry["s"] + 1e-6, entry
        assert entry["trace_s"] > 0 and entry["compile_s"] > 0
        assert entry["t0"] >= STARTUP.import_t0
    stats = engine.stats()
    json.dumps(stats["startup"])                 # what /v1/stats has to carry
    assert stats["startup"]["clock_s"] == pytest.approx(stats["clock_s"],
                                                        abs=0.5)
    assert stats["startup"]["phases"][PROGRAM]["n"] >= 2
    assert stats["startup"]["phases"]["llmctl.startup.pools"]["n"] >= 1
    # the same shapes again: no first call, no entry
    count = len(_programs())
    engine.generate([[9, 10, 11] * 5], greedy)
    assert len(_programs()) == count
    # a bucket not yet seen is one more entry, and one only
    engine.generate([[3] * 100], greedy)
    entry, = _programs()[count:]
    assert entry["name"] == "prefill 128"
    assert stats["compiled_programs"]["total"] + 1 == engine.stats()[
        "compiled_programs"]["total"]


def test_a_program_that_first_runs_after_ready_says_so_once(engine, caplog,
                                                            monkeypatch):
    greedy = SamplingParams(temperature=0.0, max_tokens=2)
    monkeypatch.setattr(STARTUP, "ready_t", None)
    engine.generate([[5] * 100], greedy)     # decode has run before ready
    monkeypatch.setattr(STARTUP, "ready_t", STARTUP.snapshot()["clock_s"])
    with caplog.at_level(logging.WARNING, logger="llmctl.serve.engine"):
        engine.generate([[4] * 30], greedy)      # the 32-token bucket: new
        engine.generate([[6] * 31], greedy)      # the same bucket again
    lines = [r.getMessage() for r in caplog.records
             if "first ran after" in r.getMessage()]
    assert len(lines) == 1 and "'prefill 32'" in lines[0]


def test_program_texts_compile_under_spans_of_their_own(engine):
    engine.generate([[5, 6, 7, 8] * 4],
                    SamplingParams(temperature=0.0, max_tokens=2))
    before = STARTUP.snapshot()["phases"][PROGRAM]["n"]
    texts = engine.program_texts()
    assert engine._decode_jit.name in texts
    assert STARTUP.snapshot()["phases"][PROGRAM]["n"] == before + len(texts)
    if len(_programs()) < MAX_EVENTS:
        assert _programs(f"{engine._decode_jit.name} (text)")


def test_a_trainer_leaves_init_state_and_train_step_entries():
    if len(_programs()) > MAX_EVENTS - 8:
        pytest.skip("this worker's process has filled the ledger's cap")
    had = {n: len(_programs(n))
           for n in ("init_state", "train_step", "eval_step")}
    params_before = STARTUP.snapshot()["phases"].get(
        "llmctl.startup.params", {"n": 0})["n"]
    cfg = get_model_config("gpt-test")
    trainer = ShardedTrainer(cfg, OptimizerConfig(lr=1e-2), ParallelConfig(),
                             devices=jax.devices()[:1])
    trainer.init_state(seed=0)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 1,
                                          cfg.vocab_size)}
    for _ in range(3):
        float(trainer.step(batch)["loss"])
    for _ in range(2):
        trainer.evaluate(batch)
    for name, n in had.items():
        entry, = _programs(name)[n:]             # ONE a program, not a call
        assert entry["compile_s"] > 0
        assert sum(entry[k] for k in FOUR) <= entry["s"] + 1e-6, entry
    assert STARTUP.snapshot()["phases"]["llmctl.startup.params"][
        "n"] == params_before + 1
    # beside the step's span the trainer left the loss's backward plan, and
    # the evaluations' traces did not touch it: [2, 31] targets in one
    # chunk against gpt-test's whole vocabulary in one slice
    assert STARTUP.snapshot()["notes"]["chunked_loss_bwd"] == {
        "axis": "vocabulary", "slices": 1, "width": cfg.vocab_size,
        "carry_bytes": 2 * 31 * cfg.hidden_size * 4,
        "transient_bytes": 2 * 31 * cfg.vocab_size * 4}


# -- the operator's surfaces ---------------------------------------------------

def test_v1_stats_carries_startup_and_a_late_bucket_warns_once(caplog,
                                                               monkeypatch):
    """A served model: ready is stamped and logged, /v1/stats has one
    ``programs`` entry a resident program, a prompt of a bucket not yet
    seen adds one entry and one warning, a second such prompt neither."""
    import asyncio

    import requests as rq

    from distributed_llm_training_and_inference_system_tpu.serve.server \
        import InferenceServer
    if len(_programs()) > MAX_EVENTS - 8:
        pytest.skip("this worker's process has filled the ledger's cap")
    monkeypatch.setattr(STARTUP, "ready_t", None)
    began = len(_programs())
    srv = InferenceServer(get_model_config("gpt-test"), ServeConfig(
        model="gpt-test", max_batch_size=4, max_seq_len=128,
        prefill_chunk=32, kv_block_size=8, dtype="float32",
        host="127.0.0.1", port=0))
    loop, started, state = asyncio.new_event_loop(), threading.Event(), {}

    def run():
        asyncio.set_event_loop(loop)

        async def main():
            state["port"] = (await srv.start_async()).addresses[0][1]
            started.set()
        loop.run_until_complete(main())
        loop.run_forever()

    with caplog.at_level(logging.INFO):
        thread = threading.Thread(target=run, daemon=True)
        thread.start()
        try:
            assert started.wait(timeout=30)
            base = f"http://127.0.0.1:{state['port']}"

            def ask(n):          # (its own token: no prefix is shared)
                r = rq.post(f"{base}/v1/completions", json={
                    "prompt": [n] * n, "max_tokens": 3, "temperature": 0.0},
                    timeout=120)
                assert r.status_code == 200
                stats = rq.get(f"{base}/v1/stats", timeout=10).json()
                return stats, [p["name"] for p in stats["startup"]["programs"]
                               if p["name"] != UNSCOPED][-8:]
            stats, names = ask(20)                 # the 32-token bucket
            assert stats["startup"]["ready_t"] == STARTUP.ready_t is not None
            resident = stats["compiled_programs"]["total"]
            scoped = [p for p in _programs()[began:] if p["name"] != UNSCOPED]
            assert len(scoped) == resident == 2
            assert names[-2:] == ["prefill 32", "_decode_impl_n"]
            stats, names = ask(50)                 # a bucket not yet seen
            assert names[-1] == "prefill 64"
            assert stats["compiled_programs"]["total"] == resident + 1
            again, names_again = ask(60)           # the same bucket
            assert names_again == names
        finally:
            loop.call_soon_threadsafe(loop.stop)
            thread.join(timeout=5)
            srv.stop_engine()
    lines = [r.getMessage() for r in caplog.records]
    assert sum(m.startswith("start-up ") for m in lines) == 1
    late = [m for m in lines if "first ran after the server was ready" in m]
    # this server has no warm-up: its first request compiled under traffic
    assert [m.split("'")[1] for m in late] == ["prefill 32", "_decode_impl_n",
                                               "prefill 64"]


def test_a_cpu_profile_shows_the_startup_spans_and_summarize_tables_them(
        tmp_path):
    from click.testing import CliRunner
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with STARTUP.phase("llmctl.startup.pools"):
            with STARTUP.program("tests.profiled"):
                jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(16)
                                                 ).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    loaded = trace_cli.load_profile(trace_cli.find_xplane(str(tmp_path)))
    names = {n for one in loaded["host_spans"].values() for n, _, _ in one}
    assert {"llmctl.startup.pools", PROGRAM} <= names
    (name, seconds), = loaded["startup_programs"]
    assert name == "tests.profiled" and seconds > 0
    res = CliRunner().invoke(trace_cli.app, ["summarize", str(tmp_path)])
    assert res.exit_code == 0, res.output[-2000:]
    assert "start-up:" in res.output and "tests.profiled" in res.output


def test_the_prometheus_name_is_registered_and_exported():
    from distributed_llm_training_and_inference_system_tpu.analysis import (
        run_lint)
    from distributed_llm_training_and_inference_system_tpu.metrics import (
        names, observability)
    assert not run_lint(rules=["counter-wiring"]).unsuppressed
    spec = names.METRICS["llmctl_startup_phase_seconds"]
    assert spec.kind == names.GAUGE and spec.labels == ("phase",)
    pytest.importorskip("prometheus_client")
    from prometheus_client import REGISTRY
    try:
        exp = observability.PrometheusExporter(port=0)
    except ValueError:
        pytest.skip("another test of this worker holds the registry's names")
    exp.export_inference({"startup_phases": {PROGRAM: {"s": 14.5, "n": 5}}})
    exp.export_inference({"startup_phases": {PROGRAM: {"s": 16.0, "n": 6}}})
    assert REGISTRY.get_sample_value(
        "llmctl_startup_phase_seconds", {"phase": PROGRAM}) == 16.0


def test_both_recorders_take_a_clock_and_default_to_monotonic():
    import time
    assert SpanRecorder()._clock is time.monotonic
    assert StartupRecorder()._clock is time.monotonic
    assert STARTUP._clock is time.monotonic and STARTUP._listening
    assert spans.STARTUP is STARTUP
