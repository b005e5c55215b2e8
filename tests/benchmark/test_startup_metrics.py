"""The start-up metrics (PR 35): each entry pinned BY NAME, each reader on a
hand-made run, on a run of a program without the recorder, and the sum that
`startup.unattributed_s` closes. No number here is a device metric."""


import pytest

from benchmark import layer_metrics, startup_counters
from benchmark.run import load_cell
from manifest_pins import MANIFEST, assert_lists, entry

LAYER = ("start-up (metrics/spans.py StartupRecorder: serve/engine.py "
         "_Program, parallel/api.py, runtime/engine.py)")
TRAINING = ["internlm2-1.8b-6l.pretrain-4k", "internlm2-1.8b.pretrain-4k-fsdp4"]
SERVING = ["mistral-7b-16l.chat", "mistral-7b-16l.batch-64",
           "olmoe-1b-7b-10l.moe-batch-64",
           "nemotron-3-nano-30b-a3b-14l-ep2.reason-batch-128",
           "xing4.0-29b-a4b-7l.doc-qa-64"]
CELLS = [c["name"] for c in MANIFEST["workloads"]]
ENTRIES = {
    "startup.import_s": ("s", "program_span", CELLS),
    "startup.program_lowering_s": ("s", "program_counter", CELLS),
    "startup.program_compile_s": ("s", "program_counter", CELLS),
    "startup.programs": ("programs", "program_counter", CELLS),
    "startup.cache_misses": ("programs", "program_counter", CELLS),
    "startup.engine_work_s": ("s", "program_span", SERVING),
    "startup.unattributed_s": ("s", "program_span", CELLS),
}


def program(name, t0, s, trace=0.0, lower=0.0, compile_=0.0, read=0.0,
            hit=None):
    return {"name": name, "t0": t0, "s": s, "trace_s": trace,
            "lower_s": lower, "compile_s": compile_, "cache_read_s": read,
            "cache_hit": hit, "run_s": s - trace - lower - compile_ - read}


# a warm start: import 9, pools 0.5, two programs read from the cache on the
# engine thread, the benchmark's own init compiled under no span
STARTUP = {
    "clock_s": 140.0, "import_t0": 100.5, "ready_t": 112.0,
    "phases": {"llmctl.startup.import": {"s": 9.0, "n": 1},
               "llmctl.startup.pools": {"s": 0.5, "n": 1},
               "llmctl.startup.program": {"s": 3.0, "n": 2}},
    "programs": [
        program("(unscoped)", 110.0, 2.5, trace=0.25, lower=0.25,
                compile_=2.0, hit=False),
        program("prefill 256", 115.0, 1.0, trace=0.125, lower=0.5,
                read=0.25, hit=True),
        program("_decode_impl_n", 117.0, 2.0, trace=0.25, lower=0.75,
                read=0.5, hit=True)]}
SERVE_RUN = {
    "kind": "serve", "setup_s": 40.0,
    "stats": {"before": {
        "startup": STARTUP,
        # the engine thread: 20 s idle, 8 s of spans of which 3 s are the
        # two programs' first calls
        "phases": {"llmctl.engine.idle": {"s": 20.0, "n": 400},
                   "llmctl.engine.prefill.host": {"s": 1.5, "n": 9},
                   "llmctl.engine.decode.submit": {"s": 2.5, "n": 40},
                   "llmctl.engine.decode.wait": {"s": 4.0, "n": 40}}}}}
WANT_SERVE = {"startup.import_s": 9.0,
              "startup.program_lowering_s": 0.5 + 0.625 + 1.0,
              "startup.program_compile_s": 2.0 + 0.25 + 0.5,
              "startup.programs": 3, "startup.cache_misses": 1,
              "startup.engine_work_s": 8.0 - 3.0,
              "startup.unattributed_s": 40.0 - 12.5 - 5.0}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_entry_is_pinned_by_name(name):
    """By name and by membership: the cells PR 35 listed are in the list,
    whatever joined since."""
    unit, source, cells = ENTRIES[name]
    for cell in cells:
        assert_lists(name, cell, unit=unit, better="lower", source=source,
                     layer=LAYER, moves="setup_s")
    assert not set(entry(name)["workloads"]) - set(CELLS)


def test_no_other_metric_moves_setup_s_and_every_cell_reports_it():
    moving = {m["name"] for m in MANIFEST["per_layer"]
              if m["moves"] == "setup_s"}
    assert moving == set(ENTRIES)
    # every cell that serves lists the engine's start-up work, no other
    assert set(entry("startup.engine_work_s")["workloads"]) == (
        set(CELLS) - set(TRAINING))
    assert set(SERVING + TRAINING) <= set(CELLS)
    setup = next(m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup              # every cell reports it
    assert len(LAYER) <= 200


@pytest.mark.parametrize("cell", CELLS)
def test_which_cells_list_which_metrics(cell):
    listed = {m["name"] for m in load_cell(cell, MANIFEST)["per_layer"]
              if m["name"] in ENTRIES}
    want = set(ENTRIES) - ({"startup.engine_work_s"} if cell in TRAINING
                           else set())
    assert listed == want


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_on_a_hand_made_serving_run(name):
    assert layer_metrics.load(name).read(SERVE_RUN) == pytest.approx(
        WANT_SERVE[name])


def test_unattributed_is_setup_less_the_named_parts_on_that_run():
    read = {n: layer_metrics.load(n).read(SERVE_RUN) for n in ENTRIES}
    named = startup_counters.named_seconds(SERVE_RUN)
    assert named == 9.0 + 0.5 + 3.0
    assert (read["startup.unattributed_s"] + named
            + read["startup.engine_work_s"]) == pytest.approx(
                SERVE_RUN["setup_s"])


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_on_a_run_of_a_program_without_the_recorder(name,
                                                           monkeypatch):
    """The parent's: its stats have no ``startup`` and its package no
    ``STARTUP``; the reader finds nothing and does not raise."""
    before = dict(SERVE_RUN["stats"]["before"])
    del before["startup"]
    serve = dict(SERVE_RUN, stats={"before": before})
    assert layer_metrics.load(name).read(serve) is None
    monkeypatch.setattr(startup_counters, "recorder", lambda: None)
    if name != "startup.engine_work_s":
        train = {"kind": "train", "setup_s": 30.0,
                 "blocks": [(130.0, 135.0, 4)]}
        assert layer_metrics.load(name).read(train) is None


class _Recorder:
    """Stands for the program's ``STARTUP`` in a training run's process."""

    def snapshot(self, until=None):
        assert until == 130.0        # the first block's first stamp
        return {"clock_s": 200.0, "import_t0": 100.5, "ready_t": None,
                "phases": {"llmctl.startup.import": {"s": 12.0, "n": 1},
                           "llmctl.startup.data": {"s": 0.25, "n": 1},
                           "llmctl.startup.program": {"s": 6.0, "n": 1}},
                "programs": [
                    program("(unscoped)", 114.0, 5.0, compile_=4.5, hit=False),
                    program("train_step", 122.0, 6.0, trace=1.0, lower=1.5,
                            compile_=3.0, hit=False)]}


@pytest.mark.parametrize("name,want", [
    ("startup.import_s", 12.0), ("startup.program_lowering_s", 2.5),
    ("startup.program_compile_s", 7.5), ("startup.programs", 2),
    ("startup.cache_misses", 2),
    ("startup.unattributed_s", 30.0 - 18.25)])
def test_reader_on_a_hand_made_training_run(name, want, monkeypatch):
    monkeypatch.setattr(startup_counters, "recorder", _Recorder)
    run = {"kind": "train", "setup_s": 30.0,
           "blocks": [(130.0, 135.0, 4), (135.0, 140.0, 4)]}
    assert layer_metrics.load(name).read(run) == pytest.approx(want)


def test_the_training_reader_asks_the_programs_own_recorder():
    from distributed_llm_training_and_inference_system_tpu.metrics.spans \
        import STARTUP
    assert startup_counters.recorder() is STARTUP
    snap = startup_counters.snapshot(
        {"kind": "train", "blocks": [(STARTUP.import_t0 - 1.0, 0.0, 4)]})
    assert snap["phases"] == {} and snap["programs"] == []
