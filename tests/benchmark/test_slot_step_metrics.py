"""The nine readers of the engine's slot-step ledger and of ``starved_s`` by
span (PR 51), each on a hand-made run dict, and their entries in
``BENCHMARK.json`` pinned by NAME: nothing here pins a position in a list or
a count of its entries."""

import pytest

from benchmark import layer_metrics, slot_step_counters
from benchmark.run import load_cell
from manifest_pins import MANIFEST, assert_lists

LAYER = "scheduler + engine (serve/scheduler.py, serve/engine.py)"
EIGHT = ["mistral-7b-16l.batch-64", "olmoe-1b-7b-10l.moe-batch-64",
         "nemotron-3-nano-30b-a3b-14l-ep2.reason-batch-128",
         "xing4.0-29b-a4b-7l.doc-qa-64",
         "kimi-linear-48b-a3b-12l-ep8.reason-docs-128",
         "sdar-30b-a3b-7l.diffusion-batch-64",
         "solar-open2-250b-4l-ep8.sessions-64",
         "falcon-h1-34b-4l.chat-batch-128"]
SEVEN = [c for c in EIGHT if c != "sdar-30b-a3b-7l.diffusion-batch-64"]
NINE = ["mistral-7b-16l.chat"] + EIGHT
# name: (unit, better, moves, cells)
ENTRIES = {
    "engine.slot_steps.useful_share":
        ("%", "higher", "serve_tokens_per_s", EIGHT),
    "engine.slot_steps.overrun_share":
        ("%", "lower", "serve_tokens_per_s", EIGHT),
    "engine.slot_steps.prompt_wait_share":
        ("%", "lower", "serve_tokens_per_s", EIGHT),
    "engine.slot_steps.empty_share":
        ("%", "lower", "serve_tokens_per_s", EIGHT),
    "engine.wall_ms_per_decode_step":
        ("ms", "lower", "serve_tokens_per_s", EIGHT),
    "engine.ledger_tokens_per_s":
        ("tokens/s", "higher", "serve_tokens_per_s", EIGHT),
    "engine.seat_to_first_token_mean_ms":
        ("ms", "lower", "serve_tokens_per_s", SEVEN),
    "engine.starved_ms_per_decode_step.deliver":
        ("ms", "lower", "tpot_p95_ms", NINE),
    "engine.starved_ms_per_decode_step.dispatch":
        ("ms", "lower", "tpot_p95_ms", NINE),
}


@pytest.mark.parametrize("name", list(ENTRIES))
def test_the_entry_is_as_the_issue_names_it(name):
    unit, better, moves, cells = ENTRIES[name]
    for cell in cells:      # a subset: a later cell may join the list
        assert_lists(name, cell, unit=unit, better=better,
                     source="program_counter", layer=LAYER, moves=moves)
    reporting = next(e for e in MANIFEST["end_to_end"] if e["name"] == moves)
    assert set(cells) <= set(reporting["workloads"])
    for cell in cells:
        assert name in {m["name"] for m in load_cell(cell)["per_layer"]}
    assert callable(layer_metrics.load(name).read)


def _stats(steps, clock, classes, first, tokens, starved=None):
    out = {"decode_steps": steps, "clock_s": clock,
           "slot_steps": dict(zip(slot_step_counters.CLASSES, classes),
                              first_tokens=first, tokens_credited=tokens)}
    if starved is not None:
        out.update(starved_by_phase=starved, starved_s=sum(starved.values()))
    return out


def _run():
    """A window of 1,000 steps of 32 slots in 20 s; a traced stretch of 250
    steps inside it."""
    before = _stats(100, 500.0, (2000, 400, 600, 200), 50, 2050)
    after = _stats(1100, 520.0, (2000 + 27200, 400 + 1600, 600 + 2240,
                                 200 + 960), 50 + 160, 2050 + 27360)
    t0 = _stats(300, 501.0, (0,) * 4, 0, 0, starved={
        "llmctl.engine.apply": 1.0, "llmctl.engine.deliver": 2.0,
        "(no span)": 0.5, "llmctl.engine.admit": 0.25})
    t1 = _stats(550, 506.0, (0,) * 4, 0, 0, starved={
        "llmctl.engine.apply": 1.05, "llmctl.engine.deliver": 2.2,
        "llmctl.engine.snapshot.take": 0.025, "(no span)": 0.6,
        "llmctl.engine.admit": 0.275, "llmctl.engine.decode.submit": 0.1,
        "llmctl.engine.decode.wait": 0.01})
    return {"stats": {"before": before, "after": after},
            "trace_stats": {"before": t0, "after": t1},
            "serve_cfg": {"max_batch_size": 32}, "window": (10.0, 30.0),
            "stamps": {"records": [
                {"chunks": [9.0, 11.0, 29.5, 30.5], "batch_sizes": [8] * 4},
                {"chunks": [12.0], "batch_sizes": [27000]}]}}


WANT = {
    "engine.slot_steps.useful_share": 100 * 27200 / 32000,
    "engine.slot_steps.overrun_share": 100 * 1600 / 32000,
    "engine.slot_steps.prompt_wait_share": 100 * 2240 / 32000,
    "engine.slot_steps.empty_share": 100 * 960 / 32000,
    "engine.wall_ms_per_decode_step": 20.0,
    "engine.ledger_tokens_per_s": 27360 / 20.0,
    "engine.seat_to_first_token_mean_ms": 2240 * 20.0 / 160,
    "engine.starved_ms_per_decode_step.deliver":
        1e3 * (0.05 + 0.2 + 0.025) / 250,
    "engine.starved_ms_per_decode_step.dispatch": 1e3 * (0.025 + 0.1) / 250,
}


@pytest.mark.parametrize("name", list(ENTRIES))
def test_the_reader_on_a_hand_made_run(name, capsys):
    assert layer_metrics.load(name).read(_run()) == pytest.approx(WANT[name])
    said = capsys.readouterr().err
    if "starved" in name:       # the whole table, the waits and the rest too
        assert "(no span) 0.100000" in said
        assert "llmctl.engine.decode.wait 0.010000" in said
        assert "add to 0.510000 of starved_s 0.510000" in said
    if name == "engine.ledger_tokens_per_s":
        # the clients' count of the same window beside the ledger's, and
        # the ledger's raw counts against steps x slots
        assert "the clients counted 27016 in 20.000 s" in said
        assert "add to 32000 of 1000 steps x 32 slots = 32000" in said


def test_the_four_shares_add_to_a_hundred_only_if_the_ledger_does():
    run = _run()
    names = [n for n in ENTRIES if "slot_steps" in n]
    assert sum(layer_metrics.load(n).read(run)
               for n in names) == pytest.approx(100.0)
    run["stats"]["after"]["slot_steps"]["overrun"] -= 320    # a leak
    assert sum(layer_metrics.load(n).read(run)
               for n in names) == pytest.approx(99.0)


@pytest.mark.parametrize("name", list(ENTRIES))
def test_a_program_without_the_keys_reads_nothing(name):
    """The parent commit has neither ``slot_steps`` nor
    ``starved_by_phase``: the reader returns None and the line leaves the
    metric out; so does a window (a stretch) without a decode step."""
    read = layer_metrics.load(name).read
    old = _run()
    for pair in (old["stats"], old["trace_stats"]):
        for half in pair.values():
            half.pop("slot_steps")
            half.pop("starved_by_phase", None)
    assert read(old) is None
    still = _run()
    for pair in (still["stats"], still["trace_stats"]):
        pair["after"]["decode_steps"] = pair["before"]["decode_steps"]
    assert read(still) is None
    if name == "engine.seat_to_first_token_mean_ms":
        nobody = _run()                     # no first token in the window
        nobody["stats"]["after"]["slot_steps"]["first_tokens"] = 50
        assert read(nobody) is None
