"""`engine.prefill_ride_token_share.reason-batch` (PR 44): the hybrid cell's
entry of the riding share. The accepted reader under a name of its own, on
hand-made runs before and after, on a run of a program without the counter,
and the entry pinned BY NAME (never by its place in `per_layer`, nor by the
length of a list) and by the one cell that lists it."""

import json
from pathlib import Path

import pytest

from benchmark import layer_metrics
from benchmark.run import load_cell

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
METRIC = "engine.prefill_ride_token_share.reason-batch"
CELL = "nemotron-3-nano-30b-a3b-14l-ep2.reason-batch-128"
LAYER = "scheduler + engine (serve/scheduler.py, serve/engine.py)"


def stats(tokens: int, rode: int | None = None) -> dict:
    counter = {} if rode is None else {"prefill_ride_tokens": rode}
    return {"decode_steps": 100, "prefill_tokens": tokens, **counter}


@pytest.mark.parametrize("before,after,want", [
    # a window's ~370 prompts of ~170 tokens: before PR 44 the hybrid
    # engine has the counter and never rides; after it every prompt meets
    # a full batch
    (stats(21_000, 0), stats(84_512, 0), 0.0),
    (stats(21_000, 0), stats(84_512, 63_512), 100.0),
    # 3 prompts of the window admitted under the gate (the cold program)
    (stats(21_000, 0), stats(84_000, 63_000 - 3 * 170),
     100 * (1 - 3 * 170 / 63_000)),
    # the warm-up's prompts rode before the window: the window's share
    (stats(21_000, 20_000), stats(84_000, 83_000), 100.0),
    (stats(700, 100), stats(700, 100), None),    # no prefill in the window
    (stats(700), stats(900), None),              # a program from before PR 36
    (stats(700), stats(900, 100), None),
])
def test_reader_on_a_hand_made_run(before, after, want):
    run = {"stats": {"before": before, "after": after}}
    got = layer_metrics.load(METRIC).read(run)
    assert got == (pytest.approx(want) if want is not None else None)


def test_it_is_the_accepted_reader_under_another_name():
    assert (layer_metrics.load(METRIC).read
            is layer_metrics.load("engine.prefill_ride_token_share").read)


def test_the_entry_by_name_lists_the_hybrid_cell_alone():
    [entry] = [m for m in MANIFEST["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": LAYER,
        "moves": "serve_tokens_per_s", "workloads": [CELL]}
    # the layer is one the benchmark already names, letter for letter
    assert LAYER in {m["layer"] for m in MANIFEST["per_layer"]
                     if m["name"] != METRIC}


@pytest.mark.parametrize("cell", [c["name"] for c in MANIFEST["workloads"]])
def test_which_cells_report_it(cell):
    spec = load_cell(cell, MANIFEST)
    got = [m for m in spec["per_layer"] if m["name"] == METRIC]
    assert len(got) == (cell == CELL)
    assert all(m["moves"] in {e["name"] for e in spec["end_to_end"]}
               for m in got)


def test_the_accepted_entries_of_the_share_keep_their_cells():
    """The four entries PRs 36, 41 and 43 added, each with the cells it
    had."""
    listed = {m["name"]: m.get("workloads") for m in MANIFEST["per_layer"]}
    assert listed["engine.prefill_ride_token_share"] == [
        "mistral-7b-16l.batch-64", "olmoe-1b-7b-10l.moe-batch-64"]
    assert listed["engine.prefill_ride_token_share.chat"] == [
        "mistral-7b-16l.chat"]
    assert listed["engine.prefill_ride_token_share.doc-qa"] == [
        "xing4.0-29b-a4b-7l.doc-qa-64"]
    assert listed["engine.prefill_ride_token_share.reason-docs"] == [
        "kimi-linear-48b-a3b-12l-ep8.reason-docs-128"]


def test_the_hybrid_cell_reads_the_share_in_a_result_line():
    """The cell's line of a traced run carries the metric: 0 from the
    parent's counters, 100 from a window in which every prompt rode."""
    spec = load_cell(CELL, MANIFEST)
    [metric] = [m for m in spec["per_layer"] if m["name"] == METRIC]
    read = layer_metrics.load(metric["name"]).read
    parent = {"stats": {"before": stats(0, 0), "after": stats(63_512, 0)}}
    change = {"stats": {"before": stats(0, 0),
                        "after": stats(63_512, 63_512)}}
    assert (read(parent), read(change)) == (0.0, 100.0)
