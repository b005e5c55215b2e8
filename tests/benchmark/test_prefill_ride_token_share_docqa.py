"""`engine.prefill_ride_token_share.doc-qa` (PR 41): the latent cell's entry
of the riding share. The accepted reader under a name of its own, on
hand-made runs, on a run of a program without the counter, and the entry
pinned by name, by place (the last of `per_layer`) and by the one cell that
lists it. (The accepted `test_prefill_ride_token_share.py` pins the two
entries PR 36 added as the ONLY ones of that prefix, and the latent cell as
listing none: those two cases fail from this PR on, as the pins of PRs 25
and 27 do; a `benchmark` PR may edit them.)"""

import json
from pathlib import Path

import pytest

from benchmark import layer_metrics
from benchmark.run import load_cell

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
METRIC = "engine.prefill_ride_token_share.doc-qa"
CELL = "xing4.0-29b-a4b-7l.doc-qa-64"
LAYER = "scheduler + engine (serve/scheduler.py, serve/engine.py)"


def stats(tokens: int, rode: int | None = None) -> dict:
    counter = {} if rode is None else {"prefill_ride_tokens": rode}
    return {"decode_steps": 100, "prefill_tokens": tokens, **counter}


@pytest.mark.parametrize("before,after,want", [
    # 450 tails of ~225 tokens behind their documents' pages, 4 of them
    # admitted under the gate and prefilled by the suffix program
    (stats(9000, 0), stats(9000 + 450 * 225, 446 * 225), 100 * 446 / 450),
    (stats(9000, 0), stats(110_000, 0), 0.0),    # the parent: nothing rides
    (stats(300, 300), stats(812, 812), 100.0),
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


def test_the_entry_is_the_last_and_lists_the_latent_cell_alone():
    assert MANIFEST["per_layer"][-1] == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": LAYER,
        "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert [m["name"] for m in MANIFEST["per_layer"]].count(METRIC) == 1


@pytest.mark.parametrize("cell", [c["name"] for c in MANIFEST["workloads"]])
def test_which_cells_report_it(cell):
    spec = load_cell(cell, MANIFEST)
    got = [m for m in spec["per_layer"] if m["name"] == METRIC]
    assert len(got) == (cell == CELL)
    assert all(m["moves"] in {e["name"] for e in spec["end_to_end"]}
               for m in got)


def test_the_accepted_entries_of_the_share_are_as_they_were():
    """PR 36's two entries, untouched beside the new one."""
    accepted = {m["name"]: m.get("workloads") for m in MANIFEST["per_layer"]
                if m["name"].startswith("engine.prefill_ride_token_share")
                and m["name"] != METRIC}
    assert accepted == {
        "engine.prefill_ride_token_share": [
            "mistral-7b-16l.batch-64", "olmoe-1b-7b-10l.moe-batch-64"],
        "engine.prefill_ride_token_share.chat": ["mistral-7b-16l.chat"]}
