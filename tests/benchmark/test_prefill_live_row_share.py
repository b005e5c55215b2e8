"""`serve_programs.prefill_live_row_share` (PR 30): the reader on hand-made
runs, on a run of a program without the counter, and the entry that lists
it, pinned by name and not by place."""

import pytest

from benchmark import layer_metrics
from benchmark.run import load_cell
from manifest_pins import MANIFEST, assert_lists, entry

METRIC = "serve_programs.prefill_live_row_share"
SERVING = ["mistral-7b-16l.chat", "mistral-7b-16l.batch-64",
           "olmoe-1b-7b-10l.moe-batch-64"]


def stats(tokens: int, rows: int | None = None) -> dict:
    counter = {} if rows is None else {"prefill_padded_tokens": rows}
    return {"decode_steps": 100, "prefill_tokens": tokens, **counter}


@pytest.mark.parametrize("before,after,want", [
    # 48 prefills of 333 tokens on average, in programs of 455 rows
    (stats(5000, 8192), stats(5000 + 48 * 333, 8192 + 48 * 455),
     100 * 333 / 455),
    (stats(0, 0), stats(1024, 1024), 100.0),    # every prompt on a rung
    (stats(0, 0), stats(200, 512), 100 * 200 / 512),
    (stats(700, 1024), stats(700, 1024), None),  # no prefill in the window
    (stats(700), stats(900), None),              # a parent without the counter
    (stats(700), stats(900, 256), None),
])
def test_reader_on_a_hand_made_run(before, after, want):
    run = {"stats": {"before": before, "after": after}}
    got = layer_metrics.load(METRIC).read(run)
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_entry_names_the_serve_programs_layer_and_the_serving_cells():
    per_ktok = entry("serve_programs.prefill_device_ms_per_ktok")
    for cell in SERVING:
        assert_lists(METRIC, cell, unit="%", better="higher",
                     source="program_counter", layer=per_ktok["layer"],
                     moves="tpot_p95_ms")


@pytest.mark.parametrize("cell,listed", [
    *[(c, True) for c in SERVING],
    ("internlm2-1.8b-6l.pretrain-4k", False),
    ("internlm2-1.8b.pretrain-4k-fsdp4", False),
])
def test_which_cells_report_it(cell, listed):
    spec = load_cell(cell, MANIFEST)
    assert (METRIC in [m["name"] for m in spec["per_layer"]]) == listed
    if listed:
        assert "tpot_p95_ms" in [m["name"] for m in spec["end_to_end"]]
