"""`kernels.paged_attention_live_page_share` (PR 28): the reader on
hand-made runs, on a run of a program without the counters, and the entry
that lists it, pinned by name and not by place."""

import pytest

from benchmark import layer_metrics
from benchmark.run import load_cell
from manifest_pins import MANIFEST, assert_lists, entry

METRIC = "kernels.paged_attention_live_page_share"
SERVING = ["mistral-7b-16l.chat", "mistral-7b-16l.batch-64",
           "olmoe-1b-7b-10l.moe-batch-64"]


def run_with(before_kv: dict, after_kv: dict) -> dict:
    return {"stats": {"before": {"kv": before_kv}, "after": {"kv": after_kv}},
            "runner": "serve"}


def kv(live=None, table=None) -> dict:
    counters = {} if table is None else {"live_pages": live,
                                         "table_pages": table}
    return {"num_pages": 715, "free_pages": 100, **counters}


@pytest.mark.parametrize("before,after,want", [
    # 50 dispatches of 32 slots x 32 pages; 77 pages walked in each
    (kv(1000, 10240), kv(1000 + 50 * 77, 10240 + 50 * 1024), 100 * 77 / 1024),
    (kv(0, 0), kv(1024, 1024), 100.0),          # every slot at full length
    (kv(5, 64), kv(5, 64), None),               # no dispatch in the window
    (kv(), kv(), None),                         # a parent without counters
    (kv(), kv(10, 64), None),
])
def test_reader_on_a_hand_made_run(before, after, want):
    got = layer_metrics.load(METRIC).read(run_with(before, after))
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_entry_names_the_kernels_layer_and_the_serving_cells():
    kernel_ms = entry("kernels.paged_attention_ms_per_decode_step")
    for cell in SERVING:
        assert_lists(METRIC, cell, unit="%", better="higher",
                     source="program_counter", layer=kernel_ms["layer"],
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
