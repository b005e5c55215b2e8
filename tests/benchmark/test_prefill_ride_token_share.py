"""`engine.prefill_ride_token_share` (PR 36; one entry for every riding cell
since PR 59): the reader on hand-made runs of each cell's own windows, on a
run of a program without the counter, and the two entries that list it
(split by the end-to-end metric the cell reports), pinned by NAME and by
MEMBERSHIP. The per-cell aliases of PRs 41-55 (`.doc-qa`, `.reason-docs`,
`.reason-batch`, `.sessions`, `.chat-batch`, `.assist-batch`) are gone; their
cases are the cells' cases here."""

import pytest

from benchmark import layer_metrics
from benchmark.run import load_cell
from manifest_pins import (CELLS, MANIFEST, MERGED_INTO, READERS,
                           assert_lists, by_name)

METRIC = "engine.prefill_ride_token_share"
CHAT = "mistral-7b-16l.chat"
DOC_QA = "xing4.0-29b-a4b-7l.doc-qa-64"
REASON_BATCH = "nemotron-3-nano-30b-a3b-14l-ep2.reason-batch-128"
REASON_DOCS = "kimi-linear-48b-a3b-12l-ep8.reason-docs-128"
# the saturated cells whose layer table rides, and the prompt tokens of a
# window of each (my chip runs, PRs 36-55)
RIDING = {"mistral-7b-16l.batch-64": 96_570,
          "olmoe-1b-7b-10l.moe-batch-64": 96_570,
          REASON_BATCH: 63_512, DOC_QA: 101_250, REASON_DOCS: 150_794,
          "solar-open2-250b-4l-ep8.sessions-64": 283_000,
          "falcon-h1-34b-4l.chat-batch-128": 190_000,
          "lfm2-8b-a1b-16l.assist-batch-256": 500_000}
RETIRED = sorted(k for k, v in MERGED_INTO.items() if v == METRIC)
LAYER = "scheduler + engine (serve/scheduler.py, serve/engine.py)"


def stats(tokens: int, rode: int | None = None) -> dict:
    counter = {} if rode is None else {"prefill_ride_tokens": rode}
    return {"decode_steps": 100, "prefill_tokens": tokens, **counter}


WINDOWS = [
    # 290 prompts of 333 tokens, 6 of them prefilled cold
    ("batch-64", stats(5000, 2000),
     stats(5000 + 290 * 333, 2000 + 284 * 333), 100 * 284 / 290),
    ("under the gate", stats(0, 0), stats(50_000, 0), 0.0),   # all cold
    ("all rode", stats(0, 0), stats(1024, 1024), 100.0),
    ("no prefill", stats(700, 100), stats(700, 100), None),
    ("no counter", stats(700), stats(900), None),   # a parent before PR 36
    ("counter after only", stats(700), stats(900, 100), None),
]
CELL_WINDOWS = [
    # doc-qa-64: 450 tails of ~225 tokens behind their documents' pages, 4
    # of them admitted under the gate and prefilled by the suffix program
    ("doc-qa", stats(9000, 0), stats(9000 + 450 * 225, 446 * 225),
     100 * 446 / 450),
    ("doc-qa parent", stats(9000, 0), stats(110_000, 0), 0.0),
    ("doc-qa warm-up", stats(300, 300), stats(812, 812), 100.0),
    # reason-batch-128: ~370 prompts of ~170 tokens: before PR 44 the hybrid
    # engine has the counter and never rides; after it every prompt meets a
    # full batch
    ("reason-batch parent", stats(21_000, 0), stats(84_512, 0), 0.0),
    ("reason-batch", stats(21_000, 0), stats(84_512, 63_512), 100.0),
    # 3 prompts of the window admitted under the gate (the cold program)
    ("reason-batch 3 cold", stats(21_000, 0),
     stats(84_000, 63_000 - 3 * 170), 100 * (1 - 3 * 170 / 63_000)),
    # the warm-up's prompts rode before the window: the window's share
    ("reason-batch warm-up", stats(21_000, 20_000), stats(84_000, 83_000),
     100.0),
    # reason-docs-128: ~150k prompt tokens; before PR 43 the engine has the
    # counter and never rides
    ("reason-docs parent", stats(40_000, 0), stats(190_794, 0), 0.0),
    ("reason-docs", stats(40_000, 0), stats(190_794, 150_794), 100.0),
    # 9 questions of the window admitted under the gate
    ("reason-docs 9 cold", stats(40_000, 0),
     stats(190_000, 150_000 - 9 * 256), 100 * (1 - 9 * 256 / 150_000)),
]


def _ids(cases):
    return [c[0] for c in cases]


@pytest.mark.parametrize("name", [METRIC, METRIC + ".chat"])
@pytest.mark.parametrize("_,before,after,want", WINDOWS, ids=_ids(WINDOWS))
def test_reader_on_a_hand_made_run(name, _, before, after, want):
    run = {"stats": {"before": before, "after": after}}
    got = layer_metrics.load(name).read(run)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("_,before,after,want", CELL_WINDOWS,
                         ids=_ids(CELL_WINDOWS))
def test_reader_on_a_window_of_each_riding_cell(_, before, after, want):
    """The windows the retired aliases' tests fed them: the reader needs no
    family (no ``runner`` on the run), only the engine's two counters."""
    run = {"stats": {"before": before, "after": after}}
    assert layer_metrics.load(METRIC).read(run) == pytest.approx(want)


def test_the_chat_entry_is_the_same_reader_under_the_name_it_is_split_by():
    assert (layer_metrics.load(METRIC + ".chat").read
            is layer_metrics.load(METRIC).read)


@pytest.mark.parametrize("cell", RIDING)
def test_the_entry_by_name_lists_each_riding_cell(cell):
    entry = assert_lists(METRIC, cell, unit="%", better="higher",
                         source="program_counter", layer=LAYER,
                         moves="serve_tokens_per_s")
    assert CHAT not in entry["workloads"]
    # the layer is one the benchmark names elsewhere too, letter for letter
    assert LAYER in {m["layer"] for m in MANIFEST["per_layer"]
                     if not m["name"].startswith(METRIC)}


def test_the_chat_entry_by_name_is_judged_the_other_way_round():
    entry = assert_lists(METRIC + ".chat", CHAT, unit="%", better="lower",
                         source="program_counter", layer=LAYER,
                         moves="tpot_p95_ms")
    assert not set(entry["workloads"]) & set(RIDING)


@pytest.mark.parametrize("retired", RETIRED)
def test_a_retired_alias_has_neither_an_entry_nor_a_file(retired):
    assert retired not in by_name()
    assert not (READERS / (retired + ".py")).exists()


@pytest.mark.parametrize("name", [METRIC, METRIC + ".chat"])
@pytest.mark.parametrize("cell", CELLS)
def test_which_cells_report_it(cell, name):
    """A riding cell lists the share, the chat cell its own split, and a cell
    whose engine cannot ride (training, diffusion, the window that drafts)
    neither."""
    spec = load_cell(cell, MANIFEST)
    got = [m for m in spec["per_layer"] if m["name"] == name]
    want = cell in RIDING if name == METRIC else cell == CHAT
    assert len(got) == want
    assert all(m["moves"] in {e["name"] for e in spec["end_to_end"]}
               for m in got)


@pytest.mark.parametrize("cell,tokens", RIDING.items())
def test_the_cell_reads_the_share_in_a_result_line(cell, tokens):
    """The cell's line of a traced run carries the metric: 0 from a parent
    that has the counter and never rides, 100 from a window in which every
    prompt rode."""
    spec = load_cell(cell, MANIFEST)
    [metric] = [m for m in spec["per_layer"] if m["name"] == METRIC]
    read = layer_metrics.load(metric["name"]).read
    parent = {"stats": {"before": stats(0, 0), "after": stats(tokens, 0)}}
    change = {"stats": {"before": stats(0, 0),
                        "after": stats(tokens, tokens)}}
    assert (read(parent), read(change)) == (0.0, 100.0)
