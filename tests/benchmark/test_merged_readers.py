"""PR 59: the per-layer manifest lists a reader once. Where several entries
were the same reading of different cells they are ONE entry that lists the
cells, whose reader asks the run's family (``benchmark/families/<runner>.py``,
found by the name of the runner that produced the run) for the bytes, the
counters and the times it needs.

Held here: each merged reader, in each cell its entry lists, gives on a
fabricated run BIT FOR BIT what that cell's own reader of the commit before
(325b885: ``merged_readers_golden.json``, made by ``fabricated_runs.py``
against that commit) gave, for a run with every counter and for a run of a
program without the family's own; it is its family's function; it says
nothing for a run of a family that has no such function; a new family is a
new FILE; and the manifest, whole, has a reader for every entry and an
entry for every reader."""

import json
from pathlib import Path

import pytest

from benchmark import families, flops, layer_metrics, readers
from fabricated_runs import fabricated, serving_cells
from manifest_pins import (CELLS, CHAT, MANIFEST, MERGED_INTO, READERS, ROOT,
                           by_name)

GOLDEN = json.loads(
    (Path(__file__).parent / "merged_readers_golden.json").read_text())
FAMILY = serving_cells(MANIFEST)
CELL = {c["name"]: c for c in MANIFEST["workloads"]}
MERGED = sorted(set(MERGED_INTO.values()))
# the merged entries whose reader IS the family's function of this name ...
DELEGATES = {
    "kernels.moe_gmm_ms_per_decode_step": "moe_gmm_ms_per_decode_step",
    "moe.held_experts_hit_share": "held_experts_hit_share",
    "moe.experts_hit_share": "experts_hit_share",
    "moe.expert_load_imbalance": "expert_load_imbalance",
    "kernels.paged_attention_ms_per_decode_step":
        "paged_attention_ms_per_decode_step",
    "kernels.paged_attention_live_page_share":
        "paged_attention_live_page_share",
    "kernels.mla_attention_ms_per_decode_step":
        "mla_attention_ms_per_decode_step",
    "kernels.mla_attention_roofline_share": "mla_attention_roofline_share",
    "kernels.mla_live_page_share": "mla_live_page_share",
    "kernels.kda_decode_ms_per_decode_step": "kda_decode_ms_per_decode_step",
    "kernels.kda_decode_hbm_roofline_share": "kda_decode_hbm_roofline_share",
    "kernels.ssm_decode_ms_per_decode_step": "ssm_decode_ms_per_decode_step",
    "kernels.ssm_decode_hbm_roofline_share": "ssm_decode_hbm_roofline_share",
    "kernels.ssm_prefill_roofline_share": "ssm_prefill_roofline_share",
    "ssm.state_share_of_decode_bytes": "ssm_state_share_of_decode_bytes",
    "kv.prefix_cached_token_share": "prefix_cached_token_share",
}
# ... and those that hold one formula over the family's bytes and time
FORMULAS = {
    "serve_programs.decode_hbm_roofline_share":
        ("decode_step_bytes", "decode_step_ms"),
    "kernels.moe_gmm_hbm_roofline_share": ("expert_bytes", "moe_gmm_step_s"),
    "kernels.paged_attention_roofline_share":
        ("kv_bytes_per_token", "live_kv_tokens",
         "paged_attention_ms_per_decode_step"),
}
PAIRS = [(name, cell) for name in MERGED
         for cell in by_name()[name]["workloads"]]


def parents_name(name: str, cell: str) -> str:
    """What the commit before called this reading in this cell: the per-cell
    copy that listed the cell, or the entry itself."""
    [was] = [n for n in GOLDEN[cell]["full"]
             if MERGED_INTO.get(n, n) == name]
    return was


def read(name: str, cell: str, bare: bool = False, **stamp):
    run = dict(fabricated(CELL[cell], bare), **stamp)
    return layer_metrics.load(name).read(run)


# -- each merged reader against the reader it replaced ---------------------------

def test_every_pair_is_a_reading_the_commit_before_had():
    assert set(MERGED) == set(DELEGATES) | set(FORMULAS) | {
        "engine.prefill_ride_token_share"}
    assert len(PAIRS) >= 70 and all(cell in FAMILY for _, cell in PAIRS)
    for name, cell in PAIRS:
        parents_name(name, cell)


@pytest.mark.parametrize("which", ["full", "bare"])
@pytest.mark.parametrize("name,cell", PAIRS)
def test_the_merged_reader_gives_the_float_the_cells_own_reader_gave(
        name, cell, which):
    """``==`` on floats, no tolerance: the copy's body moved, unchanged.
    ``bare`` is a run of a program without the family's own counters and
    scopes, where most copies said nothing."""
    want = GOLDEN[cell][which][parents_name(name, cell)]
    assert read(name, cell, bare=which == "bare") == want
    if which == "full":
        assert want is not None and want > 0


def test_the_bare_runs_are_where_the_copies_said_nothing():
    silent = [(name, cell) for name, cell in PAIRS
              if GOLDEN[cell]["bare"][parents_name(name, cell)] is None]
    assert len(silent) >= 40
    # the family's own group gone, the guarded copies of a model's shares
    # said nothing though the counters they read are all there
    for name in ("moe.experts_hit_share", "moe.expert_load_imbalance",
                 "kernels.paged_attention_live_page_share"):
        assert (name, "lfm2-8b-a1b-16l.assist-batch-256") in silent
    for name in ("moe.held_experts_hit_share", "kv.prefix_cached_token_share",
                 "kernels.mla_live_page_share"):
        assert (name, "joyai-llm-flash-8l-ep2.agent-turns-64") in silent


# -- each merged reader against its family's file --------------------------------

@pytest.mark.parametrize("name,cell", [p for p in PAIRS if p[0] in DELEGATES])
def test_the_merged_reader_is_its_familys_function(name, cell):
    run = fabricated(CELL[cell])
    family = families.load(FAMILY[cell])
    assert families.of(run) is family
    want = getattr(family, DELEGATES[name])(run)
    assert layer_metrics.load(name).read(run) == want is not None


@pytest.mark.parametrize("name,cell", [p for p in PAIRS if p[0] in FORMULAS])
def test_the_merged_formula_takes_its_familys_bytes_and_time(name, cell):
    run = fabricated(CELL[cell])
    family = families.load(FAMILY[cell])
    peak = flops.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    *moved, took = (getattr(family, f)(run) for f in FORMULAS[name])
    seconds = took if FORMULAS[name][-1].endswith("_s") else took * 1e-3
    floor_s = moved[0] * (moved[1] if len(moved) > 1 else 1) / peak
    assert layer_metrics.load(name).read(run) == pytest.approx(
        100.0 * floor_s / seconds, rel=1e-12)
    assert 0 < floor_s < seconds * 10


@pytest.mark.parametrize("name,cell", [
    p for p in PAIRS if p[0] in DELEGATES or p[0] in FORMULAS])
def test_a_run_of_a_family_without_the_function_reads_nothing(name, cell):
    """A run no runner stamped, the training runner's, and every serving
    family that has no function for this reading: None, not another
    family's arithmetic over this run's counters. (The riding share is read
    from the engine's two counters and asks no family.)"""
    needs = [DELEGATES[name]] if name in DELEGATES else FORMULAS[name]
    strangers = [f for f in sorted(set(FAMILY.values()))
                 if not all(hasattr(families.load(f), n) for n in needs)]
    assert strangers or name == "serve_programs.decode_hbm_roofline_share"
    for stamp in (None, "train", "no-such-family", *strangers):
        assert read(name, cell, runner=stamp) is None, stamp


# -- a family is a FILE: a later PR adds one and edits none ----------------------

def test_a_new_family_is_one_new_file(tmp_path, monkeypatch):
    """A made-up family in a directory of its own: the merged entries read
    through it, and nothing that is there was edited (the loader finds a
    family by the runner's name alone)."""
    (tmp_path / "looped.py").write_text(
        '"""A stack walked several times over one set of weights."""\n'
        "def decode_step_ms(run):\n"
        "    return run['made_up']['step_ms']\n\n\n"
        "def decode_step_bytes(run):\n"
        "    return run['made_up']['passes'] * run['made_up']['bytes']\n")
    monkeypatch.setattr(families, "load", readers.loader(str(tmp_path)))
    run = dict(fabricated(CELL[CHAT]), runner="looped",
               made_up={"step_ms": 30.0, "passes": 4, "bytes": 4.914e9})
    share = layer_metrics.load("serve_programs.decode_hbm_roofline_share")
    assert share.read(run) == pytest.approx(
        100 * (4 * 4.914e9 / 819e9) / 30e-3)
    # what the made-up file does not name is left out, not guessed
    assert layer_metrics.load("kernels.moe_gmm_hbm_roofline_share").read(
        run) is None
    assert families.of(dict(run, runner="serve")) is None   # not in tmp_path


def test_the_family_is_resolved_in_one_place_and_never_from_a_name():
    """No reader and no family file names a cell, a configuration or a
    traffic mix; the readers reach a ``flops_*`` or ``*_counters`` module of
    one model only through ``benchmark/families/``."""
    names = (set(CELLS) | {c["name"] for c in MANIFEST["configs"]}
             | {c["traffic"] for c in MANIFEST["workloads"]}) - {"chat"}
    merged = [READERS / (n + ".py") for n in MERGED]
    files = merged + sorted((ROOT / "benchmark" / "families").glob("*.py"))
    for path in files:
        text = path.read_text()
        assert not [n for n in names if n in text], path.name
        assert 'run["config"]["name"]' not in text and "cell" not in (
            text.split('"""')[2]), path.name          # past the docstring
    for path in merged:
        text = path.read_text()
        assert "_counters" not in text.split('"""')[2], path.name
        assert "flops_" not in text.split('"""')[2], path.name
    for family in set(FAMILY.values()):
        assert (ROOT / "benchmark" / "families" / (family + ".py")).is_file()


# -- the manifest, whole ---------------------------------------------------------

def test_the_manifest_whole():
    per_layer = MANIFEST["per_layer"]
    names = [m["name"] for m in per_layer]
    assert len(names) == len(set(names))            # no name stands twice
    files = {p.stem for p in READERS.glob("*.py")} - {"__init__"}
    assert files == set(names)      # a reader for each entry, and no other
    assert len(per_layer) <= 99                     # ISSUE 59's bound
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in per_layer:
        assert m["moves"] in e2e, m["name"]
        for cell in m["workloads"]:
            assert cell in CELLS, (m["name"], cell)
            assert cell in e2e[m["moves"]].get("workloads", CELLS), (
                m["name"], cell)        # the cell reports what it moves
        assert m["workloads"] == [c for c in CELLS if c in m["workloads"]]
    for retired, merged in MERGED_INTO.items():
        assert retired not in names and merged in names, retired
        assert not (READERS / (retired + ".py")).exists(), retired


def test_a_merged_entry_moves_what_the_rule_says():
    """``tpot_p95_ms`` where the list holds the chat cell (which reports no
    tokens per second), else ``serve_tokens_per_s``; layer, unit, better
    and source are the un-prefixed entry's."""
    for name in MERGED:
        m = by_name()[name]
        assert m["moves"] == ("tpot_p95_ms" if CHAT in m["workloads"]
                              else "serve_tokens_per_s"), name
    whole = by_name()["serve_programs.decode_hbm_roofline_share"]
    assert set(whole["workloads"]) == set(FAMILY) - {
        "sdar-30b-a3b-7l.diffusion-batch-64"}      # every decode step's
    assert whole["unit"] == "%" and whole["better"] == "higher"


def test_the_prefill_programs_time_lists_the_cells_that_run_one():
    """The driver's note on every ledger line since PR 45: the three riding
    cells read nothing there and have left the list."""
    entry = by_name()["serve_programs.prefill_device_ms_per_ktok"]
    assert entry["workloads"] == [CHAT, "sdar-30b-a3b-7l.diffusion-batch-64"]
    text = (READERS / (entry["name"] + ".py")).read_text()
    assert "engine.prefill_ride_token_share" in text.split('"""')[1]
