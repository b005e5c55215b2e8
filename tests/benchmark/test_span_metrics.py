"""The readers of the program's own spans and counters (PR 25), on hand-made
runs: what they compute, and that a run of a program without the keys (every
commit before the PR) reads as None and the metric is left out of the line.
"""

import pytest

from benchmark import layer_metrics, span_counters
from benchmark.run import result_line
from manifest_pins import MANIFEST, assert_lists, entry, listed_by

NEW = ["scheduler.queue_wait_p95_ms", "engine.host_ms_per_decode_step",
       "engine.prefill_stall_ms_per_decode_step",
       "engine.device_starved_share",
       "kernels.paged_attention_ms_per_decode_step"]
LE = [1, 2, 5, 10, 20, 50, 100, 150, 200, 300, 400, 500, 750, 1000, 1500,
      2500, 5000, "+inf"]


def hist(counts: dict, total_ms: float = 0.0) -> dict:
    c = [counts.get(b, 0) for b in LE]
    return {"le": LE, "counts": c, "sum": total_ms, "n": sum(c)}


def stats(clock, steps, starved, phases, queue=None) -> dict:
    return {"clock_s": clock, "decode_steps": steps, "starved_s": starved,
            "phases": {f"llmctl.engine.{k}": {"s": v, "n": 1}
                       for k, v in phases.items()},
            "queue_wait_ms": queue or hist({})}


def run_with(before, after, trace=None) -> dict:
    return {"stats": {"before": before, "after": after},
            "trace_stats": {"before": before, "after": after},
            "trace": trace or {},
            "runner": "serve",      # whose family file the merged readers ask
            "serve_cfg": {"decode_steps_per_dispatch": 8,
                          "max_batch_size": 32}}


BEFORE = stats(100.0, 1000, 2.0,
               {"admit": 1.0, "apply": 3.0, "prefill.wait": 4.0,
                "decode.wait": 50.0, "idle": 9.0},
               hist({50: 10, 100: 5}, 600.0))
AFTER = stats(105.0, 1100, 2.25,
              {"admit": 1.01, "apply": 3.2, "prefill.wait": 4.4,
               "decode.wait": 53.5, "idle": 9.0, "prefill.host": 0.09,
               "capacity": 0.02, "decode.submit": 0.05, "deliver": 0.03,
               "prefill.key_wait": 0.7},
              hist({50: 10, 100: 45, 300: 40, 400: 18, 500: 2}, 20600.0))
TRACE = {"programs": {"decode": (12, 3.4), "prefill": (5, 0.3)},
         "busy_s": 4.7, "window_s": 5.0, "idle_gaps": [],
         "device_ops": [["copy.190", 0.5],
                        ["paged_attention.7:tpu_custom_call", 0.24],
                        ["paged_attention_mq.2:tpu_custom_call", 0.9]]}


@pytest.mark.parametrize("metric,want", [
    # admit .01 + prefill.host .09 + capacity .02 + decode.submit .05 +
    # apply .2 + deliver .03 = 0.4 s over 100 steps; prefill.key_wait (.7)
    # is a wait for the device and belongs to neither
    ("engine.host_ms_per_decode_step", 4.0),
    ("engine.prefill_stall_ms_per_decode_step", 4.0),
    ("engine.device_starved_share", 5.0),
    # 100 admissions: 40 <= 100 ms, 40 in (200, 300], 18 in (300, 400], 2 in
    # (400, 500]; rank 95 lies 15 of 18 into (300, 400]
    ("scheduler.queue_wait_p95_ms", 300.0 + 100.0 * 15 / 18),
    ("kernels.paged_attention_ms_per_decode_step", 1e3 * 0.24 / (12 * 8)),
])
def test_reader_on_a_hand_made_run(metric, want):
    run = run_with(BEFORE, AFTER, TRACE)
    assert layer_metrics.load(metric).read(run) == pytest.approx(want)


@pytest.mark.parametrize("metric", NEW)
def test_reader_finds_nothing_in_a_run_of_the_parent(metric):
    """The parent's stats() has none of the keys and its kernels no names."""
    old = {"decode_steps": 1000, "padded_slot_steps": 3, "admitted": 7}
    run = run_with(old, dict(old, decode_steps=1100),
                   {"programs": {"decode": (12, 3.4)},
                    "device_ops": [["closed_call.12:tpu_custom_call", 0.3]]})
    assert layer_metrics.load(metric).read(run) is None
    assert layer_metrics.load(metric).read(run_with(old, old)) is None


@pytest.mark.parametrize("ops,said", [
    (TRACE["device_ops"], False),
    ([["copy.190", 0.5], ["fusion.4", 0.23]], True),   # under the tenth
    ([], False),                                       # no trace at all
])
def test_kernel_reader_says_when_its_kernel_is_off_the_list(ops, said,
                                                            capsys):
    run = run_with(BEFORE, AFTER, dict(TRACE, device_ops=ops))
    got = layer_metrics.load(NEW[4]).read(run)
    assert (got is None) == (not ops or said)
    assert ("no paged_attention operation" in capsys.readouterr().err) == said


@pytest.mark.parametrize("counts,want", [
    ({1: 20}, 0.95),                       # inside the first bucket, from 0
    ({"+inf": 3}, 5000.0),                 # no upper bound: its lower one
    ({50: 19, "+inf": 1}, 50.0),           # rank 19 of 20 ends a bucket
    ({}, None),                            # nothing admitted in the window
])
def test_queue_wait_percentile_at_the_edges(counts, want):
    run = run_with(stats(0.0, 0, 0.0, {}), stats(1.0, 8, 0.0, {},
                                                 hist(counts)))
    got = layer_metrics.load("scheduler.queue_wait_p95_ms").read(run)
    assert got == (pytest.approx(want) if want is not None else None)


def test_no_steps_or_no_clock_reads_as_nothing():
    same = run_with(AFTER, AFTER, TRACE)
    for metric in NEW[1:4]:
        assert layer_metrics.load(metric).read(same) is None
    assert span_counters.phase_seconds(same["trace_stats"],
                                       span_counters.HOST_PHASES) == 0.0


@pytest.mark.parametrize("cell,names", [
    ("mistral-7b-16l.chat", NEW),
    ("mistral-7b-16l.batch-64", NEW[1:]),
    ("internlm2-1.8b-6l.pretrain-4k", []),
])
def test_the_entries_by_name_list_the_cells_and_name_layers_that_exist(
        cell, names):
    """By name and by membership (PR 59): where an entry stands in the list,
    and which later cells joined it, is nobody's pin."""
    others = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in NEW}
    for name in NEW:
        m = entry(name)
        assert m["layer"] in others and m["moves"] == "tpot_p95_ms"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for name in names:
        assert_lists(name, cell)
    assert listed_by(cell) & set(NEW) == set(names)


def test_result_line_leaves_out_what_the_parent_cannot_report():
    old = {"decode_steps": 1000}
    run = dict(run_with(old, dict(old, decode_steps=1100)), kind="train",
               blocks=[(0.0, 1.0, 8)], check={"ok": True}, all_finite=True,
               device={"platform": "tpu"}, memory_peak_bytes=1,
               compiled_in_window=0)
    metrics = [m for m in MANIFEST["per_layer"] if m["name"] in NEW]
    line = result_line(run, metrics, layer_metrics.load, traced=True)
    assert line["metrics"] == {}
    run.update(run_with(BEFORE, AFTER, TRACE))
    line = result_line(run, metrics, layer_metrics.load, traced=True)
    assert set(line["metrics"]) == set(NEW)
