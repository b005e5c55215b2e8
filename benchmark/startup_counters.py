"""What the readers of the start-up metrics (``startup.*``, all of which move
``setup_s``) share: the program's own account of its start-up
(``metrics/spans.py StartupRecorder``: ``llmctl.startup.*`` phases and the
compile ledger, one ``programs`` entry a program's first call) as it stood at
the window's first instant.

A serving run carries it in ``run["stats"]["before"]["startup"]``: the
runner snapshots ``engine.stats()`` where set-up ends. A training run has no
such snapshot, so the reader asks the program's recorder in this process and
keeps what had ended by the first block's first stamp. A program without the
recorder (every commit before PR 35) gives ``None`` and the line leaves the
metric out.
"""

from __future__ import annotations

from importlib import import_module

from benchmark import harness

PREFIX = "llmctl.startup."
IMPORT = PREFIX + "import"
ENGINE_PREFIX = "llmctl.engine."
ENGINE_IDLE = ENGINE_PREFIX + "idle"
UNSCOPED = "(unscoped)"


def recorder():
    """The program's ``STARTUP``, or None where it has none."""
    try:
        spans = import_module(f"{harness.PKG}.metrics.spans")
    except ImportError:
        return None
    return getattr(spans, "STARTUP", None)


def snapshot(run: dict) -> dict | None:
    """``StartupRecorder.snapshot()`` cut at the window's first instant."""
    if run["kind"] == "serve":
        return run["stats"]["before"].get("startup")
    rec = recorder()
    return rec.snapshot(until=run["blocks"][0][0]) if rec else None


def phase_seconds(run: dict, name: str) -> float | None:
    snap = snapshot(run)
    if snap is None:
        return None
    return snap["phases"].get(name, {"s": 0.0})["s"]


def programs(run: dict) -> list | None:
    """Ledger entries of the programs first called before the window, those
    compiled under no program span (``(unscoped)``) among them."""
    snap = snapshot(run)
    return None if snap is None else snap["programs"]


def ledger_seconds(run: dict, fields) -> float | None:
    entries = programs(run)
    if entries is None:
        return None
    return sum(p[f] for p in entries for f in fields)


def named_seconds(run: dict) -> float | None:
    """Self seconds of every ``llmctl.startup.*`` span that had ended by the
    window (import, backend, params, restore, pools, data, program): they
    nest by self time, so the sum counts no second twice."""
    snap = snapshot(run)
    if snap is None:
        return None
    return sum(cell["s"] for name, cell in snap["phases"].items()
               if name.startswith(PREFIX))


def engine_work_seconds(run: dict) -> float | None:
    """What the engine thread did before the window, compiles apart: self
    seconds of every ``llmctl.engine.*`` span but ``idle`` at the window's
    start, less the program spans nested in them (before the window a serve
    program first runs on the engine thread, inside ``prefill.host`` or
    ``decode.submit``): the check's and the warm-up's requests."""
    entries = programs(run)
    before = run["stats"]["before"] if run["kind"] == "serve" else {}
    if entries is None or "phases" not in before:
        return None
    engine = sum(cell["s"] for name, cell in before["phases"].items()
                 if name.startswith(ENGINE_PREFIX) and name != ENGINE_IDLE)
    return engine - sum(p["s"] for p in entries if p["name"] != UNSCOPED)
