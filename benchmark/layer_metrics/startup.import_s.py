"""Seconds of ``llmctl.startup.import``: from the program's package's first
line to the end of its entry module's import (``serve/server.py``,
``runtime/engine.py``), less the start-up spans inside. Under the benchmark,
whose harness imports jax and asks for the devices in between, those lie in
it too."""
from benchmark import startup_counters


def read(run):
    return startup_counters.phase_seconds(run, startup_counters.IMPORT)
