"""Metric readers are files named after their metric (``tpot_p95_ms.py``,
``loadgen.lateness_p95_ms.py``): a later PR adds a metric by adding a file.
Names hold dots, so the files are loaded by path and not by ``import``."""

from __future__ import annotations

import importlib.util
import os


def loader(directory: str):
    cache: dict = {}

    def load(metric: str):
        if metric not in cache:
            path = os.path.join(directory, metric + ".py")
            if not os.path.isfile(path):
                raise FileNotFoundError(f"no reader for metric {metric!r}: "
                                        f"{path} does not exist")
            spec = importlib.util.spec_from_file_location(
                "benchmark_reader_" + metric.replace(".", "_"), path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            cache[metric] = module
        return cache[metric]
    return load
