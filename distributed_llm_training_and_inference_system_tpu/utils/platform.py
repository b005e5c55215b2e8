"""What a process needs to know about the device it runs on, in one place:
where the compile cache lives, which device it holds, that device's
published peaks, and the line a hot op prints when it picks its
implementation.

Importing this module does not import jax: ``cli/main.py`` calls
``enable_compile_cache()`` before any command does, and config-only
commands never pay the jax import.
"""

from __future__ import annotations

import logging
import os
import sys
from pathlib import Path
from typing import Optional

# <checkout>/.jax_cache — a FIXED path: the directory is part of the
# cache key's lookup, so one made from a temporary name, a pid or the
# time would never hit
DEFAULT_COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set (the program then names no
    other directory); otherwise the cache is ``<checkout>/.jax_cache``.
    Called before jax is imported the choice travels through the
    environment, which jax reads at import and child processes inherit;
    called after, the live config is updated too."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_COMPILE_CACHE_DIR)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path


def devices() -> list:
    """``jax.devices()`` under the ``llmctl.startup.backend`` span: the
    process's first call discovers the devices and initialises the backend
    (seconds on a TPU host; nothing where a caller, as the benchmark's
    harness, has asked JAX itself before)."""
    import jax

    from ..metrics.spans import STARTUP
    with STARTUP.phase("llmctl.startup.backend"):
        return jax.devices()


def device_summary() -> dict:
    """The device as JAX reports it to THIS process (imports jax and
    initialises the backend — only the process that owns the chip calls
    this)."""
    found = devices()
    return {"platform": found[0].platform,
            "kind": found[0].device_kind,
            "count": len(found)}


def device_line() -> str:
    d = device_summary()
    return (f"device: platform={d['platform']} kind={d['kind']!r} "
            f"count={d['count']}")


# Published per-chip peaks (bf16 TFLOP/s, HBM GB/s) from the Google Cloud
# TPU documentation, keyed by chip family with the ``device_kind``
# spellings jax reports ("TPU v5 lite" IS the v5e). The ONE table: `llmctl
# hw`, `plan verify`, `bench.py` and the trainer's MFU line all read it.
CHIP_PEAKS = {
    "v6e": ((918.0, 1640.0), ("v6e", "v6 lite", "trillium")),
    "v5p": ((459.0, 2765.0), ("v5p",)),
    "v5e": ((197.0, 819.0), ("v5e", "v5 lite", "v5lite")),
    "v4": ((275.0, 1228.0), ("v4",)),
}


class UnknownChipError(RuntimeError):
    """An accelerator whose ``device_kind`` is not in ``CHIP_PEAKS``."""


def chip_peaks(platform: str, device_kind: str) -> Optional[dict]:
    """Datasheet peaks of an accelerator, ``None`` on the CPU (which has no
    device peak: callers print no utilisation there). A kind the table
    does not know is an error, never a default."""
    if platform == "cpu":
        return None
    kind = device_kind.lower()
    for family, ((tflops, bw), aliases) in CHIP_PEAKS.items():
        if any(a in kind for a in aliases):
            return {"peak_bf16_tflops": tflops, "hbm_bw_gbps": bw,
                    "source": "datasheet", "chip_family": family}
    raise UnknownChipError(
        f"no published peaks for device_kind {device_kind!r} on platform "
        f"{platform!r}: add it to utils/platform.py CHIP_PEAKS with its "
        "source")


def kernel_impl(name: str = "pallas") -> str:
    """How a Pallas kernel runs in this process: compiled on the TPU
    (``name``), the interpreter anywhere else (``name-interpret``)."""
    import jax
    return name if jax.default_backend() == "tpu" else f"{name}-interpret"


_impl_logger = logging.getLogger("llmctl.impl")
_impl_reported: set = set()


def report_impl(op: str, impl: str, detail: str = "") -> None:
    """One log line saying which implementation a hot op resolved to.

    Ops call this while they are TRACED, so a choice is printed when a
    program that makes it is compiled — a kernel swapped for its
    reference is then visible in the log of the process that made the
    choice (`chip_smoke.py` fails a phase whose line names a reference or
    interpret implementation on the chip). The same (op, impl, shapes)
    line is printed once per process: a layer scan or a re-trace repeats
    the decision, not the news."""
    line = (op, impl, detail)
    if line in _impl_reported:
        return
    _impl_reported.add(line)
    _impl_logger.info("impl %s=%s%s", op, impl,
                      f" ({detail})" if detail else "")


def reported_impls() -> list[tuple[str, str, str]]:
    """Every (op, impl, detail) this process has reported, sorted: which
    implementation each traced program's hot ops took (the benchmark's
    short-conv runner holds a run to them: a gather route on the chip is
    ``correct: false``, not a slow number)."""
    return sorted(_impl_reported)
