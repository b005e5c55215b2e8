"""What both runners need from the process that holds the chip: the device
as JAX reports it, the refusal to measure anything but a TPU, the compile
cache, peak memory, and a traced stretch of the window."""

from __future__ import annotations

import contextlib
import shutil
import sys
import tempfile
import time

from benchmark import trace_reduce

PKG = "distributed_llm_training_and_inference_system_tpu"
TRACE_SECONDS = 5.0          # at most this much of a window is traced


def model_dict(config: dict) -> dict:
    """The configuration file's model keys (the published ``config.json``
    names) as ``ModelConfig.from_dict`` takes them."""
    d = {k: v for k, v in config.items()
         if not isinstance(v, (dict, list))}
    d["rope"] = {"base": config["rope_theta"]}
    d["name"] = config["name"]
    return d


def mark(what: str, since: float) -> None:
    """One line on stderr saying how far into the run a set-up phase ended:
    where set-up time goes is read from these."""
    print(f"[bench] +{time.monotonic() - since:7.2f}s {what}", file=sys.stderr)


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def start(chips: int, require_tpu: bool = True) -> dict:
    """Turn the compile cache on (``$JAX_COMPILATION_CACHE_DIR``, else the
    program's fixed ``<checkout>/.jax_cache``), initialise JAX and return
    the device line. Raises ``NoAccelerator`` rather than measure a CPU."""
    from importlib import import_module
    platform = import_module(f"{PKG}.utils.platform")
    cache = platform.enable_compile_cache() if require_tpu else None
    import jax
    if cache:
        # every program of a run is cached, also those that compile in
        # under a second: set-up is paid by every run of every later check
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if require_tpu and (info["platform"] != "tpu" or info["count"] < chips):
        raise NoAccelerator(
            f"this cell needs {chips} TPU chip(s); JAX reports {info}")
    print(f"[bench] device {info} compile cache {cache}", file=sys.stderr)
    return info


def memory_peak_bytes(n_devices: int) -> int:
    """Peak bytes in use on the fullest of the first ``n_devices`` chips
    (0 where the backend reports none, as on the CPU)."""
    import jax
    peak = 0
    for d in jax.devices()[:n_devices]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Trace:
    """``with Trace(on) as t: ...`` traces the body with the JAX profiler
    (python tracer off: it slows the host) and reduces the device planes.
    ``t.result`` is ``trace_reduce.reduce``'s dict, or {} when off."""

    def __init__(self, on: bool):
        self.on = on
        self.result: dict = {}
        self.listing: dict | None = None     # for --dump, read by hand
        self.t0 = self.t1 = 0.0

    def __enter__(self):
        if self.on:
            import jax
            self._dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._dir, profiler_options=opts)
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.t1 = time.monotonic()
        if not self.on:
            return False
        import jax
        try:
            jax.profiler.stop_trace()
            path = trace_reduce.find_xplane(self._dir)
            if path and exc[0] is None:
                profile = jax.profiler.ProfileData.from_file(path)
                self.result = trace_reduce.reduce(
                    trace_reduce.load(profile), self.t1 - self.t0)
                if self.result:
                    self.result["t0"], self.result["t1"] = self.t0, self.t1
                self.listing = trace_reduce.listing(profile)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False


@contextlib.contextmanager
def scratch_dir(prefix: str):
    """A directory under ``$TMPDIR`` that is gone when the run ends."""
    path = tempfile.mkdtemp(prefix=prefix)
    try:
        yield path
    finally:
        shutil.rmtree(path, ignore_errors=True)
