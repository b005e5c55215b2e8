"""Shared device-synchronised timing for every benchmark path.

One methodology used by comms/bench, cli bench, hw benchmark, and the
autotuner — so a change to how we measure is a change everywhere.

Two rules (BASELINE.md round-2 notes; neither re-measured on a directly
attached chip, where ``block_until_ready`` is expected to be a sound
fence — removing the value fetch is left to a later simplicity pass):

- the fence fetches a VALUE that depends on the result (a one-element
  reduction — never the full array, which would time the transfer, not
  the compute): a dependent value cannot arrive before the computation
  it depends on, whatever the backend does with ``block_until_ready``.
- per-call sync pays a host round trip per call, so calls are timed in
  pipelined WINDOWS with one fence per window; the best window is
  reported.
"""

from __future__ import annotations

import time
from typing import Callable


def _fence(out) -> None:
    """Block until *out* is actually computed.

    Fetches the value of a REDUCTION over the result: a scalar that reads
    the whole buffer cannot arrive before the buffer is computed (the
    same fence bench.py validates against physically-possible MFU
    ceilings)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    leaves = [x for x in jax.tree_util.tree_leaves(out)
              if hasattr(x, "dtype")]
    if not leaves:
        return
    leaf = leaves[0]
    if getattr(leaf, "ndim", 0) == 0:
        np.asarray(leaf)
    else:
        float(jnp.sum(jnp.abs(leaf.astype(jnp.float32))
                      if jnp.issubdtype(leaf.dtype, jnp.floating)
                      else leaf.astype(jnp.float32)))


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 10,
            windows: int = 3) -> float:
    """Best-window mean wall-clock seconds per call, value-fenced.

    ``iters`` is the TOTAL timed-call budget (callers like the autotuner
    size it per candidate config); it is split across ``windows``."""
    out = None
    for _ in range(max(warmup, 1)):
        out = fn(*args)
    _fence(out)
    # the fence itself costs a host round trip (noisy; not re-measured on
    # a directly attached chip); estimate it (median of 3 on the already-computed
    # result) and subtract, flooring at 20% of the raw window so noise can
    # never produce absurd sub-ns "timings"
    costs = []
    for _ in range(3):
        t0 = time.perf_counter()
        _fence(out)
        costs.append(time.perf_counter() - t0)
    fence_cost = sorted(costs)[1]
    windows = max(min(windows, iters), 1)
    per_window = max(iters // windows, 1)
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(per_window):
            out = fn(*args)
        _fence(out)
        raw = time.perf_counter() - t0
        elapsed = max(raw - fence_cost, 0.2 * raw)
        best = min(best, elapsed / per_window)
    return best
