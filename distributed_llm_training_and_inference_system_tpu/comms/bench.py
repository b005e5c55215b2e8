"""Real collective microbenchmarks over a live mesh.

Replaces the reference's *simulated* CommunicationTuner
(reference autotuning.py:203-257: base_time x backend-factor x bucket-factor
+ gaussian noise) and its stub `bench comms`
(reference cli/commands/bench.py:51-64). Every number here is a measured
wall-clock over actual `jax.lax` collectives dispatched through shard_map on
the current mesh — fake CPU devices in tests, real ICI on a pod.
"""

from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import collectives as cc


from ..utils.timing import time_fn as _time_fn


def _payload(mesh: Mesh, axis: str, size_mb: float, dtype=jnp.float32):
    n = mesh.shape[axis]
    elems = int(size_mb * 1e6 / jnp.dtype(dtype).itemsize)
    cols = 128
    # rows divisible by n^2: the local shard (rows/n) must itself split n
    # ways for the in-shard reduce_scatter pattern
    rows = max(elems // cols // (n * n), 1) * n * n
    x = jnp.ones((rows, cols), dtype)
    return jax.device_put(x, NamedSharding(mesh, P(axis, None)))


def bench_collective(mesh: Mesh, axis: str, pattern: str,
                     size_mb: float = 16.0, dtype=jnp.float32,
                     iters: int = 10) -> dict:
    """Measure one collective pattern over *axis*. Returns timing + the
    standard algorithmic-bandwidth figure (bus BW for ring algorithms)."""
    n = mesh.shape[axis]
    x = _payload(mesh, axis, size_mb, dtype)
    spec = P(axis, None)

    if pattern == "allreduce":
        body = lambda v: cc.allreduce_sum(v, axis)
        out_spec = spec
        # ring allreduce moves 2*(n-1)/n of the buffer per device
        algo_factor = 2 * (n - 1) / n if n > 1 else 1.0
    elif pattern == "all_gather":
        body = lambda v: cc.all_gather(v, axis)
        out_spec = P(None, None)
        algo_factor = (n - 1) / n if n > 1 else 1.0
    elif pattern == "reduce_scatter":
        body = lambda v: cc.reduce_scatter(v, axis)
        out_spec = spec
        algo_factor = (n - 1) / n if n > 1 else 1.0
    elif pattern == "ppermute":
        body = lambda v: cc.ring_shift(v, axis)
        out_spec = spec
        algo_factor = 1.0 / n
    elif pattern == "all_to_all":
        # split along rows (payload guarantees rows % n^2 == 0); splitting
        # the fixed 128-column dim would break for axes wider than 128
        body = lambda v: cc.all_to_all(v, axis, split_dim=0, concat_dim=1)
        out_spec = spec
        algo_factor = (n - 1) / n if n > 1 else 1.0
    else:
        raise ValueError(f"unknown pattern {pattern!r}")

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                           out_specs=out_spec, check_vma=False))
    sec = _time_fn(fn, x, iters=iters)
    bytes_total = x.size * x.dtype.itemsize
    bus_gbps = bytes_total * algo_factor / sec / 1e9
    return {
        "pattern": pattern, "axis": axis, "devices": n,
        "size_mb": size_mb, "dtype": str(jnp.dtype(dtype)),
        "time_ms": sec * 1e3, "bus_bandwidth_gbps": bus_gbps,
    }


def bench_all(mesh: Mesh, axis: str, size_mb: float = 16.0,
              patterns=("allreduce", "all_gather", "reduce_scatter",
                        "ppermute", "all_to_all")) -> list[dict]:
    return [bench_collective(mesh, axis, p, size_mb) for p in patterns]
