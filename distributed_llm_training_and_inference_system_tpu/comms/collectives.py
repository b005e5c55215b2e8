"""Named collectives over mesh axes — the real ``comms`` layer.

The reference's ``llmctl/comms`` is an empty package ("collectives, overlap
engine" — reference llmctl/comms/__init__.py:1); its collectives happen
implicitly inside torch DDP and its comm tuner fabricates timings
(reference autotuning.py:222-245). Here every primitive is a thin, explicitly
named wrapper over ``jax.lax`` collectives usable inside ``shard_map``
bodies, so pipeline/ring/MoE code reads like the comm pattern it implements:

    allreduce       <- jax.lax.psum         (dp/fsdp grad sync, tp matmuls)
    all_gather      <- jax.lax.all_gather   (ZeRO-3 param gather)
    reduce_scatter  <- jax.lax.psum_scatter (bandwidth-optimal grad sync)
    ring_shift      <- jax.lax.ppermute     (pipeline p2p, ring attention)
    all_to_all      <- jax.lax.all_to_all   (MoE dispatch, Ulysses SP)

Over ICI these lower to XLA's native torus collectives; across slices XLA
routes them over DCN — the reference's NCCL/Gloo/IB distinction collapses
into mesh-axis placement.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def allreduce_sum(x: jax.Array, axis: str) -> jax.Array:
    return lax.psum(x, axis_name=axis)


def allreduce_mean(x: jax.Array, axis: str) -> jax.Array:
    return lax.pmean(x, axis_name=axis)


def all_gather(x: jax.Array, axis: str, *, gather_dim: int = 0,
               tiled: bool = True) -> jax.Array:
    return lax.all_gather(x, axis_name=axis, axis=gather_dim, tiled=tiled)


def reduce_scatter(x: jax.Array, axis: str, *, scatter_dim: int = 0) -> jax.Array:
    return lax.psum_scatter(x, axis_name=axis, scatter_dimension=scatter_dim,
                            tiled=True)


def ring_shift(x: jax.Array, axis: str, *, shift: int = 1) -> jax.Array:
    """Send to (i+shift) mod n — the pipeline/ring-attention hop."""
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name=axis, perm=perm)


def all_to_all(x: jax.Array, axis: str, *, split_dim: int,
               concat_dim: int) -> jax.Array:
    return lax.all_to_all(x, axis_name=axis, split_axis=split_dim,
                          concat_axis=concat_dim, tiled=True)


def axis_index(axis: str) -> jax.Array:
    return lax.axis_index(axis)


def axis_size(axis: str) -> int:
    return lax.axis_size(axis)


def barrier(axis: str) -> None:
    """Synchronisation point: a trivial psum forces a collective boundary."""
    lax.psum(jnp.zeros((), jnp.int32), axis_name=axis)


# ---------------------------------------------------------------------------
# Overlap engine
# ---------------------------------------------------------------------------

# TPU compiler flags enabling async collective fusion / compute-collective
# overlap: the TPU equivalent of the reference's (absent) "overlap engine".
# Applied by runtime/launcher.py to every spawned training process through
# LIBTPU_INIT_ARGS — the installed jaxlib (0.9.0) aborts on ANY ``xla_tpu_``
# flag in XLA_FLAGS ("Unknown flag in XLA_FLAGS", fatal, whatever the
# backend), while libtpu 0.0.34 parses them from its own variable, which a
# CPU process never reads.
OVERLAP_LIBTPU_ARGS = (
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
    "--xla_tpu_overlap_compute_collective_tc=true"
)


def overlap_flags() -> str:
    """The overlap flags, for ``LIBTPU_INIT_ARGS`` (never ``XLA_FLAGS``)."""
    return OVERLAP_LIBTPU_ARGS
