"""Grouped matmul for dropless mixture-of-experts: rows sorted by expert,
one matmul over the ragged groups, expert weights read where they lie.

``lhs`` [M, K] holds the routed rows, grouped by expert, each group padded
to a whole number of ``tm``-row tiles (models/layers.py moe_block lays them
out), so every row tile belongs to ONE expert and ``tile_group[i]`` names
it. ``rhs`` is the expert stack as the parameter tree stores it,
[L, E, K, N], taken WHOLE: the layer index and the tile->expert table ride
the scalar prefetch and the weight BlockSpec's index map addresses
``rhs[layer, tile_group[i], :, j]``. Nothing expert-sized is sliced out or
copied per layer (a layer's experts are 805 MB at OLMoE's widths; PR 26
found exactly that copy for the KV pools).

Grid (N / tn, tiles): row tiles innermost, so consecutive tiles of one
expert present the same weight block index and the pipeline skips the
re-fetch: each HIT expert's weights are streamed once per column block,
which is the decode step's bandwidth floor. K is taken whole (2048 / 1024
at OLMoE's widths: a [2048, 512] bf16 block is 2 MB), so there is no
accumulator and no K loop. Tiles past ``tiles_used`` (the static tile count
is an upper bound over all routings) are clamped to the last used tile's
expert by the caller (no DMA) and write zeros.

Off the TPU, and under an ambient multi-device mesh (a Pallas call is a
custom call GSPMD cannot partition), ``grouped_matmul`` takes
``jax.lax.ragged_dot`` on the same layout: the XLA reference of this
kernel, as the page gather is of the paged-attention kernel. That is the
one selector; tests run the kernel itself with ``moe_gmm(interpret=True)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.platform import report_impl

# a weight block [K, tn] is double-buffered in VMEM: 2 MB blocks at bf16
# keep the kernel's footprint under the 16 MiB a TPU kernel may use
_BLOCK_BYTES = 2 << 20


def _col_tile(k: int, n: int, itemsize: int) -> int:
    """Widest column tile (a multiple of 128 dividing n) whose [k, tn]
    weight block stays within ``_BLOCK_BYTES``."""
    tn = n
    while tn % 256 == 0 and k * tn * itemsize > _BLOCK_BYTES:
        tn //= 2
    return tn


def _gmm_kernel(group_ref, layer_ref, used_ref, lhs_ref, rhs_ref, out_ref):
    # (group_ref and layer_ref are for the index maps alone)
    i = pl.program_id(1)

    @pl.when(i < used_ref[0])
    def _():
        out_ref[...] = jnp.dot(
            lhs_ref[...], rhs_ref[...],
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    @pl.when(i >= used_ref[0])
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def moe_gmm(lhs: jax.Array, rhs: jax.Array, tile_group: jax.Array,
            tiles_used: jax.Array, layer, *, tm: int,
            name: str = "moe_gmm", interpret: bool = False) -> jax.Array:
    """[M, K] x rhs[layer, tile_group[row // tm]] -> [M, N] in lhs.dtype.

    ``rhs`` [L, E, K, N]; ``tile_group`` [M / tm] int32, non-decreasing
    over the used tiles; ``tiles_used`` int32 scalar; ``layer`` int32
    scalar (traced or not); ``name`` is what a device trace shows the
    kernel as."""
    M, K = lhs.shape
    N = rhs.shape[-1]
    n_tiles = M // tm
    assert M == n_tiles * tm and rhs.shape[-2] == K, (lhs.shape, rhs.shape)
    tn = _col_tile(K, N, rhs.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # tile_group, layer, tiles_used
        grid=(N // tn, n_tiles),
        in_specs=[
            pl.BlockSpec((tm, K), lambda j, i, g, ly, u: (i, 0)),
            pl.BlockSpec((None, None, K, tn),
                         lambda j, i, g, ly, u: (ly[0], g[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, i, g, ly, u: (i, j)),
    )
    with jax.named_scope(name):
        return pl.pallas_call(
            _gmm_kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
            interpret=interpret, name=name,
        )(tile_group.astype(jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1),
          jnp.asarray(tiles_used, jnp.int32).reshape(1), lhs, rhs)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, tile_group: jax.Array,
                   tiles_used: jax.Array, layer=None, *, tm: int,
                   name: str = "moe_gmm") -> jax.Array:
    """The grouped matmul of the dropless MoE block. ``rhs`` is the expert
    stack [L, E, K, N] with ``layer`` its index, or one layer's [E, K, N]
    with ``layer=None``. The kernel on one TPU device;
    ``jax.lax.ragged_dot`` off the TPU and under a multi-device mesh."""
    from ..parallel.sharding import current_mesh
    if layer is None:
        rhs, layer = rhs[None], 0
    mesh = current_mesh()
    detail = f"lhs{tuple(lhs.shape)} rhs{tuple(rhs.shape)} tm{tm}"
    if jax.default_backend() == "tpu" and (mesh is None or mesh.size == 1):
        report_impl("moe_gmm", "pallas", detail)
        return moe_gmm(lhs, rhs, tile_group, tiles_used, layer, tm=tm,
                       name=name)
    report_impl("moe_gmm", "xla-ragged_dot", detail)
    E = rhs.shape[1]
    used = jnp.arange(tile_group.shape[0]) < tiles_used
    # rows a group holds = its tiles x tm; the unused tail tiles belong to
    # no group and ragged_dot leaves their rows UNDEFINED (on the chip they
    # are not zero): moe_block never gathers them for a live row
    sizes = jnp.zeros((E,), jnp.int32).at[tile_group].add(
        jnp.where(used, tm, 0).astype(jnp.int32))
    w = jax.lax.dynamic_index_in_dim(rhs, jnp.asarray(layer, jnp.int32), 0,
                                     keepdims=False)
    return jax.lax.ragged_dot(lhs, w, sizes).astype(lhs.dtype)
