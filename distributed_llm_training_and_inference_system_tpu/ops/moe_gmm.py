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
accumulator and no K loop; an expert width that is no multiple of 128
(1856 at Nemotron-3-Nano's) takes the transposed form instead (see
``moe_gmm``). Tiles past ``tiles_used`` (the static tile count
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
# a width that only cuts into odd multiples of 128 (2688 = 21 x 128) may
# take blocks up to twice that: at 1.4 MB a block the grid's steps were a
# third of the kernel's time (64 % of its bytes' roofline: my chip run,
# PR 31, call 1); two 3.3 MB buffers still leave most of the 16 MiB
_ODD_BLOCK_BYTES = 4 << 20


def _col_tile(k: int, n: int, itemsize: int) -> int:
    """Widest column tile (a multiple of 128 dividing n) whose [k, tn]
    weight block stays within ``_BLOCK_BYTES``."""
    tn = n
    while tn % 256 == 0 and k * tn * itemsize > _BLOCK_BYTES:
        tn //= 2
    if k * tn * itemsize > _BLOCK_BYTES:
        # a width that does not halve down to the budget (2688 = 21 x 128):
        # the widest 128-multiple divisor that fits, if there is one
        fits = [d for d in range(128, n, 128)
                if n % d == 0 and k * d * itemsize <= _ODD_BLOCK_BYTES]
        tn = max(fits, default=tn)
    return tn


def _k_tile(k: int, n: int, itemsize: int) -> int:
    """K block of the transposed-weight kernel: the widest 128-multiple
    divisor of K (or K whole) whose [N, tk] weight block fits the budget."""
    fits = [d for d in range(128, k + 1, 128)
            if k % d == 0 and d * n * itemsize <= _ODD_BLOCK_BYTES]
    return max(fits, default=k)


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


def _gmm_kernel_nt(group_ref, layer_ref, used_ref, lhs_ref, rhs_ref,
                   out_ref, acc_ref):
    """rhs [N, tk] x lhs [tm, tk]^T into a float32 accumulator [N, tm], K
    cut into blocks (grid axis 1, innermost); the tile's [tm, N] is the
    accumulator transposed once, at the last block. The weights are the
    matmul's FIRST operand, so what is transposed a block is the 16-row
    tile and not the 3.3 MB of weights: with the operands the other way
    round the kernel ran at half of its bytes' roofline (my chip run,
    PR 31, call 4: 1.11 ms a call against 0.60 for the down kernel)."""
    i, k = pl.program_id(0), pl.program_id(1)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < used_ref[0])
    def _():
        acc_ref[...] += jax.lax.dot_general(
            rhs_ref[...], lhs_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = acc_ref[...].T.astype(out_ref.dtype)


def moe_gmm(lhs: jax.Array, rhs: jax.Array, tile_group: jax.Array,
            tiles_used: jax.Array, layer, *, tm: int,
            name: str = "moe_gmm", interpret: bool = False,
            rhs_transposed: bool = False) -> jax.Array:
    """[M, K] x rhs[layer, tile_group[row // tm]] -> [M, N] in lhs.dtype.

    ``rhs`` [L, E, K, N], or with ``rhs_transposed`` [L, E, N, K] (out,
    in); ``tile_group`` [M / tm] int32, non-decreasing over the used
    tiles; ``tiles_used`` int32 scalar; ``layer`` int32 scalar (traced or
    not); ``name`` is what a device trace shows the kernel as.

    The transposed form exists for an output width that is no multiple of
    128 (1856 = 29 x 64 at Nemotron-3-Nano's): such an N cannot be cut
    into column blocks, and as the MINOR dimension of the stack it makes
    the chip lay the stack out transposed, which a kernel's operand may
    not be: the whole 3.8 GB stack was copied in front of every decode
    dispatch (the v5e compiler's ``memory_analysis``, PR 31). Stored
    [N, K] the minor dimension is K (2688 = 21 x 128), the stack lies as
    the kernel reads it, N is taken whole and K is cut into blocks."""
    M, K = lhs.shape
    n_tiles = M // tm
    scalars = (tile_group.astype(jnp.int32),
               jnp.asarray(layer, jnp.int32).reshape(1),
               jnp.asarray(tiles_used, jnp.int32).reshape(1))
    if rhs_transposed:
        N = rhs.shape[-2]
        assert M == n_tiles * tm and rhs.shape[-1] == K, (lhs.shape,
                                                          rhs.shape)
        tk = _k_tile(K, N, rhs.dtype.itemsize)
        last = K // tk - 1

        def k_block(i, k, u):
            # an unused tile keeps the LAST used tile's last block (its
            # expert is clamped by the caller): the same block index, no
            # DMA. Walking k there re-fetched an expert's 10 MB a tile:
            # 34 unused tiles of 84 were 0.5 of the kernel's 1.1 ms a call
            # (my chip runs, PR 31, calls 4 and 5)
            return jnp.where(i < u[0], k, last)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles, K // tk),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda i, k, g, ly, u: (i, k_block(i, k, u))),
                pl.BlockSpec((None, None, N, tk), lambda i, k, g, ly, u: (
                    ly[0], g[i], 0, k_block(i, k, u))),
            ],
            out_specs=pl.BlockSpec((tm, N), lambda i, k, g, ly, u: (i, 0)),
            scratch_shapes=[pltpu.VMEM((N, tm), jnp.float32)],
        )
        with jax.named_scope(name):
            return pl.pallas_call(
                _gmm_kernel_nt, grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
                interpret=interpret, name=name)(*scalars, lhs, rhs)
    N = rhs.shape[-1]
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
        )(*scalars, lhs, rhs)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, tile_group: jax.Array,
                   tiles_used: jax.Array, layer=None, *, tm: int,
                   name: str = "moe_gmm", rhs_transposed: bool = False
                   ) -> jax.Array:
    """The grouped matmul of the dropless MoE block. ``rhs`` is the expert
    stack [L, E, K, N] with ``layer`` its index, or one layer's [E, K, N]
    with ``layer=None`` ([.., N, K] with ``rhs_transposed``). The kernel on
    one TPU device; ``jax.lax.ragged_dot`` off the TPU and under a
    multi-device mesh."""
    from ..parallel.sharding import current_mesh
    if layer is None:
        rhs, layer = rhs[None], 0
    mesh = current_mesh()
    detail = f"lhs{tuple(lhs.shape)} rhs{tuple(rhs.shape)} tm{tm}"
    if jax.default_backend() == "tpu" and (mesh is None or mesh.size == 1):
        report_impl("moe_gmm", "pallas", detail)
        return moe_gmm(lhs, rhs, tile_group, tiles_used, layer, tm=tm,
                       name=name, rhs_transposed=rhs_transposed)
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
    if rhs_transposed:
        w = w.swapaxes(-1, -2)
    return jax.lax.ragged_dot(lhs, w, sizes).astype(lhs.dtype)
