"""Paged attention over LATENT pages (multi-head latent attention, absorbed
form): every query head of a slot scores the same page of latent rows, and
the page's first ``value_width`` values are the values too.

The cache of a latent-attention model is ONE pool [L, NP, 1, PS, W]: a row
is [c_kv (kv_lora_rank) | rope(k_pe)], zero-padded to W = whole 128-lane
tiles (576 -> 640; ``MLAConfig.page_width`` says why). The absorbed query
``q~`` [B, T, N, W] carries ``q_nope W_kvb[k]^T`` beside ``q_pe`` (and zeros
over the padding), so

    s[b, t, n, j] = q~[b, t, n] . row[b, j] * scale        j <= start_b + t
    o~[b, t, n]   = softmax_j(s) @ row[b, :, :value_width]

``mla_paged_attention`` dispatches like ``ops.paged_attention``: the Pallas
kernel on a TPU (``mla_paged_attention`` for one query a slot,
``mla_paged_attention_mq`` for a window), the gather twin elsewhere.

The kernel is PR 28's live-page walk (ops/paged_attention_pallas.py) over
one pool: grid (slots, query tiles), the pool whole in HBM, a ring of VMEM
page buffers whose copies run ``_PAGES_AHEAD`` loop steps ahead in grid order
and across grid steps, the loop's trip count the slot's live pages. What
differs: one copy a page (not K and V), no head folding (all N heads' rows
are the query tile: [tile * N, W] against the page's [PS, W], no cross-head
mask), matmul operands in the pool's dtype with float32 accumulation, the
value product over the page's first ``value_width`` lanes, and (PR 64) a
loop step that scores a GROUP of 2 (4) small pages under one softmax update
where the query tile is small (``_pages_a_step``: read off the call's
shapes; ``report_impl``'s line says ``group=``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.layers import NEG_INF
from ..utils.platform import kernel_impl, report_impl
from .paged_attention import _at


def mla_paged_attention(
    q: jax.Array,              # [B, T, N, W] absorbed queries (zero-padded)
    pool: jax.Array,           # [L, NP, 1, PS, W] ([NP, 1, PS, W], no layer)
    block_tables: jax.Array,   # [B, maxP] int32
    start_positions: jax.Array,  # [B] int32: position of q[:, 0]
    *,
    scale: float,
    value_width: int,
    impl: str = "auto",        # auto | pallas | gather
    layer=None,
) -> jax.Array:
    """Returns o~ [B, T, N, value_width]; query t of slot b attends causally
    over [0, start_b + t] through the pages (the window's own rows must
    already be written)."""
    on_tpu = jax.default_backend() == "tpu"
    op = "mla_paged_attention" if q.shape[1] == 1 else \
        "mla_paged_attention_mq"
    detail = f"q{tuple(q.shape)}"
    if impl == "auto":
        impl = "pallas" if on_tpu else "gather"
        if not on_tpu:
            detail += f", backend {jax.default_backend()}"
    else:
        detail += ", requested by caller"
    if impl == "pallas":
        detail += f", group={_tiling(q, pool)[1]}"
    report_impl(op, kernel_impl() if impl == "pallas" else impl, detail)
    if impl == "pallas":
        return mla_paged_attention_pallas(
            q, pool, block_tables, start_positions, scale=scale,
            value_width=value_width, layer=layer, interpret=not on_tpu)
    return _gather(q, pool, block_tables, start_positions, scale,
                   value_width, layer)


def _gather(q, pool, block_tables, start_positions, scale, value_width,
            layer):
    """The XLA twin: each slot's rows gathered through its block table, then
    plain masked attention in float32 statistics."""
    B, T, N, W = q.shape
    PS = pool.shape[-2]
    maxP = block_tables.shape[1]
    rows = pool[_at(layer, block_tables)].reshape(B, maxP * PS, W)
    s = jnp.einsum("btnw,bkw->bntk", q, rows.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    q_pos = start_positions[:, None] + jnp.arange(T, dtype=jnp.int32)
    seen = jnp.arange(maxP * PS, dtype=jnp.int32)[None, None] \
        <= q_pos[:, :, None]                                  # [B, T, K]
    s = jnp.where(seen[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bntk,bkr->btnr", p,
                     rows[..., :value_width].astype(q.dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# how many loop steps the copies run ahead (the ring holds one step's buffers
# more): PR 28's value for K/V pages, and the sweeps of experiments/
# mla_kernel_alone.py are made at it. At two pages a step (below) 2 ahead
# read what 3 do (1,501 | 1,488 us a call at T = 1, 1,644 | 1,654 at T = 2)
# and 1 ahead 1,749 | 1,896: the ring stays 3 steps deep at every group
_PAGES_AHEAD = 3
# query rows (tokens x heads) a grid step holds: the float32 accumulator
# [rows, value_width] is 2 MB at 1,024 x 512, the score tile [rows, PS] 1 MB
# at pages of 256
_MAX_QUERY_ROWS = 1024
# The most query rows (bfloat16; float32 half, as the tile's) at which a
# loop step scores a GROUP of pages. Alone on a v5e (the doc-qa shape: 64
# slots, 32 heads, ~12.9k live tokens a slot, pages of 256 x 640 bf16 = 328
# KB; us a call at 1 | 2 pages a step; PERF.md 6, PR 64):
#   rows   32 (T = 1)  1,968 | 1,488    (0.602 | 0.455 us a page, 66 | 88 %
#   rows   64 (T = 2)  2,475 | 1,654     of the HBM peak at T = 1)
#   rows  128 (T = 4)  2,885 | 2,206
#   rows  256 (T = 8)  4,317 | 3,631
#   rows  512 (T = 16) 7,512 | 6,501
# a group saves 0.15-0.3 us a page at every tile measured; the tile of 1,024
# rows (a riding piece, a suffix: ~3 us of arithmetic a page) was not
# measured and keeps its score tile and its VMEM. Pages of 128 | 64 rows
# (164 | 82 KB) at T = 1, 1 | 2 | 4 pages a step: 3,355 | 2,095 | 1,486 and
# 6,158 | 3,576 | 2,309.
_GROUP_MAX_ROWS = 512


def _pages_a_step(page_bytes: int, rows: int, itemsize: int) -> int:
    """Pages a loop step of the walk scores under one softmax update. A step
    costs a serial chain (wait, load, q . page^T, row maximum, exp, row sum,
    p . values, rescale) of ~0.45 us whatever the page holds, and a page
    under ``PAGE_COPY_BYTES`` is copied in less: 2 of them pay the chain
    once (4 where even two are under those bytes). A larger query tile's
    step is its arithmetic and keeps one page."""
    from ..serve.kv_cache import PAGE_COPY_BYTES  # serve imports ops
    if page_bytes >= PAGE_COPY_BYTES \
            or rows > _GROUP_MAX_ROWS * 2 // max(itemsize, 2):
        return 1
    return 2 if 2 * page_bytes >= PAGE_COPY_BYTES else 4


def _tiling(q, pool) -> tuple[int, int]:
    """(query tokens a grid step holds, pages a loop step scores) of a call.
    A long window (suffix / chunked prefill) is tiled along the queries: each
    tile an independent online-softmax pass over the slot's pages (4-byte
    operands take half the rows: the tile's query and output blocks are
    twice the bytes, and 1,024 rows of 32 heads x 640 float32 asked for more
    than the kernel's 16 MB of VMEM inside a decode program: chip_smoke.py's
    float32 arm, PERF.md 6, PR 41)."""
    T, N = q.shape[1:3]
    max_rows = _MAX_QUERY_ROWS * 2 // max(q.dtype.itemsize, 2)
    tile = T if T * N <= max_rows else max(max_rows // N, 1)
    page_bytes = pool.shape[-2] * pool.shape[-1] * pool.dtype.itemsize
    return tile, _pages_a_step(page_bytes, tile * N, q.dtype.itemsize)


def _kernel(tables_ref, starts_ref, layer_ref,          # scalar prefetch
            q_ref, pool_hbm, o_ref, acc_ref, m_ref, l_ref, buf, sems,
            ring_ref, *, page_size: int, scale: float, heads: int,
            window: int, queries: int, value_width: int, group: int):
    """One grid step: one slot's query tile ([window * heads, W], token
    major) against that slot's LIVE latent pages. The ring's bookkeeping is
    ops/paged_attention_pallas.py ``_extend_kernel``'s, a GROUP of ``group``
    consecutive pages a loop step: the lead starts a group's copies into
    adjacent buffers (``p`` counts pages, ``i`` buffers, both by ``group``)
    and the step scores them as one [group * PS, W] operand under one
    softmax update. A tile's last group may be short: its missing members
    copy the tile's LAST live page again, so that the buffer holds finite
    rows of the pool (never what the ring held before), and the causal mask
    takes every column of theirs (their positions lie past the tile's last
    query), so they reach ``acc`` as 0 x finite. ``group`` 1 is the walk a
    page a step, its text unchanged."""
    b, t = pl.program_id(0), pl.program_id(1)
    n_slots, n_tiles = pl.num_programs(0), pl.num_programs(1)
    max_pages = tables_ref.shape[1]
    n_bufs = buf.shape[0]
    layer = layer_ref[0]

    def visible(slot, tile):
        return starts_ref[slot] + jnp.minimum((tile + 1) * window, queries)

    def live_pages(slot, tile):
        return jnp.clip((visible(slot, tile) + page_size - 1) // page_size,
                        1, max_pages)

    def page_copy(slot, p, i):
        return pltpu.make_async_copy(
            pool_hbm.at[layer, tables_ref[slot, p]], buf.at[i], sems.at[i])

    def next_buffer(i):
        return jnp.where(i + group == n_bufs, 0, i + group)

    def fetch_next(lead):
        slot, tile, p, i = lead

        @pl.when(slot < n_slots)
        def _start():
            page_copy(slot, p, i).start()
            for g in range(1, group):
                page_copy(slot, jnp.minimum(p + g, live_pages(slot, tile) - 1),
                          i + g).start()

        tile_done = p + group >= live_pages(jnp.minimum(slot, n_slots - 1),
                                            tile)
        slot_done = tile_done & (tile + 1 >= n_tiles)
        return (jnp.where(slot_done, slot + 1, slot),
                jnp.where(slot_done, 0, jnp.where(tile_done, tile + 1, tile)),
                jnp.where(tile_done, 0, p + group),
                next_buffer(i))

    @pl.when((b == 0) & (t == 0))
    def _prime():
        lead = (jnp.int32(0),) * 4
        for _ in range(n_bufs // group - 1):
            lead = fetch_next(lead)
        for i, x in enumerate((*lead, jnp.int32(0))):
            ring_ref[i] = x

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    start = starts_ref[b] + t * window
    max_len = visible(b, t)

    def score_page(p, i):
        if group == 1:
            page = buf[i, 0]                                 # [PS, W]
        else:                                        # [group * PS, W]
            page = buf[pl.ds(i, group), 0].reshape(group * page_size, -1)
        s = jax.lax.dot_general(
            q_ref[...].astype(page.dtype), page, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [rows, group * PS]
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(p * page_size + col <= start + row // heads, s,
                      NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p_ = jnp.exp(jnp.where(m_new > NEG_INF / 2, s - m_new, NEG_INF))
        alpha = jnp.exp(jnp.where(m_new > NEG_INF / 2, m_prev - m_new, 0.0))
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p_, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p_.astype(page.dtype), page[:, :value_width],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    def one_page(p, ring):
        *lead, i = ring
        lead = fetch_next(lead)
        if group > 1:
            p = p * group                     # the loop counts groups
        page_copy(b, p, i).wait()
        for g in range(1, group):
            page_copy(b, p, i + g).wait()

        @pl.when(p * page_size < max_len)       # false only at length 0
        def _score():
            score_page(p, i)

        return (*lead, next_buffer(i))

    steps = live_pages(b, t)
    if group > 1:
        steps = (steps + group - 1) // group
    ring = jax.lax.fori_loop(0, steps, one_page,
                             tuple(ring_ref[i] for i in range(5)))
    for i, x in enumerate(ring):
        ring_ref[i] = x

    l = l_ref[...]
    o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def mla_paged_attention_pallas(q, pool, block_tables, start_positions, *,
                               scale: float, value_width: int, layer=None,
                               interpret: bool = False) -> jax.Array:
    """The kernel behind ``mla_paged_attention``; same contract."""
    B, T_in, N, W = q.shape
    PS = pool.shape[-2]
    if layer is None:
        pool, layer = pool[None], 0

    tile, group = _tiling(q, pool)
    n_bufs = (_PAGES_AHEAD + 1) * group
    T = -(-T_in // tile) * tile
    if T != T_in:
        q = jnp.pad(q, ((0, 0), (0, T - T_in), (0, 0), (0, 0)))
    rows = tile * N
    q_spec = pl.BlockSpec((None, rows, W),
                          lambda b, t, tbl, st, ly: (b, t, 0))
    o_spec = pl.BlockSpec((None, rows, value_width),
                          lambda b, t, tbl, st, ly: (b, t, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,       # tables, starts, layer
        grid=(B, T // tile),
        in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=o_spec,
        scratch_shapes=[
            pltpu.VMEM((rows, value_width), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((n_bufs, *pool.shape[2:]), pool.dtype),
            pltpu.SemaphoreType.DMA((n_bufs,)),
            pltpu.SMEM((5,), jnp.int32),
        ],
    )
    name = "mla_paged_attention" if T_in == 1 else "mla_paged_attention_mq"
    with jax.named_scope(name):
        out = pl.pallas_call(
            functools.partial(_kernel, page_size=PS, scale=scale, heads=N,
                              window=tile, queries=T_in,
                              value_width=value_width, group=group),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, T * N, value_width), q.dtype),
            # the ring of page copies runs across grid steps: in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name=name,
        )(block_tables.astype(jnp.int32), start_positions.astype(jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1),
          q.reshape(B, T * N, W), pool)
    return out.reshape(B, T, N, value_width)[:, :T_in]
