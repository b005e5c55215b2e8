"""Pallas TPU kernel for paged attention: stream a slot's LIVE pages
HBM->VMEM.

The gather baseline (ops/paged_attention.py) materialises every slot's full
[Nkv, maxP*PS, D] KV prefix in HBM each decode step: O(max_seq) traffic per
token regardless of the sequence's actual length. This kernel reads only the
pages a sequence owns, and spends time only on them:

- Grid (B, query tiles): one grid step a slot and query tile. The pools
  [L, NP, Nkv, PS, D] come in WHOLE and stay in HBM (never a layer's
  slice); the layer index, the block table and the start positions ride
  the scalar prefetch (``PrefetchScalarGridSpec``).
- Inside a grid step a loop walks the pages the tile's queries can see,
  live = ceil((start + tile) / PS) of them and no more, so the trip count
  follows the slot's length and not the block table's width: table entries
  past the live length are never looked at. (With the page axis in the grid
  a call paid ~0.15 us for each of its B * maxP steps, live or not: 155 us
  of a 200 us call at 32 slots x 32 pages.)
- The body copies ``pages[layer, table[b, p]]``, ALL kv heads [Nkv, PS, D],
  into a ring of ``_PAGES_AHEAD + 1`` VMEM buffers with
  ``pltpu.make_async_copy``. The copies run ``_PAGES_AHEAD`` pages ahead of
  the page being scored, in grid order and ACROSS grid steps: while a slot's
  last pages are scored the next slot's first ones are already on their
  way, so a copy's ~0.5 us latency is hidden even where every slot holds
  one page. Every grid step fetches at least the page its table names first
  (an idle slot sits at position 0 and sees one token of it), which keeps
  that order free of any search for the next slot with work.
- Online softmax in fp32 VMEM scratch across pages (same recurrence as the
  training-side flash kernel); GQA folds the q-head group into the tile,
  and head folding means each KV page is loaded ONCE per slot, not per
  kv head, let alone per q head.

Numerics match ops.paged_attention.paged_attention (the gather baseline),
asserted in tests/test_ops.py for every page type, head layout and the
lengths a loop bound can get wrong. The baseline remains the CPU /
``tp > 1`` / ``head_dim % 128`` route.

Reference defect this replaces: the dead KVCacheManager + full-prefix
recompute at reference serve/server.py:57-87,199-204.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.layers import NEG_INF


def _extend_kernel(tables_ref, starts_ref, layer_ref,   # scalar prefetch
                   q_ref, *refs,
                   page_size: int, scale: float, groups: int,
                   window: int, queries: int, num_kv: int, kv_quant: str,
                   block: int = 0, sliding: int = 0):
    """One grid step: one slot's query tile against that slot's LIVE pages.

    ``refs``: (the slot's [maxP, Nkv, PS] scale tiles for K and V, when the
    pages are quantised,) the K and V pools (HBM, whole), the output block,
    the float32 online-softmax scratch (acc, m, l), the K and V rings of
    page buffers in VMEM, the DMA semaphores [K / V, buffer] and the SMEM
    cells that carry the ring's state from one grid step to the next.

    Query row j (= row // groups within a head) of the tile sits at
    position start + j and attends causally over [0, start + j], so the
    tile needs the pages that hold the start + window tokens its last
    query sees, and the page loop runs that many times. With ``block`` > 0
    (generation by diffusion over blocks; the slot's start and a tile's are
    whole numbers of blocks) row j sees its whole block instead:
    [0, start + (j // block + 1) * block). Each iteration starts the copy of the
    page ``_PAGES_AHEAD`` places further on in grid order (this tile's, or
    the next grid step's once this tile's are all under way), waits for
    its own page and scores it. A slot at length 0 waits for the one page
    fetched for it and scores nothing. With ``sliding`` > 0 (a WINDOW
    layer; the table is the slot's ring laid out by logical page,
    ``SplitPages.tables_of``) row j sees the last ``sliding`` keys alone,
    (start + j - sliding, start + j]: the loop STARTS at the page that holds
    the first key the tile's first query sees (``first_page``) and runs
    ceil((sliding + window - 1) / PS) + 1 times at most, whatever the
    slot's length; the rows of that first page behind a query's window are
    masked.

    Head folding: q rows [Nkv*T*G, D] against the whole page [Nkv*PS, D]
    in ONE dot pair per page. Cross-head score blocks are masked to
    NEG_INF, so their post-softmax probabilities are exactly zero and the
    folded AV dot needs no block-diagonal bookkeeping. The dot does Nkv x
    the useful FLOPs; a dot pair per head would load as many MXU weight
    tiles for an Nkv-th of the rows each.

    ``kv_quant``: "int8" pages carry a per-page [Nkv, PS] scale tile
    (one row scale per token, QuantPages layout); "int4" pages pack two
    page slots per byte along the slot axis ([Nkv, PS/2, D] uint8 tile,
    Int4Pages) with the SAME scale tile. Dequantisation happens in VMEM
    right before the float32 dot, so HBM page traffic is halved (int8) or
    quartered (int4)."""
    if kv_quant != "none":
        ks_ref, vs_ref, *refs = refs
    (k_hbm, v_hbm, o_ref, acc_ref, m_ref, l_ref,
     k_buf, v_buf, sems, ring_ref) = refs
    b, t = pl.program_id(0), pl.program_id(1)
    n_slots, n_tiles = pl.num_programs(0), pl.num_programs(1)
    max_pages = tables_ref.shape[1]
    n_bufs = k_buf.shape[0]
    tg = window * groups                  # query rows per kv head
    d = q_ref.shape[-1]
    layer = layer_ref[0]

    def visible(slot, tile):
        # tokens the tile's last query sees (the rows that pad the last
        # tile of a window are not queries: they see what it sees)
        rows = jnp.minimum((tile + 1) * window, queries)
        if block:       # ... and the last query sees its block's end
            rows = (rows + block - 1) // block * block
        return starts_ref[slot] + rows

    def live_pages(slot, tile):
        # the pages holding them; at least the one fetched
        return jnp.clip((visible(slot, tile) + page_size - 1) // page_size,
                        1, max_pages)

    def first_page(slot, tile):
        # the page of the first key the tile's FIRST query sees (a lead that
        # has run past the last slot reads the last slot's start)
        if not sliding:
            return 0
        first = (starts_ref[jnp.minimum(slot, n_slots - 1)] + tile * window
                 - (sliding - 1))
        return jnp.maximum(first, 0) // page_size

    def page_copies(slot, p, buf):
        page = tables_ref[slot, p]
        return [pltpu.make_async_copy(pool.at[layer, page], ring.at[buf],
                                      sems.at[i, buf])
                for i, (pool, ring) in enumerate(((k_hbm, k_buf),
                                                  (v_hbm, v_buf)))]

    def next_buffer(buf):
        return jnp.where(buf + 1 == n_bufs, 0, buf + 1)

    def fetch_next(lead):
        """Start the copy of the page the lead stands on, if any is left,
        and move the lead on in grid order: a tile's pages, a slot's
        tiles, the slots."""
        slot, tile, p, buf = lead

        @pl.when(slot < n_slots)
        def _start():
            for copy in page_copies(slot, p, buf):
                copy.start()

        tile_done = p + 1 >= live_pages(jnp.minimum(slot, n_slots - 1), tile)
        slot_done = tile_done & (tile + 1 >= n_tiles)
        slot = jnp.where(slot_done, slot + 1, slot)
        tile = jnp.where(slot_done, 0, jnp.where(tile_done, tile + 1, tile))
        return (slot, tile,
                jnp.where(tile_done, first_page(slot, tile), p + 1),
                next_buffer(buf))

    # the ring's state rides SMEM from one grid step to the next: where
    # the lead stands (slot, tile, page, buffer) and which buffer holds
    # the next page to score
    @pl.when((b == 0) & (t == 0))
    def _prime():
        lead = (jnp.int32(0), jnp.int32(0), jnp.int32(first_page(0, 0)),
                jnp.int32(0))
        for _ in range(n_bufs - 1):
            lead = fetch_next(lead)
        for i, x in enumerate((*lead, jnp.int32(0))):
            ring_ref[i] = x

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    # this grid step's query tile: ``window`` rows starting at
    # start + tile * window (one tile unless the wrapper split a long
    # suffix-prefill window, see _query_tile)
    start = starts_ref[b] + t * window
    max_len = visible(b, t)

    def score_page(p, buf):
        q = q_ref[...].astype(jnp.float32).reshape(num_kv * tg, d)
        if kv_quant == "none":
            k = k_buf[buf].astype(jnp.float32)       # [Nkv, PS, D]
            v = v_buf[buf].astype(jnp.float32)
        else:
            # shared nibble / absmax math (ops.quantization): pure jnp,
            # safe in a Pallas body. int4 unpack is a sublane relabel of
            # the [Nkv, PS/2, D] byte tile, then int8's row-scale multiply
            from .quantization import (dequantize_int4_rows,
                                       dequantize_int8_rows)
            dequantize = (dequantize_int4_rows if kv_quant == "int4"
                          else dequantize_int8_rows)
            k = dequantize(k_buf[buf], ks_ref[p])
            v = dequantize(v_buf[buf], vs_ref[p])
        k = k.reshape(num_kv * page_size, d)
        v = v.reshape(num_kv * page_size, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [Nkv*TG, Nkv*PS]
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        pos = p * page_size + col % page_size
        row_j = (row % tg) // groups
        same_head = (row // tg) == (col // page_size)
        last_seen = ((row_j // block + 1) * block - 1 if block else row_j)
        seen = same_head & (pos <= start + last_seen)
        if sliding:
            seen = seen & (pos > start + row_j - sliding)
        s = jnp.where(seen, s, NEG_INF)

        m_prev = m_ref[...]                            # [Nkv*TG, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p_ = jnp.exp(jnp.where(m_new > NEG_INF / 2, s - m_new, NEG_INF))
        alpha = jnp.exp(jnp.where(m_new > NEG_INF / 2, m_prev - m_new, 0.0))
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p_, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p_, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    def one_page(p, ring):
        *lead, buf = ring
        lead = fetch_next(lead)
        for copy in page_copies(b, p, buf):
            copy.wait()

        @pl.when(p * page_size < max_len)       # false only at length 0
        def _score():
            score_page(p, buf)

        return (*lead, next_buffer(buf))

    ring = jax.lax.fori_loop(first_page(b, t), live_pages(b, t), one_page,
                             tuple(ring_ref[i] for i in range(5)))
    for i, x in enumerate(ring):
        ring_ref[i] = x

    l = l_ref[...]
    o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
        o_ref.dtype).reshape(o_ref.shape)


# Largest folded score tile [Nq*tile, Nkv*PS] (fp32 elements) the kernel
# builds per grid step. The v5e compiler gives a kernel 16 MB of scoped
# VMEM: an untiled 256-token window at gpt-1b (a 4096 x 1024 score tile)
# asked for 19.3 MB and was refused, and 2**21 tiled (q/out blocks double-
# buffered across tiles) was refused for the GQA 32/8 layout. 2**20 (4 MB:
# 64 query tokens per step at both layouts) compiles at every window the
# engine can pass — tests/test_tpu_compile_kernels.py holds it there.
_MAX_SCORE_ELEMS = 1 << 20
# Most folded query rows [Nq*tile] a grid step holds (bfloat16; float32
# half): 64 query tokens at 32 query heads.
_MAX_QUERY_ROWS = 1 << 11

# How many pages the copies run ahead of the page being scored (the ring
# holds one buffer more). Alone on a v5e (32 slots, 200 live pages, us a
# call at GQA 32/8 | MHA 16/16; PERF.md 6, PR 28): 1 ahead 128 | 168,
# 2 ahead 105 | 144, 3 ahead 103 | 143, 5 and 7 the same as 3.
_PAGES_AHEAD = 3


def _query_tile(T: int, Nq: int, Nkv: int, PS: int,
                itemsize: int = 2) -> int:
    """Query rows per grid step: the whole window when its folded score
    tile fits, else the largest power of two (>= 8) that does. Float32
    queries and pages (``itemsize`` 4) get half the score tile: their q /
    out blocks and page ring are twice the bytes, and inside a decode
    program that carries a 128-row piece at GQA 32/8 the full tile asked
    for 16.73 of the 16 MB (chip_smoke.py's ``ride`` phase, PR 36)."""
    budget = _MAX_SCORE_ELEMS * 2 // max(itemsize, 2)
    # ... and at most ``_MAX_QUERY_ROWS`` folded query rows: what the score
    # budget alone gives every layout of at least 8 kv heads. With fewer
    # (GQA 32 / 4) the same score tile is twice the rows, and the q / out
    # blocks and the accumulator, which grow with the rows, took a
    # 256-row window to 18.7 of the 16 MB (PERF.md 6, PR 42)
    rows = _MAX_QUERY_ROWS * 2 // max(itemsize, 2)
    if Nq * T * Nkv * PS <= budget and Nq * T <= rows:
        return T
    tile = max(min(budget // (Nq * Nkv * PS), rows // Nq), 8)
    return 1 << (tile.bit_length() - 1)


def paged_attention_pallas_multi(
    q: jax.Array,              # [B, T, Nq, D] — T consecutive tokens/slot
    k_pages: jax.Array,        # [L, NP, Nkv, PS, D] ([NP, ...] if no layer)
    v_pages: jax.Array,
    block_tables: jax.Array,   # [B, maxP] int32
    start_positions: jax.Array,  # [B] int32 — position of q[:, 0]
    *,
    layer=None,                # int32 scalar: which layer's pages to read
    interpret: bool = False,
    block: int = 0,            # static: the block rule's block length
    scale: float | None = None,  # of the scores; None: D ** -0.5 (a pool
                               # of heads in pairs states its heads' own)
    sliding: int = 0,          # static: a window layer's keys (0: all)
) -> jax.Array:
    """Returns [B, T, Nq, D]; query j attends over [0, start+j] via pages
    (the window's own K/V must already be written to the pages). With
    ``block`` > 0 query j attends over [0, start + (j // block + 1) *
    block): every start a whole number of blocks, ``block`` dividing the
    page size, so a block never straddles two pages (the kernel then runs
    under the name ``paged_attention_blk``). With ``sliding`` > 0 query j
    attends over (start + j - sliding, start + j] and the table is a ring's
    (the kernel's name is ``window_attention`` / ``window_attention_mq``).

    The pools are operands as they are, never a layer's slice of them: the
    layer rides the scalar prefetch beside the block table and the body's
    copies address ``pages[layer, page]``. One layer's [NP, ...] pages with
    ``layer=None`` are the L = 1 pool."""
    from .paged_attention import Int4Pages, QuantPages, _at
    kv_quant = ("int4" if isinstance(k_pages, Int4Pages)
                else "int8" if isinstance(k_pages, QuantPages) else "none")
    B, T_in, Nq, D = q.shape
    Nkv, PS = k_pages.shape[-3:-1]
    maxP = block_tables.shape[1]
    groups = Nq // Nkv
    if scale is None:
        scale = 1.0 / float(D) ** 0.5

    # long windows (suffix / chunked prefill) are tiled along the query
    # axis: one more grid dimension, each tile an independent online-
    # softmax pass over the slot's pages. A window that is not a whole
    # number of tiles is padded; the pad rows are computed and dropped.
    tile = _query_tile(T_in, Nq, Nkv, PS, q.dtype.itemsize)
    if block and (PS % block or (tile < T_in and tile % block)):
        raise ValueError(f"block {block} must divide the page size {PS} "
                         f"and a query tile ({tile} of {T_in} rows)")
    T = -(-T_in // tile) * tile
    if T != T_in:
        q = jnp.pad(q, ((0, 0), (0, T - T_in), (0, 0), (0, 0)))
    n_tiles = T // tile

    # [B, Nkv, T*G, D]: T outer, groups inner, so row // groups == j
    qg = q.reshape(B, T, Nkv, groups, D).transpose(0, 2, 1, 3, 4).reshape(
        B, Nkv, T * groups, D)
    starts = start_positions.astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)

    # one grid step a slot and query tile; the pools stay whole in HBM and
    # the body copies pages[layer, table[b, p]] itself (int4 pages as the
    # packed [Nkv, PS/2, D] byte tile, half the int8 bytes per page).
    # A quantised page's [Nkv, PS] scale tile cannot ride those copies:
    # Mosaic refuses to slice an HBM operand whose minor dimension is
    # under 128 lanes (PS is 64). The slot's scale tiles are gathered
    # through its table beforehand instead and come in with q: 2 KB a
    # page beside 64 KB of int8, at the table's width.
    q_spec = pl.BlockSpec((None, Nkv, tile * groups, D),
                          lambda b, t, tbl, st, ly: (b, 0, t, 0))
    in_specs, inputs = [q_spec], [qg]
    if kv_quant != "none":
        scale_spec = pl.BlockSpec((None, maxP, Nkv, PS),
                                  lambda b, t, tbl, st, ly: (b, 0, 0, 0))
        in_specs += [scale_spec, scale_spec]
        inputs += [k_pages.scale[_at(layer, tables)],
                   v_pages.scale[_at(layer, tables)]]
        pools = [k_pages.values, v_pages.values]
    else:
        pools = [k_pages, v_pages]
    if layer is None:
        pools, layer = [a[None] for a in pools], 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,       # tables, starts, layer
        grid=(B, n_tiles),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Nkv * tile * groups, D), jnp.float32),
            pltpu.VMEM((Nkv * tile * groups, 1), jnp.float32),
            pltpu.VMEM((Nkv * tile * groups, 1), jnp.float32),
            *[pltpu.VMEM((_PAGES_AHEAD + 1, *a.shape[2:]), a.dtype)
              for a in pools],
            pltpu.SemaphoreType.DMA((2, _PAGES_AHEAD + 1)),  # [K / V, buffer]
            pltpu.SMEM((5,), jnp.int32),
        ],
    )

    # one query a sequence is the decode step; a window is the
    # speculative verify or a suffix prefill
    name = ("paged_attention_blk" if block else
            ("window_attention" if sliding else "paged_attention")
            + ("" if T_in == 1 else "_mq"))
    with jax.named_scope(name):
        out = pl.pallas_call(
            functools.partial(_extend_kernel, page_size=PS, scale=scale,
                              groups=groups, window=tile, queries=T_in,
                              num_kv=Nkv, kv_quant=kv_quant, block=block,
                              sliding=sliding),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, Nkv, T * groups, D), q.dtype),
            # the ring of page copies runs across grid steps: in order,
            # on one core
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name=name,
        )(tables, starts, jnp.asarray(layer, jnp.int32).reshape(1),
          *inputs, *pools)
    return out.reshape(B, Nkv, T, groups, D).transpose(0, 2, 1, 3, 4).reshape(
        B, T, Nq, D)[:, :T_in]


def paged_attention_pallas(
    q: jax.Array,            # [B, Nq, D] — one query token per sequence
    k_pages: jax.Array,      # [L, NP, Nkv, PS, D] ([NP, ...] if no layer)
    v_pages: jax.Array,
    block_tables: jax.Array, # [B, maxP] int32 physical page ids
    lengths: jax.Array,      # [B] int32 — attend over [0, lengths)
    *,
    layer=None,
    interpret: bool = False,
) -> jax.Array:
    """Returns [B, Nq, D] in q.dtype; same contract as the gather baseline.

    The T=1 case of ``paged_attention_pallas_multi`` (one kernel body, so
    the decode and extend paths can never diverge numerically): start
    position = lengths - 1, window = 1.
    """
    out = paged_attention_pallas_multi(
        q[:, None], k_pages, v_pages, block_tables,
        lengths.astype(jnp.int32) - 1, layer=layer, interpret=interpret)
    return out[:, 0]
