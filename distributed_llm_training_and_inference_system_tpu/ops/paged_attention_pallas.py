"""Pallas TPU kernel for paged decode attention: stream pages HBM->VMEM.

The gather baseline (ops/paged_attention.py) materialises every slot's full
[Nkv, maxP*PS, D] KV prefix in HBM each decode step — O(max_seq) traffic per
token regardless of the sequence's actual length. This kernel reads only the
pages a sequence owns:

- Grid (B, query tiles, maxP), page index innermost. The pools
  [L, NP, Nkv, PS, D] come in WHOLE and stay in HBM; each grid step's
  BlockSpec uses the scalar-prefetched layer index and block table to DMA
  one physical page of that layer — ALL kv heads, [Nkv, PS, D] — into VMEM
  (``PrefetchScalarGridSpec`` — the pallas_guide.md pattern for
  data-dependent addressing). Pallas double-buffers the copies,
  overlapping page DMA with compute. Heads are folded into one dot pair
  per page (cross-head blocks masked): the earlier (B, Nkv, maxP) grid
  paid ~10 us of pipeline overhead per [1,128]x[128,64] dot at MHA decode
  — 12.3 ms of a 24.2 ms gpt-1b decode step (round-3 ablation,
  BASELINE.md).
- Pages past a sequence's live length are CLAMPED to its last used page in
  the index map. Consecutive identical block indices elide the re-fetch
  entirely (the pipeline emitter skips the DMA), so per-token HBM traffic is
  proportional to the sequence's true length — the whole point of paging.
- Online softmax in fp32 VMEM scratch across pages (same recurrence as the
  training-side flash kernel); GQA folds the q-head group into the tile,
  and head folding means each KV page is loaded ONCE per slot — not per
  kv head, let alone per q head.

Numerics match ops.paged_attention.paged_attention (the gather baseline) —
asserted in tests/test_serve.py. The baseline remains the CPU/interpret
fallback.

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
                   *refs,                          # see unpack below
                   page_size: int, scale: float, groups: int,
                   window: int, num_kv: int, kv_quant: str):
    """Multi-query variant: ``window`` consecutive query tokens per slot
    (speculative verify / cached-prefix suffix prefill). Each page is
    DMA'd ONCE per slot and scored against all T queries of ALL kv heads —
    the flattened-row fallback re-streams the prefix T times. Query row
    j (= row // groups within a head) sits at position start + j and
    attends causally over [0, start + j].

    Head folding (round-3 redesign): the original grid (B, Nkv, maxP) ran
    one [T*G, D] x [D, PS] dot per grid step — at MHA decode (T=G=1)
    that is a [1,128]x[128,64] dot per step and 1,280 grid steps/layer,
    measured 12.3 ms of a 24.2 ms decode step in pure per-step pipeline
    overhead (the data floor is ~1.2 ms). This kernel folds ALL kv heads
    into one grid step: q rows [Nkv*T*G, D] against the whole page
    [Nkv*PS, D] in ONE dot pair per page. Cross-head score blocks are
    masked to NEG_INF, so their post-softmax probabilities are exactly
    zero and the folded AV dot needs no block-diagonal bookkeeping. The
    dot does Nkv x the useful FLOPs, but decode attention FLOPs are
    trivia next to per-grid-step overhead (16 GFLOPs/step at gpt-1b B=8
    vs a ~100 us MXU budget).

    ``kv_quant``: "int8" pages carry a per-page [Nkv, PS] scale tile
    (one row scale per token — QuantPages layout); "int4" pages pack two
    page slots per byte along the slot axis ([Nkv, PS/2, D] uint8 tile,
    Int4Pages) with the SAME scale tile. Either way dequant happens in
    VMEM right before the fp32 dot, so HBM page traffic is halved
    (int8) or quartered (int4) — the whole point of the quantized KV
    cache."""
    # (layer_ref is for the index maps alone: they pick the layer's page)
    if kv_quant != "none":
        (q_ref, k_ref, ks_ref, v_ref, vs_ref,
         o_ref, acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    p = pl.program_id(2)
    tg = window * groups                  # query rows per kv head
    d = q_ref.shape[-1]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # this grid step's query tile: ``window`` rows starting at
    # start + tile * window (one tile unless the wrapper split a long
    # suffix-prefill window — see _query_tile)
    start = starts_ref[b] + pl.program_id(1) * window
    max_len = start + window             # last tile token's length

    @pl.when(p * page_size < max_len)
    def _body():
        q = q_ref[...].astype(jnp.float32).reshape(num_kv * tg, d)
        if kv_quant == "int4":
            # shared nibble math (ops.quantization): unpack is a sublane
            # relabel of the [Nkv, PS/2, D] byte tile, then the same
            # row-scale multiply as int8
            from .quantization import dequantize_int4_rows
            k = dequantize_int4_rows(k_ref[...], ks_ref[...], jnp.float32)
            v = dequantize_int4_rows(v_ref[...], vs_ref[...], jnp.float32)
        elif kv_quant == "int8":
            # shared absmax math (ops.quantization): pure jnp, safe in a
            # Pallas body — page scales are the [Nkv, PS] per-page tile
            from .quantization import dequantize_int8_rows
            k = dequantize_int8_rows(k_ref[...], ks_ref[...])
            v = dequantize_int8_rows(v_ref[...], vs_ref[...])
        else:
            k = k_ref[...].astype(jnp.float32)        # [Nkv, PS, D]
            v = v_ref[...].astype(jnp.float32)
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
        s = jnp.where(same_head & (pos <= start + row_j), s, NEG_INF)

        m_prev = m_ref[...]                            # [Nkv*TG, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p_ = jnp.exp(jnp.where(m_new > NEG_INF / 2, s - m_new, NEG_INF))
        alpha = jnp.exp(jnp.where(m_new > NEG_INF / 2, m_prev - m_new, 0.0))
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p_, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p_, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(p == pl.num_programs(2) - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype).reshape(o_ref.shape)


# Largest folded score tile [Nq*tile, Nkv*PS] (fp32 elements) the kernel
# builds per grid step. The v5e compiler gives a kernel 16 MB of scoped
# VMEM: an untiled 256-token window at gpt-1b (a 4096 x 1024 score tile)
# asked for 19.3 MB and was refused, and 2**21 tiled (q/out blocks double-
# buffered across tiles) was refused for the GQA 32/8 layout. 2**20 (4 MB:
# 64 query tokens per step at both layouts) compiles at every window the
# engine can pass — tests/test_tpu_compile.py holds it there.
_MAX_SCORE_ELEMS = 1 << 20


def _query_tile(T: int, Nq: int, Nkv: int, PS: int) -> int:
    """Query rows per grid step: the whole window when its folded score
    tile fits, else the largest power of two (>= 8) that does."""
    if Nq * T * Nkv * PS <= _MAX_SCORE_ELEMS:
        return T
    tile = max(_MAX_SCORE_ELEMS // (Nq * Nkv * PS), 8)
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
) -> jax.Array:
    """Returns [B, T, Nq, D]; query j attends over [0, start+j] via pages
    (the window's own K/V must already be written to the pages).

    The pools are operands as they are, never a layer's slice of them: the
    layer rides the scalar prefetch beside the block table and the index
    maps address ``pages[layer, page]``. One layer's [NP, ...] pages with
    ``layer=None`` are the L = 1 pool."""
    from .paged_attention import Int4Pages, QuantPages
    kv_quant = ("int4" if isinstance(k_pages, Int4Pages)
                else "int8" if isinstance(k_pages, QuantPages) else "none")
    B, T_in, Nq, D = q.shape
    Nkv, PS = k_pages.shape[-3:-1]
    maxP = block_tables.shape[1]
    groups = Nq // Nkv
    scale = 1.0 / float(D) ** 0.5

    # long windows (suffix / chunked prefill) are tiled along the query
    # axis: one more grid dimension, each tile an independent online-
    # softmax pass over the slot's pages. A window that is not a whole
    # number of tiles is padded; the pad rows are computed and dropped.
    tile = _query_tile(T_in, Nq, Nkv, PS)
    T = -(-T_in // tile) * tile
    if T != T_in:
        q = jnp.pad(q, ((0, 0), (0, T - T_in), (0, 0), (0, 0)))
    n_tiles = T // tile

    # [B, Nkv, T*G, D]: T outer, groups inner, so row // groups == j
    qg = q.reshape(B, T, Nkv, groups, D).transpose(0, 2, 1, 3, 4).reshape(
        B, Nkv, T * groups, D)
    starts = start_positions.astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)

    def page_of(b, t, p, tbl, st, ly):
        # pages past the tile's live length are CLAMPED to its last used
        # page: consecutive identical block indices elide the DMA
        last_used = jnp.maximum((st[b] + (t + 1) * tile + PS - 1) // PS - 1,
                                0)
        return ly[0], tbl[b, jnp.minimum(p, last_used)]

    # head-folded grid (B, tiles, maxP): one whole page (all kv heads)
    # per step. The scale tile [Nkv, PS] rides the SAME clamped
    # block-table index map as its page, so Pallas elides its re-fetch
    # together with the page's on consecutive identical indices. int4
    # pages DMA the packed [Nkv, PS/2, D] byte tile — half the int8
    # bytes per page.
    page_rows = PS // 2 if kv_quant == "int4" else PS
    page_spec = pl.BlockSpec(
        (None, None, Nkv, page_rows, D),
        lambda *grid_and_scalars: (*page_of(*grid_and_scalars), 0, 0, 0))
    scale_spec = pl.BlockSpec(
        (None, None, Nkv, PS),
        lambda *grid_and_scalars: (*page_of(*grid_and_scalars), 0, 0))
    q_spec = pl.BlockSpec((None, Nkv, tile * groups, D),
                          lambda b, t, p, tbl, st, ly: (b, 0, t, 0))
    if kv_quant != "none":
        in_specs = [page_spec, scale_spec, page_spec, scale_spec]
        pools = [k_pages.values, k_pages.scale,
                 v_pages.values, v_pages.scale]
    else:
        in_specs = [page_spec, page_spec]
        pools = [k_pages, v_pages]
    if layer is None:
        pools, layer = [a[None] for a in pools], 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,       # tables, starts, layer
        grid=(B, n_tiles, maxP),
        in_specs=[q_spec, *in_specs],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Nkv * tile * groups, D), jnp.float32),
            pltpu.VMEM((Nkv * tile * groups, 1), jnp.float32),
            pltpu.VMEM((Nkv * tile * groups, 1), jnp.float32),
        ],
    )

    # one query a sequence is the decode step; a window is the
    # speculative verify or a suffix prefill
    name = "paged_attention" if T_in == 1 else "paged_attention_mq"
    with jax.named_scope(name):
        out = pl.pallas_call(
            functools.partial(_extend_kernel, page_size=PS, scale=scale,
                              groups=groups, window=tile, num_kv=Nkv,
                              kv_quant=kv_quant),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, Nkv, T * groups, D), q.dtype),
            interpret=interpret,
            name=name,
        )(tables, starts, jnp.asarray(layer, jnp.int32).reshape(1),
          qg, *pools)
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
