"""Paged decode attention over a block-table-indexed KV cache.

The reference's KVCacheManager is dead code — instantiated but never read
during generation, so every decode step recomputes the full prefix
(reference serve/server.py:57-87 + :199-204, defect SURVEY §2.4.2). This op
is the real thing: KV lives in fixed-size pages in HBM, each sequence owns a
block table of page indices, and decode attends through the table.

Layout: pages [L, num_pages, Nkv, page_size, D], one pool for all layers.
Every function here takes the pool WHOLE plus ``layer`` (a traced int32
scalar) and addresses ``pages[layer, phys, ...]`` directly, so the serve
programs carry the donated pools through their layer loop and update them
in place: no layer's slab is ever sliced out or stacked back. ``layer=None``
is the one-layer case: pages [num_pages, Nkv, page_size, D]. Static shapes
throughout — the block table has a fixed ``max_pages_per_seq`` width and
unused entries point at the reserved scratch page 0, so XLA compiles one
program regardless of how many sequences or tokens are live (SURVEY §7.3.2:
continuous batching under XLA static shapes).

The gather-based implementation below is the portable baseline; on TPU the
same layout is consumed by the Pallas kernel in ops/paged_attention_pallas
that streams pages HBM->VMEM without materialising the gathered cache.
``paged_attention(impl="auto")`` dispatches between them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models.layers import NEG_INF


from ..utils.platform import kernel_impl, report_impl
from .quantization import QuantTensor


def _at(layer, *index):
    """The index of ``pages[layer, *index]`` in an [L, NP, ...] pool, or of
    ``pages[*index]`` in one layer's [NP, ...] pages (``layer`` None)."""
    return index if layer is None else (layer, *index)


LANES = 128     # the minor axis of the chip's tiles


def heads_a_row(num_kv_heads: int, head_dim: int) -> int:
    """KV heads that share one row of a page: 1, or, for heads of 64 values
    (an even number of them), 2: a PAIR of heads side by side on the 128
    lanes, the pool laid out [L, NP, Nkv / 2, PS, 128]. Chosen by the head
    size alone and never for 128. HBM bytes are those of head_dim 64; the
    page-streaming kernel reads such a pool as Nkv / 2 heads of 128 with
    each query padded onto its head's half of the lanes
    (``_on_own_lanes``), so its dots do twice the work in a kernel that is
    bound by bytes."""
    return 2 if 2 * head_dim == LANES and num_kv_heads % 2 == 0 else 1


def _heads_packed(pages, head_dim: int) -> int:
    """How many heads of ``head_dim`` a row of ``pages`` holds (1: the plain
    layout; a latent pool's padded row is also 1 head)."""
    f = pages.shape[-1] // head_dim
    return f if f > 1 and f * head_dim == LANES == pages.shape[-1] else 1


def _pack_heads(new_kv: jax.Array, pages) -> jax.Array:
    """``new_kv`` [..., Nkv, D] as the pool's rows [..., Nkv / f, f D] (heads
    are contiguous with their values: the reshape moves nothing)."""
    f = _heads_packed(pages, new_kv.shape[-1])
    if f == 1 or new_kv.shape[-2] != pages.shape[-3] * f:
        return new_kv
    return new_kv.reshape(*new_kv.shape[:-2], pages.shape[-3],
                          pages.shape[-1])


def _on_own_lanes(q: jax.Array, groups: int, f: int) -> jax.Array:
    """Queries [..., Nq, D] padded to the packed pool's row width
    [..., Nq, f D]: a query head whose KV head is the j-th of its row keeps
    its values on the j-th D lanes and zeros elsewhere, so its dot with a
    packed key row is its dot with its own head's key."""
    Nq, D = q.shape[-2:]
    half = (jnp.arange(Nq) // groups) % f                        # [Nq]
    lanes = jnp.arange(f * D) // D                               # [f D]
    own = half[:, None] == lanes[None]                           # [Nq, f D]
    return jnp.where(own, jnp.tile(q, (1,) * (q.ndim - 1) + (f,)), 0)


def _own_lanes_of(out: jax.Array, groups: int, f: int) -> jax.Array:
    """The packed kernel's output [..., Nq, f D] (every head of the row's
    values under the query's probabilities) -> [..., Nq, D]: each query
    head keeps the lanes of its own KV head."""
    Nq, W = out.shape[-2:]
    D = W // f
    half = (jnp.arange(Nq) // groups) % f
    parts = out.reshape(*out.shape[:-1], f, D)
    return jnp.take_along_axis(
        parts, half.reshape((1,) * (out.ndim - 2) + (Nq, 1, 1)),
        axis=-2)[..., 0, :]


def _resolve_impl(op: str, impl: str, q: jax.Array, pages=None
                  ) -> tuple[str, bool]:
    """``auto`` -> the page-streaming Pallas kernel on TPU, the gather
    baseline elsewhere; returns (impl, interpret). The choice is reported
    (once per traced program) — a gather path on the chip is a line in
    the log, never a silent detour."""
    on_tpu = jax.default_backend() == "tpu"
    # the width of a page's rows: head_dim, or a pair of 64-wide heads
    D = q.shape[-1] if pages is None else pages.shape[-1]
    detail = f"q{tuple(q.shape)}"
    if D != q.shape[-1]:
        detail += f" over rows of {D}"
    if impl == "auto":
        # the Pallas kernels tile a page's rows onto the 128-lane axis; a
        # row under 128 (heads of 64 that could not pair: an odd count, a
        # quantised or sharded pool; gpt-test's 16) fails Mosaic layout
        # inference ("unsupported shape cast") — those shapes take the
        # gather path instead of crashing the serve engine
        if not on_tpu:
            impl, detail = "gather", detail + f", backend {jax.default_backend()}"
        elif D % LANES:
            impl, detail = "gather", detail + f", page row {D} % 128 != 0"
        else:
            impl = "pallas"
    else:
        detail += ", requested by caller"
    report_impl(op, kernel_impl() if impl == "pallas" else impl, detail)
    return impl, impl == "pallas" and not on_tpu


@jax.tree_util.register_pytree_node_class
class QuantPages(QuantTensor):
    """int8 KV pages + per-token absmax scales: values [..., NP, Nkv, PS, D]
    int8, scale [..., NP, Nkv, PS] fp32 (~3% overhead at D=128, vs 50%
    saved on the page data — 2x KV capacity per HBM byte and half the
    decode-attention KV streaming).

    Scale layout: one dense PER-PAGE tensor of row scales with NO
    trailing singleton. A [..., PS, 1] layout makes the Pallas scale block
    a [Nkv, PS, 1] ref — a degenerate 1-wide lane tile Mosaic pads to a
    full [8, 128] vector register per scale — and every whole-page merge
    has to carry the dangling axis. [..., Nkv, PS] makes
    the per-page scale block a clean [Nkv, PS] tile: the decode kernel
    gets a slot's tiles gathered through its block table (a 64-wide
    minor dimension cannot be sliced out of HBM by the kernel's own
    page copies) and dequantizes in VMEM.

    The (values, scale) pytree mechanics come from QuantTensor; the
    distinct TYPE keeps page buffers out of ``cast_params``' weight-dequant
    path and marks every k_pages/v_pages consumer's isinstance branch.
    As a registered pytree it drops into jits, donation, ``lax.scan``
    carries/xs (the layer-stacked [L, ...] axis slices both leaves), and
    device_put sharding unchanged."""

    @property
    def dtype(self):
        return self.values.dtype

    def astype(self, dtype):
        # appease generic tree-casts (ops never cast pages; keep quantized)
        return self

    def dequant(self, dtype=jnp.float32):
        # scale has no keepdim axis (unlike QuantTensor weights) — the
        # row scale broadcasts over D explicitly
        from .quantization import dequantize_int8_rows
        return dequantize_int8_rows(self.values, self.scale, dtype)


@jax.tree_util.register_pytree_node_class
class Int4Pages(QuantPages):
    """Packed-int4 KV pages: values uint8 [..., NP, Nkv, PS/2, D] (two
    consecutive page slots per byte — low nibble = even slot), scale fp32
    [..., NP, Nkv, PS] (one per-token row scale, SAME kernel-friendly
    per-page tile as QuantPages). ~4% overhead at D=128 vs 75% saved on
    the page data — 2x decode slots per HBM byte over int8, 4x over bf16.

    Packing along the PAGE-SLOT axis (not head_dim) keeps D minor, so
    the Pallas page tile stays a clean [Nkv, PS/2, D] 128-lane block
    that the kernel copies like any other page, and unpack in VMEM is a
    sublane relabel (ops.quantization.unpack_int4_rows) — the KV-side
    twin of the weight kernels' [.., in/2, out] layout lesson.

    ``shape`` reports the LOGICAL [..., NP, Nkv, PS, D] geometry (like
    Quant4Tensor) so shape-inspecting consumers — attention impls,
    recover()'s reallocation, validation — see page-slot counts, not the
    packed layout. Type-driven dispatch (the PR-1 seam): every
    k_pages/v_pages consumer's isinstance chain tests Int4Pages BEFORE
    QuantPages (it subclasses it, inheriting the pytree mechanics and
    the cast_params exclusion)."""

    @property
    def shape(self):
        s = self.values.shape
        return (*s[:-2], s[-2] * 2, s[-1])

    def dequant(self, dtype=jnp.float32):
        from .quantization import dequantize_int4_rows
        return dequantize_int4_rows(self.values, self.scale, dtype)


@jax.tree_util.register_pytree_node_class
class SplitPages:
    """The K (or V) pages of a stack with WINDOW layers
    (``ModelConfig.layer_types``), two pools under one name: ``full``
    [L_full, NP, Nkv, PS, D], the full layers' pages, a slot's a chain that
    grows with its sequence, and ``window`` [L_win, NP_ring, Nkv, PS, D],
    the window layers', a slot's a RING of ``ring`` pages it holds for life:
    token t's rows of a window layer lie in ring entry ``(t // PS) % ring``
    and are overwritten ``ring`` pages later, when no query can see them any
    more (serve/kv_cache.py ``ring_pages``). ``is_window`` says, a layer of
    the stack, which pool holds it (static, like ``ring``: both are the
    pytree's aux data, so the pair rides jits, donation and scan carries
    as one argument where every other model has one array). A slot's row of
    the block tables holds its chain and, last, its ``ring`` entries
    (``tables_of``)."""

    def __init__(self, full, window, ring: int, is_window: tuple):
        self.full, self.window = full, window
        self.ring, self.is_window = ring, tuple(is_window)

    def tree_flatten(self):
        return (self.full, self.window), (self.ring, self.is_window)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def of(self, full, window) -> "SplitPages":
        """The same split over two other arrays."""
        return SplitPages(full, window, self.ring, self.is_window)

    def replace(self, **pool) -> "SplitPages":
        """... over another ``full=`` or ``window=`` array."""
        return self.of(pool.get("full", self.full),
                       pool.get("window", self.window))

    @property
    def shape(self):
        """The full pool's (what a consumer that asks a pool's geometry
        means: heads, page size and row width are the window pool's too)."""
        return self.full.shape

    @property
    def dtype(self):
        return self.full.dtype

    def delete(self) -> None:
        self.full.delete()
        self.window.delete()

    def layer_indices(self) -> tuple:
        """(the stack's layers in the full pool, those in the window pool),
        each in the order of its pool's planes."""
        return (tuple(i for i, w in enumerate(self.is_window) if not w),
                tuple(i for i, w in enumerate(self.is_window) if w))

    def tables_of(self, block_tables: jax.Array) -> tuple:
        """A slot's two tables out of its row [.., chain | ring]: the full
        layers' chain [.., maxP] as it is, and the window layers' as wide,
        logical page p naming ring entry ``p % ring``: every reader and
        writer of pages addresses ``table[b, position // PS]`` and walks a
        ring without knowing it."""
        chain = block_tables[..., :-self.ring]
        ring = block_tables[..., -self.ring:]
        at = jnp.arange(chain.shape[-1], dtype=jnp.int32) % self.ring
        return chain, jnp.take(ring, at, axis=-1)


def quantize_kv_token(new_kv: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(row, head) absmax int8 of a token's K or V [..., Nkv, D] ->
    (int8 values, fp32 scale [..., Nkv]). One implementation of the
    absmax math lives in ops.quantization (quantize_int8_rows — also the
    helper the fused quantize-on-write path uses)."""
    from .quantization import quantize_int8_rows
    return quantize_int8_rows(new_kv)


def quantize_kv_token_int4(new_kv: jax.Array) -> tuple[jax.Array, jax.Array]:
    """int4 sibling of quantize_kv_token: [..., Nkv, D] -> (UNPACKED int8
    values in [-7, 7], fp32 scale [..., Nkv]). Packing happens at the
    page merge (pack_int4_rows along the page-slot axis) — quantization
    granularity is identical to int8, only the storage width changes."""
    from .quantization import quantize_int4_rows
    return quantize_int4_rows(new_kv)


def paged_attention(
    q: jax.Array,            # [B, Nq, D] — one query token per sequence
    k_pages: jax.Array,      # [L, NP, Nkv, PS, D] ([NP, ...] if layer is None)
    v_pages: jax.Array,
    block_tables: jax.Array, # [B, maxP] int32 physical page ids
    lengths: jax.Array,      # [B] int32 — tokens already in cache INCLUDING
                             #   the current one (i.e. attend to [0, lengths))
    impl: str = "auto",      # auto | pallas | gather
    layer=None,              # int32 scalar: which layer's pages to read
) -> jax.Array:
    """Decode attention: each row attends over its paged KV prefix.

    Returns [B, Nq, D] in q.dtype. GQA via head-group broadcast, softmax in
    fp32 — numerics match models.layers.dot_product_attention.

    ``impl="auto"`` uses the page-streaming Pallas kernel on TPU (HBM
    traffic proportional to live length) and this gather baseline
    elsewhere.
    """
    impl, interpret = _resolve_impl("paged_attention", impl, q, k_pages)
    if impl == "pallas":
        # (the T = 1 case of the window kernel: one body for both)
        return paged_attention_multi(
            q[:, None], k_pages, v_pages, block_tables,
            lengths.astype(jnp.int32) - 1, impl=impl, layer=layer)[:, 0]
    return _gather_attention(q, k_pages, v_pages, block_tables, lengths,
                             layer)


def _gather_attention(q, k_pages, v_pages, block_tables, lengths,
                      layer=None, window: int = 0):
    """The portable baseline: materialise each row's [Nkv, maxP*PS, D]
    prefix through the block table, then plain masked attention (``window``
    > 0: over the last ``window`` of the ``lengths`` keys alone; what a
    ring's table names at the positions before them is masked)."""
    B, Nq, D = q.shape
    f = _heads_packed(k_pages, D)
    Nkv, PS = k_pages.shape[-3] * f, k_pages.shape[-2]
    maxP = block_tables.shape[1]
    groups = Nq // Nkv
    idx = _at(layer, block_tables)      # one gather: pages[layer, table]

    def gather(pages):
        # [B, maxP, Nkv, PS, D] -> [B, Nkv, Lmax, D]; quantized pages
        # dequant right after the gather (the matmuls below run fp32
        # anyway). Int4Pages unpack along the page-slot axis first.
        if isinstance(pages, Int4Pages):
            from .quantization import unpack_int4_rows
            vals = unpack_int4_rows(pages.values[idx], axis=-2)
            g = (vals.astype(jnp.float32)
                 * pages.scale[idx][..., None]).astype(q.dtype)
        elif isinstance(pages, QuantPages):
            g = (pages.values[idx].astype(jnp.float32)
                 * pages.scale[idx][..., None]).astype(q.dtype)
        else:
            g = pages[idx]
        if f > 1:       # a row holds f heads side by side: apart again
            g = g.reshape(B, maxP, Nkv // f, PS, f, D).transpose(
                0, 1, 2, 4, 3, 5).reshape(B, maxP, Nkv, PS, D)
        return g.transpose(0, 2, 1, 3, 4).reshape(B, Nkv, maxP * PS, D)

    k = gather(k_pages)
    v = gather(v_pages)

    qg = q.reshape(B, Nkv, groups, D)
    scores = jnp.einsum("bhgd,bhkd->bhgk", qg, k.astype(q.dtype),
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(D))

    kv_pos = jnp.arange(maxP * PS, dtype=jnp.int32)[None, :]        # [1,Lmax]
    valid = kv_pos < lengths[:, None]                                # [B,Lmax]
    if window:
        valid = valid & (kv_pos >= lengths[:, None] - window)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgk,bhkd->bhgd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Nq, D).astype(q.dtype)


def write_window_to_pages(
    pages: jax.Array,          # [L, NP, Nkv, PS, D] ([NP, ...] if no layer)
    new_kv: jax.Array,         # [B, T, Nkv, D] — T consecutive tokens/slot
    block_tables: jax.Array,   # [B, maxP]
    start_positions: jax.Array,  # [B] int32 — position of new_kv[:, 0]
    write_ok: jax.Array = None,  # [B, T] bool
    layer=None,                # int32 scalar: which layer's pages to write
) -> jax.Array:
    """The window write: what every serve program writes K and V (or a
    latent row) with (``write_token_to_pages`` over B*T rows is its
    reference). It stages a part of the pool that holds the window, merges
    the window's rows in, and scatters the staged part back, by one of two
    routes chosen from what the call can see (static, reported at trace
    time as ``window_page_write``):

    WHOLE PAGES (``_write_window_to_whole_pages``: T > 16, every window
    over ``QuantPages`` / ``Int4Pages``, and a page that is not whole
    sublane tiles). A slot's T consecutive tokens span at most
    n = (T + 2 PS - 2) // PS physical pages. This gathers those n*B pages
    out of the pool, merges the window in registers (a float32 one-hot
    select over the n*PS staging positions), and scatters n*B WHOLE pages
    back: regular page-sized DMAs that leave the pool in the layout the
    Pallas kernel reads. The B*T-row scatter does not: compiled for the v5e
    it makes XLA keep the carried pool slot-major and copy it WHOLE to the
    kernel's layout and back in every layer (3.8 GB of temporaries in the
    decode program at the benchmark's shapes, PERF.md 6, PR 26), which is
    why windows longer than a page (a riding piece, suffix and chunked
    prefill) take this route.

    TILES (``_write_window_to_tiles``: 1 <= T <= 16 over a full-precision
    pool whose pages are whole sublane tiles: every decode step's one row,
    a draft-and-verify step's two, a denoise step's eight). What PR 26
    found holds for ROWS; a sublane tile (16 rows of bfloat16, 8 of
    float32) is the unit the pool's device layout is made of, so the page
    axis splits into tiles by a reshape that moves no byte, and gathering
    and scattering the tiles a window touches leaves the pool where it
    stands (compiled for the v5e: tests/test_tpu_compile_selfdraft.py,
    tests/test_tpu_compile_shortconv.py). A draft-and-verify step's window
    of two rows staged two pages of 256 rows a slot and layer and merged
    them through float32, 4.3 ms a step for 1.5 MB of rows; by tiles the
    same write is 0.4 ms (PERF.md 6, PR 54). A decode step's one row
    staged one whole page a slot, pool and layer: 1.07 GB moved a step to
    store 2 MB of K/V rows at 256 slots over pages of 256 rows; its one
    tile is a sixteenth of that (PERF.md 6, PR 56).

    Both routes are asserted equal to the scatter, bit for bit, in
    tests/test_ops.py::test_window_write_matches_row_scatter.

    ``QuantPages`` take the whole-page route with a fused
    quantize-on-write: the window's rows are absmax-quantized once
    ([B, T, Nkv] int8 rows + scales), then values AND scales merge
    through one shared one-hot select and scatter back as whole
    (page, scale-tile) pairs. No per-row scatter (of values and scales
    separately), and no full-precision copy of any cache page is ever
    materialised; not measured on the attached chip: no cell has quantized
    pages (ROADMAP A3).
    Bit-identical to the scatter path (same quantize_int8_rows math,
    untouched rows copied int8/fp32-exact), asserted in
    tests/test_kv_quant.py.

    Masked tokens (write_ok False) and slots whose table entry is scratch
    keep their staging content / write scratch page 0, matching the
    scatter path's semantics.
    """
    new_kv = _pack_heads(new_kv, pages)
    T = new_kv.shape[1]
    rows_a_tile = _window_tile_rows(pages, T)
    report_impl("window_page_write", "tiles" if rows_a_tile else "pages",
                f"T={T} over {pages.dtype}{tuple(pages.shape)}")
    if rows_a_tile:
        return _write_window_to_tiles(pages, new_kv, block_tables,
                                      start_positions, write_ok, layer,
                                      rows_a_tile)
    return _write_window_to_whole_pages(pages, new_kv, block_tables,
                                        start_positions, write_ok, layer)


def _write_window_to_whole_pages(pages, new_kv, block_tables,
                                 start_positions, write_ok, layer):
    """``write_window_to_pages`` by whole pages: n = (T + 2 PS - 2) // PS
    pages a slot gathered, merged by a one-hot select, scattered back."""
    int4 = isinstance(pages, Int4Pages)
    quant = isinstance(pages, QuantPages)
    B, T, Nkv, D = new_kv.shape
    # logical page geometry (Int4Pages.shape reports the UNPACKED slot
    # count; its values buffer holds PS/2 bytes along that axis)
    PS = pages.shape[-2]
    maxP = block_tables.shape[1]
    # T consecutive tokens starting anywhere in a page touch at most this
    # many pages: 1 for T == 1 (it never crosses a boundary: one row over
    # quantized pages, or over a page that is not whole tiles), 2 up to
    # T == PS + 1, 5 and 9 for the 256- and 512-token suffix /
    # chunked-prefill buckets at PS 64
    n_stage = (T + 2 * PS - 2) // PS
    offs = jnp.arange(T, dtype=jnp.int32)
    pos = start_positions[:, None] + offs                     # [B, T]
    p0 = jnp.clip(start_positions // PS, 0, maxP - 1)         # [B]
    lp = jnp.clip(p0[:, None] + jnp.arange(n_stage, dtype=jnp.int32),
                  0, maxP - 1)                                # [B, n]
    phys = jnp.take_along_axis(block_tables, lp, axis=1)      # [B, n]
    # duplicate-page edge (window ending in the last logical page): a
    # staging page clipped onto the one before it would rewrite the SAME
    # page with stale content — redirect it to scratch instead
    phys = phys.at[:, 1:].set(jnp.where(lp[:, 1:] == lp[:, :-1], 0,
                                        phys[:, 1:]))

    off = pos - p0[:, None] * PS                       # [B,T] in [0,n*PS)
    ok = jnp.ones((B, T), bool) if write_ok is None else write_ok
    tok_half = jnp.clip(off // PS, 0, n_stage - 1)            # [B, T]
    tok_phys = jnp.take_along_axis(phys, tok_half, axis=1)    # [B, T]
    ok = ok & (tok_phys != 0)
    onehot = (off[:, :, None] == jnp.arange(n_stage * PS)[None, None]) \
        & ok[:, :, None]                                      # [B,T,nPS]
    hit = onehot.any(axis=1)                                  # [B, nPS]
    # the staging pages are read from, and written back into, the pool
    # itself: pages[layer, phys] -> merge -> pages.at[layer, phys]
    stage, back = _at(layer, phys), _at(layer, phys.reshape(-1))

    def merge_rows(staging, rows, dtype):
        """Select window rows into their staging positions: staging
        [B, n, Nkv, PS, D'] updated from rows [B, T, Nkv, D'] via the
        shared one-hot (exact: each staging position receives at most one
        window row; fp32 select round-trips int8/fp32 payloads bit-exact).
        """
        upd = jnp.einsum("bts,btnd->bsnd", onehot.astype(jnp.float32),
                         rows.astype(jnp.float32))            # [B,nPS,Nkv,D']
        stag = staging.transpose(0, 1, 3, 2, 4).reshape(
            B, n_stage * PS, Nkv, -1)
        merged = jnp.where(hit[:, :, None, None], upd.astype(dtype),
                           stag.astype(dtype))
        merged = merged.reshape(B, n_stage, PS, Nkv, -1).transpose(
            0, 1, 3, 2, 4)
        return merged.reshape(B * n_stage, Nkv, PS, -1)

    if int4:
        # int4 rides the SAME whole-page merge: gathered staging bytes
        # unpack to int8 rows (a sublane relabel), the window's freshly
        # quantized rows select in through the shared one-hot, and the
        # merged page repacks before the whole-page scatter. Untouched
        # rows round-trip unpack->pack bit-exact (nibbles in [-8, 7]),
        # so the merge stays bit-identical to the per-token scatter path
        # (asserted in tests/test_int4_kv.py).
        from .quantization import pack_int4_rows, unpack_int4_rows
        qv, qs = quantize_kv_token_int4(new_kv)  # [B,T,Nkv,D] i8, [B,T,Nkv]
        staging = unpack_int4_rows(pages.values[stage], axis=-2)
        merged_v = merge_rows(staging, qv, jnp.int8)      # [B*n,Nkv,PS,D]
        packed_v = pack_int4_rows(merged_v, axis=-2)
        merged_s = merge_rows(pages.scale[stage][..., None], qs[..., None],
                              jnp.float32)[..., 0]        # [B*n,Nkv,PS]
        return Int4Pages(pages.values.at[back].set(packed_v),
                         pages.scale.at[back].set(merged_s))
    if quant:
        # fused quantize-on-write: one absmax pass over the window's rows,
        # then values and scales ride the same whole-page merge
        qv, qs = quantize_kv_token(new_kv)     # [B,T,Nkv,D] i8, [B,T,Nkv]
        merged_v = merge_rows(pages.values[stage], qv, jnp.int8)
        merged_s = merge_rows(pages.scale[stage][..., None], qs[..., None],
                              jnp.float32)[..., 0]        # [B*n,Nkv,PS]
        return QuantPages(pages.values.at[back].set(merged_v),
                          pages.scale.at[back].set(merged_s))
    merged = merge_rows(pages[stage], new_kv.astype(pages.dtype), pages.dtype)
    return pages.at[back].set(merged)


_MAX_TILE_WINDOW = 16     # rows of the longest window that stages tiles


def _window_tile_rows(pages, T: int) -> int:
    """Rows of the sublane tile a window of ``T`` rows stages instead of
    whole pages (16 of bfloat16, 8 of float32), or 0 where the window takes
    the whole-page route: more than ``_MAX_TILE_WINDOW`` rows, quantized
    pages, or a page that is not whole tiles."""
    if isinstance(pages, QuantPages) or not 1 <= T <= _MAX_TILE_WINDOW:
        return 0
    rows = 32 // jnp.dtype(pages.dtype).itemsize
    return rows if pages.shape[-2] % rows == 0 else 0


def _write_window_to_tiles(pages, new_kv, block_tables, start_positions,
                           write_ok, layer, R: int):
    """``write_window_to_pages`` for a short window over a full-precision
    pool: the page axis is viewed as ``PS / R`` tiles of ``R`` rows (a
    reshape on a tile boundary: no byte moves), the n = (T + 2 R - 2) // R
    tiles a slot's window can touch (one for a single row, which crosses
    nothing; two for 1 < T <= R, the second may be the next page's first)
    are gathered, the window's rows selected in on the pool's own dtype,
    and the tiles scattered back. A staged tile that takes no row (the
    window inside one tile, a tile past the table's last page, masked
    rows, a slot over scratch) goes to scratch page 0."""
    B, T, Nkv, D = new_kv.shape
    PS = pages.shape[-2]
    maxP = block_tables.shape[1]
    tpp = PS // R                                   # tiles a page
    n = (T + 2 * R - 2) // R
    g0 = jnp.clip(start_positions // R, 0, maxP * tpp - 1)    # [B]
    g = g0[:, None] + jnp.arange(n, dtype=jnp.int32)          # [B, n]
    phys = jnp.take_along_axis(
        block_tables, jnp.clip(g // tpp, 0, maxP - 1), axis=1)
    phys = jnp.where(g < maxP * tpp, phys, 0)                 # [B, n]
    # row j of the window lands at staging row ``rel`` of the n * R (a
    # row before position 0 or past the table's end at none of them)
    rel = (start_positions - g0 * R)[:, None] + jnp.arange(
        T, dtype=jnp.int32)                                   # [B, T]
    ok = jnp.take_along_axis(
        phys, jnp.clip(rel // R, 0, n - 1), axis=1) != 0
    if write_ok is not None:
        ok = ok & write_ok
    hit = (rel[:, :, None] == jnp.arange(n * R, dtype=jnp.int32)
           ) & ok[:, :, None]                                 # [B, T, n R]
    used = hit.any(axis=1).reshape(B, n, R).any(axis=2)       # [B, n]
    phys = jnp.where(used, phys, 0)
    tile = jnp.where(used, g % tpp, 0)
    tiles = pages.reshape(*pages.shape[:-2], tpp, R, D)
    at = _at(layer, phys, slice(None), tile)      # -> [B, n, Nkv, R, D]
    staged = tiles[at]
    rows = new_kv.astype(pages.dtype)
    hit = hit.reshape(B, T, n, R)
    for j in range(T):      # a staged row takes at most one of the T rows
        staged = jax.lax.select(
            jax.lax.broadcast_in_dim(hit[:, j], staged.shape, (0, 1, 3)),
            jax.lax.broadcast_in_dim(rows[:, j], staged.shape, (0, 2, 4)),
            staged)
    return tiles.at[at].set(staged).reshape(pages.shape)


def paged_attention_multi(
    q: jax.Array,              # [B, T, Nq, D] — T consecutive tokens/slot
    k_pages: jax.Array,        # [L, NP, Nkv, PS, D] ([NP, ...] if no layer)
    v_pages: jax.Array,
    block_tables: jax.Array,   # [B, maxP]
    start_positions: jax.Array,  # [B] int32 — position of q[:, 0]
    impl: str = "auto",
    layer=None,                # int32 scalar: which layer's pages to read
    block: int = 0,            # static: > 0 = the block rule
    window: int = 0,           # static: > 0 = a WINDOW layer's keys
) -> jax.Array:
    """Multi-query paged attention: query j of slot b attends causally over
    [0, start_b + j] through the pages (the window's own K/V must already
    be written). Returns [B, T, Nq, D]. With ``block`` > 0 (generation by
    diffusion over blocks: block-aligned starts) query j sees its whole
    block: [0, start_b + (j // block + 1) * block). With ``window`` > 0 (a
    window layer over its ring of pages, ``SplitPages.tables_of``) query j
    sees the last ``window`` keys alone, (start_b + j - window, start_b +
    j]: the kernel walks from the first page that holds one (the name it
    runs under is ``window_attention``), the fallback masks.

    On TPU this runs the head-folded Pallas kernel (each page DMA'd once
    per SLOT — all kv heads, all T queries); the fallback flattens to
    [B*T] rows of the single-token path — correct everywhere, but it
    re-streams the prefix T times (the motivation for the kernel; its
    cost is not measured on the attached chip).
    """
    B, T, Nq, D = q.shape
    # the kernel wants a page's rows whole lane tiles (Mosaic): heads of
    # 128, or heads of 64 in pairs (``heads_a_row``); any other small head
    # serves via the gather fallback. Every window size takes the kernel
    # (it tiles long windows itself).
    op = "window_attention" if window else "paged_attention"
    impl, interpret = _resolve_impl(
        op if T == 1 else f"{op}_multi", impl, q, k_pages)
    if block and window:
        raise ValueError("the block rule and a window are not carried "
                         "together")
    if impl == "pallas":
        from .paged_attention_pallas import paged_attention_pallas_multi
        f = _heads_packed(k_pages, D)
        if window:
            if f > 1:
                raise ValueError("a window layer's pages hold heads of 128 "
                                 "(heads of 64 in pairs have no window "
                                 "kernel)")
            return paged_attention_pallas_multi(
                q, k_pages, v_pages, block_tables, start_positions,
                layer=layer, interpret=interpret, sliding=window)
        if f > 1:
            groups = Nq // (k_pages.shape[-3] * f)
            out = paged_attention_pallas_multi(
                _on_own_lanes(q, groups, f), k_pages, v_pages, block_tables,
                start_positions, layer=layer, interpret=interpret,
                block=block, scale=float(D) ** -0.5)
            return _own_lanes_of(out, groups, f)
        return paged_attention_pallas_multi(
            q, k_pages, v_pages, block_tables, start_positions,
            layer=layer, interpret=interpret, block=block)
    if block:       # row j sees up to the last position of its block
        ends = (jnp.arange(T, dtype=jnp.int32) // block + 1) * block - 1
        flat_pos = (start_positions[:, None] + ends).reshape(B * T)
    else:
        flat_pos = (start_positions[:, None]
                    + jnp.arange(T, dtype=jnp.int32)).reshape(B * T)
    out = _gather_attention(
        q.reshape(B * T, Nq, D), k_pages, v_pages,
        jnp.repeat(block_tables, T, axis=0), flat_pos + 1, layer, window)
    return out.reshape(B, T, Nq, D)


def pad_to_page_width(x: jax.Array, pages: jax.Array) -> jax.Array:
    """``x`` with its last axis zero-padded to the pool's row width: a latent
    pool stores a row wider than the model makes it (serve/kv_cache.py: the
    TPU compiler refuses a page copy of the row's own width), and a window's
    rows and its absorbed queries are padded alike on their way in."""
    pad = pages.shape[-1] - x.shape[-1]
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))


def write_prompt_to_pages(pages, dense, entries: jax.Array):
    """A COLD prompt's rows into its pages, whole: the third writer, beside
    a token's and a window's. ``dense`` is what a cold prefill's forward
    kept of its batch of ONE prompt, [L, 1, bucket, Nkv, D] (a latent
    model's rows [L, 1, bucket, W]: they go in at the pool's width, one
    "head" a page), laid out as pages [L, bucket / PS, Nkv, PS, D] and SET
    at ``entries`` [bucket / PS], the slot's table entries (the bucket's
    padding names scratch page 0 or lands behind the slot's length, where
    nothing reads it). ``QuantPages`` and ``Int4Pages`` quantize on the way
    in, a scale a token, as the other two writers do. Given a pair of pools
    and caches (K and V; a None pool is handed through) every cache is laid
    out before any pool is written: the cold program's order."""
    if isinstance(pages, tuple) and isinstance(pages[0], SplitPages):
        # a stack with window layers: the full layers' planes of the caches
        # into the chain (``entries[0]``), the window layers' into the ring
        # (``entries[1]``: the prompt's last ``ring`` pages name their ring
        # entries, every earlier page, overwritten before a query could
        # read it, the scratch page)
        both = [write_prompt_to_pages(
            tuple(getattr(p, pool) for p in pages),
            tuple(d[jnp.asarray(layers)] for d in dense), entries[e])
            for e, (pool, layers) in enumerate(zip(
                ("full", "window"), pages[0].layer_indices()))]
        return tuple(p.of(full, window)
                     for p, full, window in zip(pages, *both))
    if isinstance(pages, tuple):
        laid = [None if d is None else _prompt_page_layout(p, d)
                for p, d in zip(pages, dense)]
        return tuple(p if d is None else _set_pages(p, d, entries)
                     for p, d in zip(pages, laid))
    return _set_pages(pages, _prompt_page_layout(pages, dense), entries)


def _prompt_page_layout(pages, dense: jax.Array) -> jax.Array:
    PS = pages.shape[-2]
    dense = dense[:, 0]
    L, bucket = dense.shape[:2]
    if dense.ndim == 3:         # latent rows: no head axis
        return pad_to_page_width(dense, pages).reshape(
            L, bucket // PS, 1, PS, -1).astype(pages.dtype)
    # dense [L, bucket, Nkv, D] -> paged [L, n_pages, Nkv, PS, D] (heads
    # of 64 in pairs: [.., Nkv / 2, PS, 128])
    dense = _pack_heads(dense, pages)
    return dense.reshape(L, bucket // PS, PS,
                         *dense.shape[2:]).transpose(0, 1, 3, 2, 4)


def _set_pages(pages, dense: jax.Array, entries: jax.Array):
    if isinstance(pages, Int4Pages):
        # same per-token absmax granularity as int8, then the whole-page
        # pack along the slot axis ([.., PS, D] -> [.., PS/2, D] bytes)
        from .quantization import pack_int4_rows
        qv, sc = quantize_kv_token_int4(dense)
        return Int4Pages(
            pages.values.at[:, entries].set(pack_int4_rows(qv, axis=-2)),
            pages.scale.at[:, entries].set(sc))
    if isinstance(pages, QuantPages):
        # absmax over D gives the per-token scale [L, nP, Nkv, PS]: exactly
        # the per-page scale-tile layout, no reshape
        qv, sc = quantize_kv_token(dense)
        return QuantPages(pages.values.at[:, entries].set(qv),
                          pages.scale.at[:, entries].set(sc))
    return pages.at[:, entries].set(dense)


def write_token_to_pages(
    pages: jax.Array,        # [L, NP, Nkv, PS, D] ([NP, ...] if no layer)
    new_kv: jax.Array,       # [B, Nkv, D] — this step's K or V
    block_tables: jax.Array, # [B, maxP]
    positions: jax.Array,    # [B] int32 — slot-local position to write
    active: jax.Array = None,  # [B] bool — rows past their stop write scratch
    layer=None,              # int32 scalar: which layer's pages to write
) -> jax.Array:
    """Scatter one token per sequence into its page. Rows whose table entry
    is the scratch page (0) — or whose ``active`` mask is False (multi-step
    decode continuing past a row's token budget) — harmlessly overwrite
    scratch page 0 instead of corrupting pages beyond the block table.
    ``QuantPages`` get the token quantized per (row, head) on the way in."""
    new_kv = _pack_heads(new_kv, pages)
    page_size = pages.shape[-2]
    maxP = block_tables.shape[1]
    logical_page = jnp.clip(positions // page_size, 0, maxP - 1)
    offset = positions % page_size
    phys = jnp.take_along_axis(block_tables, logical_page[:, None],
                               axis=1)[:, 0]                         # [B]
    if active is not None:
        phys = jnp.where(active, phys, 0)
    row = _at(layer, phys, slice(None), offset)   # one token row per slot
    if isinstance(pages, Int4Pages):
        # two tokens share a byte along the page-slot axis, so a single-
        # token write is a read-modify-write of its byte column: fetch
        # [B, Nkv, D] bytes, splice the token's nibble into its half,
        # write the column back. The sibling nibble is untouched — the
        # scatter path stays bit-identical to the whole-page merge.
        qv, scale = quantize_kv_token_int4(new_kv)        # [B,Nkv,D] i8
        nib = (qv & 0xF).astype(jnp.uint8)
        byte = offset // 2
        col = _at(layer, phys, slice(None), byte)
        cur = pages.values[col]                           # [B,Nkv,D] u8
        is_lo = (offset % 2 == 0)[:, None, None]
        new = jnp.where(is_lo, (cur & 0xF0) | nib,
                        (cur & 0x0F) | (nib << 4)).astype(jnp.uint8)
        return Int4Pages(
            pages.values.at[col].set(new),
            pages.scale.at[row].set(scale))
    if isinstance(pages, QuantPages):
        qv, scale = quantize_kv_token(new_kv)
        return QuantPages(pages.values.at[row].set(qv),
                          pages.scale.at[row].set(scale))
    return pages.at[row].set(new_kv.astype(pages.dtype))
