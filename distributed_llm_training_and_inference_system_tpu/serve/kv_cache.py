"""Paged KV cache: device-resident pages + host-side page allocator.

Replaces the reference's LRU-dict KVCacheManager that generation never reads
(reference serve/server.py:57-87, defect SURVEY §2.4.2). Design is
vLLM-style paging mapped onto XLA's static-shape world:

- All layers' pages live in two arrays [L, num_pages, Nkv, page_size, D] in
  HBM (one allocation, no fragmentation).
- Page 0 is reserved scratch: every unused block-table entry points at it,
  so the jitted decode step can run over ALL slots every step — inactive
  slots write into scratch and read garbage that their length mask hides.
- Allocation/free is host-side (cheap integer bookkeeping between device
  steps); the device only ever sees the dense block_tables array.
"""

from __future__ import annotations

import hashlib
import logging
import math
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config.schema import ModelConfig
from ..analysis.annotations import engine_thread_only

logger = logging.getLogger("llmctl.serve.kv_cache")


# What a KIND of model refuses, and why: kind -> {feature -> reason}. A
# sequence's state is K/V pages; a model that keeps anything else a slot
# (recurrent state, one latent pool, a half-denoised window) carries it
# through a feature or refuses the feature BY NAME: none may run and be
# silently wrong. ``refuse`` raises; ``refused`` answers (prefix reuse and
# riding are turned OFF and counted instead). The first kind that refuses
# speaks.
# (PR 46: a ``K`` model with a snapshot pool follows a prefix hit THROUGH
# its recurrent state, one snapshot a prompt: ``keeps_snapshots``. The rows
# that MOVE a sequence, below, still wait for a snapshot in their payloads)
_MOVES_PAGES = ("it re-enters or moves K/V pages, and the layers' recurrent "
                "state is not in them; ROADMAP C2")
REFUSED = {
    # a model served with its next-token prediction module (``mtp_layers``;
    # ``speculative: mtp``, serve/decode.py ``draft_verify_scan``): the
    # module's rows are one more layer of the latent pool, a slot carries a
    # draft beside its last token, and a decode step is a window of two rows
    "self_drafting": {
        # OFF and counted an admission (``decode.can_carry``)
        "riding": "a piece wants a step of T = 1, its step is a window of "
                  "the last sure token and its draft; ROADMAP B7",
        "preemption: swap": "a swapped slot would carry a draft and the "
                            "module's rows, and the swap payload is a K and "
                            "a V pool; a preempted request is recomputed; "
                            "ROADMAP B7",
        "measure_device_times": "its decode probe times a token a step; a "
                                "draft-and-verify step's time is the "
                                "benchmark's serve_programs."
                                "selfdraft_step_device_ms",
        "speculative": "n-gram drafts are verified over K/V pages; a model "
                       "with a prediction module drafts for itself over "
                       "its latent pages (speculative: mtp); ROADMAP B7",
    },
    # ``M`` layers alone: ``K`` layers CARRY a chunk (ops/kda.py
    # ``recur_chunk``), and a ``K`` model with latent attention must chunk.
    # The ``M`` form exists (a riding piece runs it), but no cell and no
    # engine test has run its chunk PROGRAMS (ROADMAP B8)
    "state_space": {"chunked_prefill_tokens": _MOVES_PAGES},
    "recurrent": {
        "speculative": _MOVES_PAGES,
        "preemption: swap": _MOVES_PAGES,
        "page payload": "it carries K/V pages, and the slot's recurrent "
                        "state is not in them; ROADMAP C2",
        "fleet prefix fetch": "fetched pages carry no recurrent state",
        "fleet serving": "migration, prefill/decode handoff, prefix fetch "
                         "and the tiered KV store move K/V pages, and a "
                         "slot's recurrent state is not in them; ROADMAP C2",
        "measure_device_times": "its probes write scratch pages, and would "
                                "arm and advance live slots' state",
        # OFF (it is on by default) where no SNAPSHOT pool exists
        # (``ServeConfig.state_snapshot_entries`` 0, or ``M`` or ``C``
        # layers, whose layouts have no take / arm pair yet: a ``C`` model's
        # snapshot would be its windows at a page boundary, two rows a
        # layer): a page hit would skip tokens
        # whose recurrent state nobody kept. With a pool, a ``K`` model
        # keeps ONE snapshot a prompt, at its last whole page boundary, and
        # a hit is followed as far as a snapshot stands (``lookup_prefix``)
        "prefix_caching": "no snapshot of the recurrent state at a page "
                          "boundary is kept (state_snapshot_entries 0, or "
                          "state-space or short-convolution layers): no "
                          "page hash is registered "
                          "or looked up, a repeated prompt is prefilled "
                          "again",
    },
    # (prefix reuse stays ON: a latent page is a function of the token
    # prefix exactly as a K/V page is)
    "latent": {
        **dict.fromkeys(
            ("speculative", "preemption: swap"),
            "n-gram drafts are verified over K/V pages alone (a latent "
            "model with a prediction module drafts for itself: "
            "speculative: mtp), and the swap payload is a K and a V pool; "
            "ROADMAP B4, B7"),
        "kv_quantization": "a latent row is every head's keys AND values: "
                           "no quantised layout or kernel for it exists; "
                           "ROADMAP B4",
        "page payload": "the payload schema is a K and a V pool; latent "
                        "pages in swap and fleet transfer are ROADMAP B4",
        "fleet prefix fetch": "the page payload is a K and a V pool; "
                              "ROADMAP B4",
    },
    # (the prefix cache stays ON: a page is a whole number of blocks, so a
    # whole page's K/V depend on nothing after the page)
    "diffusion": {
        **dict.fromkeys(
            ("speculative", "preemption: swap", "tensor_parallel"),
            "a draft is verified a token at a time, a swapped slot would "
            "carry a half-denoised window, and the block kernel is opaque "
            "to GSPMD; a preempted request is recomputed from its last "
            "finished block"),
        "measure_device_times": "its decode probe times a token a step; a "
                                "denoise forward's time is the benchmark's "
                                "serve_programs.diffusion_forward_device_ms",
        # OFF and counted an admission (``decode.can_carry``)
        "riding": "a piece wants a step of T = 1, its step is a window",
    },
    # a looped stack (``total_ut_steps`` > 1): the pools' planes are passes
    # x layers and every program addresses them so (``decode.
    # extend_step_forward``), so prefix reuse, chunked and suffix prefill,
    # riding, both kinds of preemption, the page payload (its planes are
    # the pool's) and n-gram verification are what they are for any K/V
    # model (tests/test_ouro.py runs each). What no test or cell has run
    # over such a pool is refused by name
    "looped": {
        **dict.fromkeys(
            ("fleet serving", "fleet prefix fetch"),
            "no fleet test has moved pages of passes x layers planes "
            "between replicas; ROADMAP B13"),
        "kv_quantization": "no test or cell has run a quantised pool of "
                           "passes x layers planes; ROADMAP B13",
        "measure_device_times": "its probes have not been run over a "
                                "looped stack's programs; the benchmark's "
                                "serve_programs.decode_step_device_ms reads "
                                "the step",
    },
    # a stack with WINDOW layers (``ModelConfig.layer_types``): a slot's
    # window layers keep a RING of pages it holds for life, its full layers
    # a chain (``PagedKVCache``). Whatever would read, move or share a page
    # of the ring as if it held the tokens its place in a chain says is
    # refused by name
    "windowed": {
        # OFF and counted an admission
        "prefix_caching": "a page hit in the full layers is no hit in a "
                          "ring that was overwritten since: it needs a "
                          "snapshot of the ring at a page boundary (the "
                          "snapshot pool in a second layout); no page hash "
                          "is registered or looked up; ROADMAP B3",
        **dict.fromkeys(
            ("preemption: swap", "page payload", "fleet serving",
             "fleet prefix fetch"),
            "the page payload is a chain of K and V pages, and a slot's "
            "ring is not in it; a preempted request is recomputed; "
            "ROADMAP B3"),
        "speculative": "a draft-and-verify window may be rolled back, and "
                       "the rows it overwrote in the ring are gone; "
                       "ROADMAP B3",
        "kv_quantization": "no quantised layout of the ring pool, and no "
                           "window term in the quantised kernel's tests; "
                           "ROADMAP B3",
        "tensor_parallel": "pages sharded over heads take the gather "
                           "route, which gathers the ring at the table's "
                           "whole width; ROADMAP B3",
        "measure_device_times": "its probes write scratch pages through one "
                                "table; the benchmark's serve_programs."
                                "decode_step_device_ms reads the step",
    },
}


# pages of a chunk of a model with window layers where none is stated
# (``InferenceEngine._chunk_tokens``): 256 rows over pages of 128, a ring of
# 8 + 2
WINDOW_CHUNK_PAGES = 2


def ring_pages(window: int, page_size: int, rows: int = 0) -> int:
    """Pages of a slot's RING: the least count for which no program
    overwrites a row that a query of the same call may still see. A call
    writes ``rows`` consecutive rows from ``start`` and its first query
    sees back to ``start - window + 1``, so the pages of ``window - 1 +
    rows`` consecutive rows must all be distinct entries: one row a slot
    (a decode step) starts anywhere, ceil((window - 1) / PS) + 1 pages; a
    longer window (a riding piece, a chunk of a prompt) starts on a page,
    ceil((window - 1) / PS) + ceil(rows / PS). 1,024 keys over pages of 128
    with pieces of 128 rows and chunks of 256: 8 + 2 = 10. ``rows`` 0:
    a chunk of ``WINDOW_CHUNK_PAGES`` pages."""
    rows = rows or WINDOW_CHUNK_PAGES * page_size
    return -(-(window - 1) // page_size) + -(-rows // page_size)


def keeps_snapshots(cfg: ModelConfig, snapshot_entries: int) -> bool:
    """Does a cache of ``snapshot_entries`` entries keep snapshots of this
    model's recurrent state? (``K`` layers: ops/kda.py owns the layout.)"""
    return snapshot_entries > 0 and cfg.kda_layers > 0


def refused(cfg: ModelConfig, feature: str, snapshot_entries: int = 0
            ) -> Optional[tuple[str, str]]:
    """(what the model is, why) if a kind of ``cfg`` refuses ``feature``
    (``snapshot_entries``: of the engine's cache; prefix reuse through a
    recurrent state needs a snapshot pool)."""
    if feature == "prefix_caching" and keeps_snapshots(cfg, snapshot_entries):
        return None
    kinds = {
        "self_drafting": cfg.mtp_layers > 0
        and "drafts with its prediction module",
        "state_space": cfg.ssm_layers > 0 and f"has {cfg.recurrent_name}",
        "recurrent": cfg.is_recurrent and f"has {cfg.recurrent_name}",
        "latent": cfg.is_latent and "keeps latent pages",
        "diffusion": cfg.is_diffusion and "generates by diffusion over blocks",
        "looped": cfg.is_looped
        and f"walks its stack {cfg.num_passes} times",
        "windowed": cfg.has_window
        and "keeps its window layers' K/V in a ring of pages",
    }
    for kind, is_a in kinds.items():
        if is_a and feature in REFUSED[kind]:
            return is_a, REFUSED[kind][feature]
    return None


def refuse(cfg: ModelConfig, *features: str, what: str = "",
           advice: str = "") -> None:
    """Raise the refusal of the first of ``features`` that a kind of
    ``cfg`` refuses (named ``what`` where the caller has a closer name)."""
    for feature in features:
        why = refused(cfg, feature)
        if why:
            raise ValueError(f"{cfg.name} {why[0]}: {what or feature} is "
                             f"refused ({why[1]}){advice}")


# The bytes at which a page costs what its bytes cost. The page kernels
# (ops/paged_attention_pallas.py, ops/mla_paged_attention.py) score ONE
# layer's page a loop step, and a step has a serial cost whatever the page
# holds (wait, load, scores, softmax update, values: no ring depth beyond 3
# hides it, PR 28; the latent kernel pays it once for 2 or 4 pages under
# these bytes, PR 64):
# alone on a v5e a page costs max(~0.47-0.5 us, bytes / ~745 GB/s)
# (experiments/paged_kernel_alone.py --sweep, PR 58; the table is PERF.md 6):
# K + V pages of 64 / 128 / 256 KB all read 0.47-0.52 us (140 / 270 / 510-530
# GB/s), 512 KB 0.71-0.74 (714-743 GB/s), 1 MB 1.41-1.43 and 2 MB 2.81 (744-
# 745 GB/s). 512 KB is the first size of that table over 90 % of what the
# largest reads; in a cell, pages of 131 KB read 31-32 % of the HBM peak.
PAGE_COPY_BYTES = 512 * 1024
# ... and the fewest tokens a page holds whatever its rows: a [64, D] tile a
# head (16-token pages measured 2.4x slower, round 3)
MIN_PAGE_TOKENS = 64


def kv_row_bytes(cfg: ModelConfig, itemsize: int = 2,
                 quantized: str = "none") -> int:
    """Bytes one token's K + V occupy in ONE layer's page AS STORED: every
    kv head's K and V (heads of 64 stored in pairs are the same bytes), a
    quantised pool's values and its float32 scale a token and head, or a
    latent pool's ONE padded row."""
    if cfg.is_latent:
        return cfg.mla.page_width * itemsize
    per_head = {
        # packed nibbles (D/2 bytes) + fp32 per-(token, kv-head) scale —
        # the 2x-over-int8 capacity claim
        "int4": cfg.head_dim // 2 + 4,
        # int8 values + fp32 per-(token, kv-head) scale
        "int8": cfg.head_dim + 4,
    }.get(quantized, cfg.head_dim * itemsize)
    return 2 * cfg.num_kv_heads * per_head


def page_size_by_rows(row_bytes: int, most: int) -> int:
    """Tokens a page holds where none are stated: the smallest power of
    two, at least ``MIN_PAGE_TOKENS``, whose copy for ONE layer (the row's
    stored bytes x the tokens) reaches ``PAGE_COPY_BYTES``, and never more
    than ``most`` (a step's carry, ``InferenceEngine.RIDE_ROWS``: the piece
    a decode step carries is a page once a page is that large)."""
    tokens = MIN_PAGE_TOKENS
    while tokens * row_bytes < PAGE_COPY_BYTES and tokens * 2 <= most:
        tokens *= 2
    return tokens


def resolve_page_size(cfg: ModelConfig, serve_cfg, most: int) -> bool:
    """Was ``serve_cfg.kv_block_size`` stated? Where it was not (0) it is
    ``page_size_by_rows`` from here on, WRITTEN BACK onto the caller's
    object: whoever built the engine or the fleet divides by the size the
    engines hash and bucket with (a benchmark's warm-up, the fleet's prefix
    hints, a spawned worker's command line). An object used again for
    another engine then states that size."""
    stated = serve_cfg.kv_block_size > 0
    if not stated:
        serve_cfg.kv_block_size = page_size_by_rows(
            kv_row_bytes(cfg, jnp.dtype(serve_cfg.dtype).itemsize,
                         serve_cfg.kv_quantization), most)
    return stated


def prefix_page_hashes(tokens, page_size: int) -> list[bytes]:
    """Chain hashes for every FULL page of a token prefix.

    ``h_i`` digests tokens[0 : (i+1)*page_size] (via the chain), because a
    page's K/V content depends on the *entire* prefix through attention —
    two prompts may share page i only if they agree on every token through
    its end. Only full pages are shareable: a partially-filled page keeps
    receiving decode writes and stays private to its slot.
    """
    arr = np.asarray(tokens, np.int32)
    out, h = [], b""
    for i in range(len(arr) // page_size):
        h = hashlib.blake2b(
            h + arr[i * page_size:(i + 1) * page_size].tobytes(),
            digest_size=16).digest()
        out.append(h)
    return out


def slice_page_payload(content: dict, n: int) -> dict:
    """First ``n`` pages of an ``extract_pages``-schema payload (plain
    arrays or quantized {values, scale} dicts; page axis is 1)."""
    total = int(content["num_pages"])
    if not 0 < n <= total:
        raise ValueError(
            f"slice_page_payload: want {n} of {total} page(s)")

    def cut(node):
        if isinstance(node, dict):
            return {k: cut(v) for k, v in node.items()}
        return np.asarray(node)[:, :n]
    return {"k": cut(content["k"]), "v": cut(content["v"]),
            "num_pages": n}


def concat_page_payloads(a: dict, b: dict) -> dict:
    """Concatenate two page payloads along the page axis — the
    salvage-tail splice (serve/engine.py ``_maybe_fetch_salvage_tail``):
    a crash-salvaged partial payload grows by the chain pages a sibling
    replica's cache still held. Quantized and plain payloads must not
    mix (the write path validates shapes again before any scatter)."""

    def cat(x, y):
        if isinstance(x, dict) != isinstance(y, dict):
            raise ValueError(
                "concat_page_payloads: quantized/plain payload mismatch")
        if isinstance(x, dict):
            if set(x) != set(y):
                raise ValueError(
                    f"concat_page_payloads: quantized parts differ "
                    f"({sorted(x)} vs {sorted(y)})")
            return {k: cat(x[k], y[k]) for k in x}
        return np.concatenate([np.asarray(x), np.asarray(y)], axis=1)
    return {"k": cat(a["k"], b["k"]), "v": cat(a["v"], b["v"]),
            "num_pages": int(a["num_pages"]) + int(b["num_pages"])}


class PagedKVCache:
    def __init__(
        self,
        cfg: ModelConfig,
        num_slots: int,
        max_seq_len: int,
        page_size: int = 16,
        num_pages: int = 0,
        hbm_budget_gb: float = 4.0,
        dtype=jnp.bfloat16,
        page_sharding=None,     # NamedSharding over the kv-head axis for
                                # tensor-parallel serving (None = one device)
        quantized=False,        # False|"none" | True|"int8" | "int4"
        snapshot_entries: int = 0,  # snapshots of a ``K`` model's state
        page_size_stated: bool = True,  # False: ``page_size_by_rows`` gave it
        window_rows: int = 0,   # a model with window layers: rows of the
                                # longest window a program of the engine
                                # writes in one call (0: ``ring_pages``'s)
    ):
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_seq_len = max_seq_len
        self.page_size = page_size
        self.page_size_stated = page_size_stated
        self.max_pages_per_slot = math.ceil(max_seq_len / page_size)
        # normalize the quantization kind: legacy bool callers mean int8
        if quantized is True:
            kind = "int8"
        elif not quantized or quantized == "none":
            kind = "none"
        else:
            kind = str(quantized)
        if kind not in ("none", "int8", "int4"):
            raise ValueError(f"unknown KV quantization {quantized!r} "
                             "(none|int8|int4)")
        if kind == "int4" and page_size % 2:
            raise ValueError(
                f"int4 KV pages pack two page slots per byte; page_size "
                f"{page_size} must be even")
        self.quant_kind = kind
        self.quantized = kind != "none"
        if self.quantized:
            refuse(cfg, "kv_quantization", what=f"kv_quantization {kind}")
        # what a token costs the pool: its row in every layer that keeps one
        self.row_bytes = kv_row_bytes(cfg, jnp.dtype(dtype).itemsize, kind)
        # A stack with WINDOW layers keeps TWO pools: the full layers' pages
        # are the chain every model has, and what a token costs is its rows
        # in those layers alone; the window layers' pool is sized exactly:
        # ``ring_entries`` pages a slot and the scratch page. It comes out
        # of the budget first
        self.window_layers = cfg.window_layers if cfg.has_window else 0
        self.ring_entries = ring_bytes = 0
        if self.window_layers:
            if page_sharding is not None:
                refuse(cfg, "tensor_parallel")
            self.ring_entries = ring_pages(cfg.sliding_window, page_size,
                                           window_rows)
            ring_bytes = (self.window_layers * self.row_bytes * page_size
                          * (num_slots * self.ring_entries + 1))
        self.bytes_per_token = ((cfg.kv_layers - self.window_layers)
                                * self.row_bytes)
        if num_pages <= 0:
            num_pages = max(int((hbm_budget_gb * 1e9 - ring_bytes)
                                // (self.bytes_per_token * page_size)), 2)
        # never more than every slot fully resident (+1 scratch)
        num_pages = min(num_pages, num_slots * self.max_pages_per_slot + 1)
        self.num_pages = num_pages
        self.dtype = dtype

        # [L, NP, Nkv, PS, D] — (PS, D) minor-most so the Pallas decode
        # kernel can DMA one [PS, D] page tile per (kv-head, page) grid step
        # (TPU block shapes must end in the tiled dims)
        # Heads of 64 values lie in PAIRS on the 128 lanes ([.., Nkv / 2, PS,
        # 128]: ops/paged_attention.py ``heads_a_row``), so that the
        # page-streaming kernel serves them; a quantised pool (a scale a
        # token and head) or one sharded over its heads keeps the plain
        # layout and the gather route.
        from ..ops.paged_attention import heads_a_row
        pair = (1 if self.quantized or page_sharding is not None
                else heads_a_row(cfg.num_kv_heads, cfg.head_dim))
        shape = (cfg.kv_layers - self.window_layers, num_pages,
                 cfg.num_kv_heads // pair, page_size, cfg.head_dim * pair)
        self.page_sharding = page_sharding
        if cfg.is_latent:
            # the third kind of cache state: ONE pool of latent rows
            # [c_kv | rope(k_pe)] (zero-padded to whole lane tiles), every
            # head's keys and values at once. It rides the programs as
            # ``k_pages``; there is no second pool (``v_pages`` None).
            shape = (cfg.kv_layers, num_pages, 1, page_size,
                     cfg.mla.page_width)
        self.k_pages = self._new_pages(shape, dtype)
        self.v_pages = (None if cfg.is_latent
                        else self._new_pages(shape, dtype))
        if self.window_layers:
            from ..ops.paged_attention import SplitPages
            ring_shape = (self.window_layers,
                          num_slots * self.ring_entries + 1, *shape[2:])
            is_window = tuple(t == "sliding" for t in cfg.layer_types)
            self.k_pages, self.v_pages = (
                SplitPages(pool, self._new_pages(ring_shape, dtype),
                           self.ring_entries, is_window)
                for pool in (self.k_pages, self.v_pages))
        # the second kind of cache state: a state-space layer keeps, for
        # each SLOT, the last K-1 pre-activation conv columns and its
        # [nh, P, N] state, whatever the sequence's length. A slot costs
        # no pages for these layers; the pools are addressed
        # [state-space layer, slot] and ride the programs beside the page
        # pools (None for a model without such layers). A ``K`` (delta-rule
        # linear attention) layer keeps the same two things, its conv
        # window over q | k | v and a [nh, dk, dv] state, under the same two
        # names; such a model's ``*`` layers may keep LATENT pages, so the
        # latent pool and the state pools live side by side here.
        self.state = self.new_state()
        # the fourth kind: SNAPSHOTS of the state pools' rows, an ENTRY
        # where the pools have a slot, in the pools' own layout (ops/kda.py
        # ``snapshot_pools`` / ``kda_snapshot_take`` / ``kda_snapshot_arm``).
        # An entry holds the state after a whole number of pages of some
        # token prefix and stands ON that prefix's last page, under the
        # page's chain hash: a page hit is followed only as far as a
        # snapshot stands (``lookup_prefix``), and the entry goes when its
        # page is evicted, or when a new snapshot needs the room (least
        # recently used first; an entry an admitted request is about to be
        # armed from is pinned). The device copies are the engine's
        # (serve/engine.py ``_take_snapshot`` / ``_arm_from_snapshot``);
        # here is the host's bookkeeping.
        self.snapshot_entries = (snapshot_entries
                                 if keeps_snapshots(cfg, snapshot_entries)
                                 else 0)
        self.snapshots = self.new_snapshots()
        self._snap_free: list[int] = list(range(self.snapshot_entries))[::-1]
        self._snap_of: dict[bytes, int] = {}              # page hash -> entry
        self._snap_lru: OrderedDict[int, bytes] = OrderedDict()  # cold first
        self._snap_pins = np.zeros(max(self.snapshot_entries, 1), np.int32)
        self.snapshots_taken = 0
        self.snapshot_evictions = 0

        # host-side state; page 0 is scratch and never allocated
        self._free: list[int] = list(range(1, num_pages))
        self._owned: dict[int, list[int]] = {}            # slot -> pages
        self._chain_len: dict[int, int] = {}   # slot -> table entries used
        # a slot's row: its chain and, with window layers, its ring's
        # entries LAST (``SplitPages.tables_of`` tells them apart on the
        # device; every other model's row is its chain)
        self.block_tables = np.zeros(
            (num_slots, self.max_pages_per_slot + self.ring_entries),
            np.int32)
        # the window pool's pages (page 0 its scratch): ``ring_entries`` a
        # resident slot, taken at admission and held until release
        self._ring_free: list[int] = list(
            range(1, num_slots * self.ring_entries + 1))[::-1]
        self._ring_owned: dict[int, list[int]] = {}
        self.ring_wraps = 0     # see ``count_ring_wraps``

        # prefix cache: refcounted shared pages + LRU of evictable ones.
        # A page is in exactly one of: _free, referenced (_ref > 0), or
        # _evictable (ref == 0 but content cached for future hits).
        self._ref = np.zeros(num_pages, np.int32)
        self._hash_to_page: dict[bytes, int] = {}
        self._page_to_hash: dict[int, bytes] = {}
        self._evictable: OrderedDict[int, None] = OrderedDict()
        self.prefix_hits = 0          # pages served from cache
        self.prefix_queries = 0       # full pages looked up
        # tiered fleet KV store (serve/fleet/kv_store.py): when set,
        # called with (hashes, multi-page extract payload) covering the
        # cached pages an allocation evicted — the demotion seam.
        # Evictions are BATCHED per allocation call: _take_free_page
        # only records (hash, page) pairs and the allocation flushes
        # them through ONE device gather before returning (the pages'
        # content is untouched until a later dispatch writes them, and
        # every write happens on this same engine thread). A hook
        # failure must never break allocation, so the flush is guarded.
        # None (the default) changes nothing.
        self.demote_hook = None
        self._demote_pending: list[tuple[bytes, int]] = []

    def new_state(self):
        """Zeroed state pools of the recurrent layers (``M``, ``K`` or
        ``C``: a model has one kind), or None: ``conv`` [layer, slot, K-1, C]
        (``K`` and ``C`` layers: [layer, K-1, slot, C], whole tiles of
        [slots, C]; ops/kda.py ``kda_conv_step``) in the cache's dtype and
        ``ssm`` [layer, slot, heads, ., .] float32. A ``C`` (gated
        short-convolution) layer's window IS its state: such a model's
        pools are ``conv`` alone."""
        cfg = self.cfg
        if not cfg.is_recurrent:
            return None
        if cfg.conv_layers:
            return {"conv": jnp.zeros(
                (cfg.conv_layers, cfg.shortconv_kernel - 1, self.num_slots,
                 cfg.hidden_size), self.dtype)}
        if cfg.kda_layers:
            k = cfg.kda
            return {
                "conv": jnp.zeros((cfg.kda_layers, k.conv_kernel - 1,
                                   self.num_slots, k.conv_channels),
                                  self.dtype),
                "ssm": jnp.zeros((cfg.kda_layers, self.num_slots,
                                  k.num_heads, k.head_dim, k.head_dim),
                                 jnp.float32),
            }
        s = cfg.ssm
        return {
            "conv": jnp.zeros((cfg.ssm_layers, self.num_slots,
                               s.conv_kernel - 1, s.conv_channels),
                              self.dtype),
            "ssm": jnp.zeros((cfg.ssm_layers, self.num_slots, s.num_heads,
                              s.head_dim, s.state_size),
                             jnp.float32),
        }

    def state_bytes(self) -> int:
        """HBM bytes of the state pools (0 without state-space layers)."""
        return self._pool_bytes(self.state)

    @staticmethod
    def _pool_bytes(pools) -> int:
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in (pools or {}).values())

    # -- snapshots of the recurrent state ------------------------------------

    def new_snapshots(self):
        """Zeroed snapshot pools (None without any)."""
        if not self.snapshot_entries:
            return None
        from ..ops import kda
        return kda.snapshot_pools(self.state["conv"], self.state["ssm"],
                                  self.snapshot_entries)

    def snapshot_bytes(self) -> int:
        return self._pool_bytes(self.snapshots)

    @property
    def snapshots_live(self) -> int:
        """Entries that hold a snapshot."""
        return len(self._snap_of)

    def snapshot_at(self, h: bytes) -> Optional[int]:
        """The entry that stands on the page hashed ``h``, or None."""
        return self._snap_of.get(h)

    def pin_snapshot(self, h: bytes) -> None:
        """Keep ``h``'s entry from eviction until ``unpin_snapshot``: an
        admitted request is about to be armed from it."""
        self._snap_pins[self._snap_of[h]] += 1

    def unpin_snapshot(self, h: bytes) -> None:
        entry = self._snap_of.get(h)
        if entry is not None and self._snap_pins[entry] > 0:
            self._snap_pins[entry] -= 1

    def touch_snapshot(self, h: bytes, cold: bool = False) -> None:
        """``h``'s entry was used: the last to be evicted now (``cold``:
        the FIRST, a longer snapshot of the same chain having been taken)."""
        entry = self._snap_of.get(h)
        if entry is not None:
            self._snap_lru.move_to_end(entry, last=not cold)

    def claim_snapshot(self, h: bytes) -> Optional[int]:
        """An entry to copy a state into, to stand under ``h``: a free one,
        else the least recently used that nothing pins (whose snapshot is
        lost). None where ``h`` has its snapshot already (the same token
        prefix gives the same state) or every entry is pinned."""
        if h in self._snap_of:
            self.touch_snapshot(h)
            return None
        if self._snap_free:
            entry = self._snap_free.pop()
        else:
            entry = next((e for e in self._snap_lru
                          if self._snap_pins[e] == 0), None)
            if entry is None:
                return None
            del self._snap_of[self._snap_lru.pop(entry)]
            self.snapshot_evictions += 1
        self._snap_of[h] = entry
        self._snap_lru[entry] = h
        self.snapshots_taken += 1
        return entry

    def _drop_snapshot(self, h: bytes) -> None:
        """The page hashed ``h`` is gone: so is the entry that stood on it."""
        entry = self._snap_of.pop(h, None)
        if entry is not None:
            del self._snap_lru[entry]
            self._snap_pins[entry] = 0
            self._snap_free.append(entry)
            self.snapshot_evictions += 1

    def _pages_only(self, what: str) -> None:
        """Refuse, by name, to move a sequence as K/V pages alone when the
        model keeps anything else a slot (``REFUSED``)."""
        refuse(self.cfg, "page payload", what=what)

    def _new_pages(self, shape, dtype):
        """Allocate a (possibly int8/int4-quantized, possibly tensor-
        parallel-sharded) page buffer. ``shape`` is always the LOGICAL
        [L, NP, Nkv, PS, D] geometry; the int4 buffer packs the page-slot
        axis to PS/2 bytes internally (Int4Pages.shape reports logical)."""
        import jax
        if self.quantized:
            from ..ops.paged_attention import Int4Pages, QuantPages
            if self.quant_kind == "int4":
                # two page slots per byte along the slot axis; the scale
                # keeps the full per-slot [L, NP, Nkv, PS] tile
                buf = Int4Pages(
                    jnp.zeros((*shape[:-2], shape[-2] // 2, shape[-1]),
                              jnp.uint8),
                    jnp.zeros(shape[:-1], jnp.float32))
            else:
                # scale layout is the kernel-friendly per-page tensor
                # [L, NP, Nkv, PS] (no trailing singleton — QuantPages doc)
                buf = QuantPages(jnp.zeros(shape, jnp.int8),
                                 jnp.zeros(shape[:-1], jnp.float32))
            if self.page_sharding is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                # rank-aware: the VALUES leaf keeps the full 5-entry spec
                # (int4 packing shrinks the slot axis but not the rank —
                # the kv-head shard axis is untouched); the scale leaf is
                # one rank lower, so trim the head-dim entry off the spec
                ps = self.page_sharding
                scale_sharding = NamedSharding(
                    ps.mesh, PartitionSpec(*tuple(ps.spec)[:len(shape) - 1]))
                return type(buf)(
                    jax.device_put(buf.values, ps),
                    jax.device_put(buf.scale, scale_sharding))
            return buf
        buf = jnp.zeros(shape, dtype)
        if self.page_sharding is not None:
            return jax.device_put(buf, self.page_sharding)
        return buf

    def fresh_pool(self, buf):
        """A zeroed pool of ``buf``'s geometry (engine recovery: a failed
        program's donated pools are gone)."""
        from ..ops.paged_attention import SplitPages
        if isinstance(buf, SplitPages):
            return buf.of(self._new_pages(buf.full.shape, self.dtype),
                          self._new_pages(buf.window.shape, self.dtype))
        return self._new_pages(buf.shape, self.dtype)

    # -- accounting ----------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free) + len(self._evictable)

    def pages_needed(self, num_tokens: int) -> int:
        return math.ceil(max(num_tokens, 1) / self.page_size)

    def can_allocate(self, num_tokens: int) -> bool:
        """Pages for ``num_tokens`` in the chain AND, with window layers, a
        ring for the slot."""
        return (self.pages_needed(num_tokens) <= self.free_pages
                and len(self._ring_free) >= self.ring_entries)

    def _take_ring(self, slot: int) -> None:
        """Give ``slot`` its ring (a slot that holds one keeps it: growth,
        and a chain allocated anew, leave the ring where it is)."""
        if not self.ring_entries or slot in self._ring_owned:
            return
        if len(self._ring_free) < self.ring_entries:
            raise RuntimeError(
                f"KV cache OOM: no ring of {self.ring_entries} window pages "
                f"for slot {slot} ({len(self._ring_free)} free)")
        ring = [self._ring_free.pop() for _ in range(self.ring_entries)]
        self._ring_owned[slot] = ring
        self.block_tables[slot, self.max_pages_per_slot:] = ring

    @property
    def table_pages(self) -> int:
        """Entries of the slots' chains (a ring's entries are not pages a
        sequence's length covers)."""
        return self.num_slots * self.max_pages_per_slot

    @property
    def free_ring_pages(self) -> int:
        return len(self._ring_free)

    def can_ever_allocate(self, num_tokens: int) -> bool:
        """Whether an EMPTY cache could hold this many tokens (page 0 is
        reserved scratch)."""
        return self.pages_needed(num_tokens) <= self.num_pages - 1

    def hbm_bytes(self) -> int:
        def one(buf):
            from ..ops.paged_attention import QuantPages
            if isinstance(buf, QuantPages):
                return buf.values.size + buf.scale.size * 4
            if buf is None:             # a latent pool has no second one
                return 0
            return sum(leaf.nbytes
                       for leaf in jax.tree_util.tree_leaves(buf))
        return (one(self.k_pages) + one(self.v_pages) + self.state_bytes()
                + self.snapshot_bytes())

    def pool_bytes(self, kind: str) -> int:
        """Bytes of the K and V pools of the layers of ``kind`` ("full":
        every model's pages; "window": the ring pool, 0 without one)."""
        pools = [p for p in (self.k_pages, self.v_pages) if p is not None]
        if self.window_layers:
            pools = [getattr(p, kind) for p in pools]
        elif kind == "window":
            return 0
        return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(pools))

    # -- alloc / grow / free -------------------------------------------------

    def _take_free_page(self) -> int:
        """Pop a free page, evicting the LRU cached page if needed. An
        evicted hashed page is queued for the demote hook (tiered fleet
        KV store); the allocation that triggered the eviction flushes
        the queue in one batched extract before returning — HBM
        eviction then moves pages down a tier instead of destroying
        them, at one device gather per allocation instead of one per
        page."""
        if self._free:
            return self._free.pop()
        if self._evictable:
            page, _ = self._evictable.popitem(last=False)   # oldest first
            h = self._page_to_hash.pop(page, None)
            if h is not None:
                self._hash_to_page.pop(h, None)
                self._drop_snapshot(h)
                if self.demote_hook is not None:
                    self._demote_pending.append((h, page))
            return page
        raise RuntimeError("KV cache OOM: no free or evictable pages")

    def _flush_demotions(self) -> None:
        """Hand every eviction queued by ``_take_free_page`` to the
        demote hook in one batched extract. Must run before the caller
        releases the engine lock (the evicted pages' content is only
        guaranteed until the next dispatch writes them)."""
        if not self._demote_pending:
            return
        pairs, self._demote_pending = self._demote_pending, []
        hook = self.demote_hook
        if hook is None:
            return
        try:
            content = self._extract_pages_idx(
                np.asarray([p for _h, p in pairs], np.int32))
            hook([h for h, _p in pairs], content)
        except Exception:
            logger.exception(
                "KV page demotion hook failed; %d page(s) evicted "
                "without demoting", len(pairs))

    def _drop_ref(self, page: int) -> None:
        self._ref[page] -= 1
        if self._ref[page] <= 0:
            self._ref[page] = 0
            if page in self._page_to_hash:
                self._evictable[page] = None    # keep content, reclaimable
            else:
                self._free.append(page)

    def allocate(self, slot: int, num_tokens: int,
                 prefix_pages: Optional[list[int]] = None) -> None:
        """Give ``slot`` enough pages for ``num_tokens`` tokens.

        ``prefix_pages`` (already pinned via ``pin_pages``) become the head
        of the slot's block table; only the remainder is freshly allocated.
        """
        prefix_pages = prefix_pages or []
        need = self.pages_needed(num_tokens)
        fresh = need - len(prefix_pages)
        if fresh > self.free_pages:
            raise RuntimeError(
                f"KV cache OOM: need {fresh} pages, {self.free_pages} free")
        pages = [self._take_free_page() for _ in range(fresh)]
        for p in pages:
            self._ref[p] = 1
        # slot owns refs on fresh pages only; prefix pins are tracked by
        # the engine per request and dropped via unpin_pages on release
        self._owned[slot] = pages
        table = list(prefix_pages) + pages
        self.block_tables[slot, :self.max_pages_per_slot] = 0
        self.block_tables[slot, :len(table)] = table
        self._chain_len[slot] = len(table)
        self._take_ring(slot)
        self._flush_demotions()

    def slot_capacity_tokens(self, slot: int) -> int:
        """Tokens the slot's current page chain can hold."""
        return self._chain_len.get(slot, 0) * self.page_size

    def extend_slot(self, slot: int, num_tokens: int) -> bool:
        """Grow ``slot``'s chain to cover ``num_tokens`` (on-demand
        admission). Returns False — allocating nothing — if the pool can't
        supply every page needed; the engine then preempts a victim and
        retries. All-or-nothing keeps the failure path trivial: no partial
        growth to unwind."""
        need = self.pages_needed(num_tokens) - self._chain_len.get(slot, 0)
        if need <= 0:
            return True
        if need > self.free_pages:
            return False
        start = self._chain_len.get(slot, 0)
        pages = [self._take_free_page() for _ in range(need)]
        for p in pages:
            self._ref[p] = 1
        self._owned.setdefault(slot, []).extend(pages)
        self.block_tables[slot, start:start + need] = pages
        self._chain_len[slot] = start + need
        self._flush_demotions()
        return True

    def release(self, slot: int) -> None:
        """Return ``slot``'s pages. Its rows of the state pools need no
        device work: the prefill that next arms the slot overwrites them
        with a state computed from zero (serve/engine.py ``_prefill_fn``;
        a chunked prefill's first chunk and a riding prompt's first piece
        take the state as zero, ``slot_state`` of ops/kda.py and
        ops/ssm.py), so a reused slot starts from a zero
        state whatever is left here."""
        for page in self._owned.pop(slot, []):
            self._drop_ref(page)
        self._ring_free.extend(self._ring_owned.pop(slot, [])[::-1])
        self.block_tables[slot, :] = 0
        self._chain_len.pop(slot, None)

    def prompt_entries(self, slot: int, tokens: int, bucket_pages: int
                       ) -> np.ndarray:
        """The table entries a COLD prefill of ``tokens`` tokens writes its
        bucket's ``bucket_pages`` pages at (``write_prompt_to_pages``): the
        slot's chain, pages past the prompt the scratch page. With window
        layers [2, bucket_pages]: the chain's, and the ring's, where only
        the prompt's LAST ``ring_entries`` pages are written (an earlier
        page shares its entry with a later one, and no query will see it
        again)."""
        used = self.pages_needed(tokens)
        entries = np.zeros(bucket_pages, np.int32)
        entries[:used] = self.block_tables[slot, :used]
        if not self.ring_entries:
            return entries
        ring = np.zeros(bucket_pages, np.int32)
        kept = np.arange(max(used - self.ring_entries, 0), used)
        ring[kept] = self.block_tables[
            slot, self.max_pages_per_slot + kept % self.ring_entries]
        return np.stack([entries, ring])

    # -- swap (preemption to host memory) ------------------------------------

    @engine_thread_only
    def extract_slot(self, slot: int) -> dict:
        """Copy ``slot``'s written pages to HOST memory (swap-out half of
        preemption=swap). One device fetch per buffer — the page gather
        runs on-device, only the slot's own pages cross the link."""
        return self.extract_slot_pages(slot, 0, self._chain_len.get(slot, 0))

    @engine_thread_only
    def extract_slot_pages(self, slot: int, lo: int, hi: int) -> dict:
        """Copy chain entries [lo, hi) of ``slot`` to host memory.

        The page-range form is the two-phase migration courier
        (serve/fleet/migration.py): phase 1 pre-copies the full (immutable)
        pages while decode keeps appending to the tail, phase 2
        stop-and-copies only [full, written) — the partial tail plus pages
        filled since the pre-copy. Payloads are plain numpy (host) arrays,
        so they survive the source engine's death and serialize for the
        cross-host courier (serve/fleet/transport.py).

        Bounds are validated up front: an out-of-range request would
        otherwise silently gather scratch page 0 (zeros presented as real
        KV — wrong tokens downstream, no error)."""
        self._pages_only("swap-out / fleet migration (extract_slot)")
        chain = self._chain_len.get(slot, 0)
        if not 0 <= lo <= hi <= chain:
            raise ValueError(
                f"extract_slot_pages: range [{lo}, {hi}) outside slot "
                f"{slot}'s chain of {chain} page(s)")
        return self._extract_pages_idx(self.block_tables[slot, lo:hi].copy())

    @engine_thread_only
    def extract_pages(self, pages: list[int]) -> dict:
        """Copy arbitrary page ids to host memory — the owner half of the
        fleet-global prefix fetch (serve/fleet/): the pages come from
        ``lookup_prefix``, not any slot's chain. Same payload schema as
        :meth:`extract_slot_pages`. Page ids are bounds-checked (scratch
        page 0 is never a cache page; an out-of-range id would gather
        garbage presented as real KV)."""
        self._pages_only("fleet prefix export (extract_pages)")
        bad = [int(p) for p in pages if not 0 < int(p) < self.num_pages]
        if bad:
            raise ValueError(
                f"extract_pages: page id(s) {bad} outside (0, "
                f"{self.num_pages})")
        return self._extract_pages_idx(np.asarray(pages, np.int32))

    def _extract_pages_idx(self, pages: np.ndarray) -> dict:
        idx = jnp.asarray(pages)

        def grab(buf):
            from ..ops.paged_attention import QuantPages
            if isinstance(buf, QuantPages):
                return {"values": np.asarray(buf.values[:, idx]),
                        "scale": np.asarray(buf.scale[:, idx])}
            return np.asarray(buf[:, idx])
        return {"k": grab(self.k_pages), "v": grab(self.v_pages),
                "num_pages": int(len(pages))}

    def _restore_fn(self, n_bucket: int):
        """Jitted donated page-write for swap-in: out-of-place .at[].set
        outside jit would copy the WHOLE pool per restore (transient 2x
        HBM + O(pool) traffic); under jit with donation XLA scatters in
        place. One program per power-of-two page-count bucket; short
        restores pad with scratch page 0 (writing zeros there is the
        cache's documented no-op)."""
        import jax
        if not hasattr(self, "_restore_cache"):
            self._restore_cache = {}
        if n_bucket not in self._restore_cache:
            def write(k_pages, v_pages, idx, kd, vd):
                from ..ops.paged_attention import QuantPages

                def put(buf, data):
                    if isinstance(buf, QuantPages):
                        # type(buf): Int4Pages payloads (packed uint8
                        # values) restore through the same scatter
                        return type(buf)(
                            buf.values.at[:, idx].set(data["values"]),
                            buf.scale.at[:, idx].set(data["scale"]))
                    return buf.at[:, idx].set(data.astype(buf.dtype))
                return put(k_pages, kd), put(v_pages, vd)
            self._restore_cache[n_bucket] = jax.jit(
                write, donate_argnums=(0, 1))
        return self._restore_cache[n_bucket]

    @engine_thread_only
    def restore_slot(self, slot: int, content: dict) -> bool:
        """Swap-in: allocate fresh pages for the slot and write the saved
        K/V back. Returns False (allocating nothing) when the pool can't
        supply the pages — the caller falls back to recompute."""
        self._pages_only("swap-in / fleet migration (restore_slot)")
        if not isinstance(content, dict) or "num_pages" not in content:
            raise ValueError(
                "restore payload must be a dict with 'num_pages'; got "
                f"{type(content).__name__}")
        n = int(content["num_pages"])
        if n > self.free_pages:
            return False
        self.allocate(slot, n * self.page_size)
        self.write_slot_pages(slot, content)
        return True

    def _validate_payload(self, slot: int, content: dict, lo: int) -> int:
        """Schema + bounds check for a restore payload; returns its page
        count. Raises ValueError naming exactly what is malformed.
        Bounds before shapes, so a wrong page COUNT names the slot's
        chain rather than a derived shape mismatch."""
        n = self._parse_num_pages(content)
        chain = self._chain_len.get(slot, 0)
        if lo < 0 or lo + n > chain:
            raise ValueError(
                f"restore payload covers chain entries [{lo}, {lo + n}) "
                f"but slot {slot} owns only {chain} page(s)")
        self._validate_pages_shapes(content, n)
        return n

    def _validate_pages_content(self, content: dict) -> int:
        """Schema/shape validation with no slot bounds — the
        ``insert_prefix_pages`` flavor, whose fetched pages belong to no
        slot. Returns the payload's page count."""
        n = self._parse_num_pages(content)
        self._validate_pages_shapes(content, n)
        return n

    @staticmethod
    def _parse_num_pages(content) -> int:
        if not isinstance(content, dict) or "num_pages" not in content \
                or "k" not in content or "v" not in content:
            raise ValueError(
                "restore payload must be a dict with 'k', 'v' and "
                f"'num_pages'; got keys "
                f"{sorted(content) if isinstance(content, dict) else type(content).__name__}")  # noqa: E501
        try:
            n = int(content["num_pages"])
        except (TypeError, ValueError):
            raise ValueError(
                f"restore payload num_pages must be an int, got "
                f"{content['num_pages']!r}") from None
        if n < 0:
            raise ValueError(f"restore payload num_pages {n} < 0")
        return n

    def _validate_pages_shapes(self, content: dict, n: int) -> None:
        from ..ops.paged_attention import Int4Pages, QuantPages
        cfg = self.cfg
        expect = (cfg.kv_layers, n, *self.k_pages.shape[2:])
        for name, buf in (("k", self.k_pages), ("v", self.v_pages)):
            data = content[name]
            if isinstance(buf, QuantPages):
                if not isinstance(data, dict) or "values" not in data \
                        or "scale" not in data:
                    raise ValueError(
                        f"restore payload '{name}' must be a quantized "
                        "{values, scale} dict for a "
                        f"{self.quant_kind}-KV pool; got "
                        f"{type(data).__name__}")
                vexpect = expect
                if isinstance(buf, Int4Pages):
                    # packed layout: PS/2 bytes along the page-slot axis
                    vexpect = (*expect[:-2], expect[-2] // 2, expect[-1])
                shapes = {"values": vexpect, "scale": expect[:-1]}
                for part, want in shapes.items():
                    got = tuple(np.shape(data[part]))
                    if got != want:
                        raise ValueError(
                            f"restore payload '{name}.{part}' shape "
                            f"{got} != expected {want}")
                # dtype guards the int8-vs-int4 seam the shape check
                # can't always see (a wrong-width payload scattered into
                # the pool would serve garbage KV, not error)
                want_dtype = np.dtype(buf.values.dtype)
                got_dtype = np.asarray(data["values"]).dtype
                if got_dtype != want_dtype:
                    raise ValueError(
                        f"restore payload '{name}.values' dtype "
                        f"{got_dtype} != pool dtype {want_dtype} "
                        f"({self.quant_kind}-KV pool)")
            else:
                if isinstance(data, dict):
                    raise ValueError(
                        f"restore payload '{name}' is quantized but the "
                        "pool holds plain pages — quantized-KV payloads "
                        "only restore into same-kind quantized engines")
                got = tuple(np.shape(data))
                if got != expect:
                    raise ValueError(
                        f"restore payload '{name}' shape {got} != "
                        f"expected {expect}")

    @engine_thread_only
    def write_slot_pages(self, slot: int, content: dict,
                         lo: int = 0) -> None:
        """Write a host payload's pages into chain entries
        [lo, lo+num_pages) of an ALREADY-allocated slot.

        The partial-restore half of crash-payload salvage
        (serve/fleet/replica.py): a migration ticket killed between its
        two copy phases leaves the victim's FULL pages on host memory —
        the destination allocates the slot's whole chain, writes those
        pages here, and extend-prefills only the uncovered tail. The
        full-chain restore path (``restore_slot``) goes through here too.

        Payload schema and page-range bounds are validated up front
        (clear ValueError) instead of failing deep inside the jitted
        merge — a malformed courier payload must degrade to re-prefill,
        never scatter garbage into the pool.
        """
        self._pages_only("a migrated payload's write-back (write_slot_pages)")
        n = self._validate_payload(slot, content, lo)
        if n <= 0:
            return
        self._write_pages_idx(self.block_tables[slot, lo:lo + n],
                              content["k"], content["v"])

    def _write_pages_idx(self, pages: np.ndarray, kd, vd) -> None:
        """Write n pages of host K/V content into the given page ids via
        the jitted donated scatter (power-of-two bucketed; pad entries
        target scratch page 0)."""
        n = int(len(pages))
        if n <= 0:
            return
        bucket = 1
        while bucket < n:
            bucket <<= 1
        idx = np.zeros(bucket, np.int32)        # pad -> scratch page 0
        idx[:n] = pages

        def pad(data):
            if isinstance(data, dict):
                return {k: pad(v) for k, v in data.items()}
            out = np.zeros((data.shape[0], bucket, *data.shape[2:]),
                           data.dtype)
            out[:, :n] = data
            return out
        kd, vd = pad(kd), pad(vd)
        from ..ops.paged_attention import QuantPages
        def as_arg(buf, d):
            if isinstance(buf, QuantPages):
                return {"values": jnp.asarray(d["values"]),
                        "scale": jnp.asarray(d["scale"])}
            return jnp.asarray(d)
        self.k_pages, self.v_pages = self._restore_fn(bucket)(
            self.k_pages, self.v_pages, jnp.asarray(idx),
            as_arg(self.k_pages, kd), as_arg(self.v_pages, vd))

    # -- prefix cache --------------------------------------------------------

    def lookup_prefix(self, hashes: list[bytes]) -> list[int]:
        """Longest cached page chain for these full-page hashes (NOT pinned;
        call ``pin_pages`` under the same lock before releasing it). Pure
        lookup — hit/query stats are counted by the caller once per
        admission, so a head-of-line request retried every step doesn't
        skew the rate."""
        pages = [self._hash_to_page[h]
                 for h in hashes[:self.hashed_pages(hashes)]]
        if self.snapshot_entries:
            # a recurrent model: only as far as a snapshot stands (the
            # state after a page on which none stands nobody kept)
            last = max((i + 1 for i in range(len(pages))
                        if hashes[i] in self._snap_of), default=0)
            pages = pages[:last]
        return pages

    def hashed_pages(self, hashes: list[bytes]) -> int:
        """Pages of the chain ``hashes`` the cache holds, from its start."""
        n = 0
        while n < len(hashes) and hashes[n] in self._hash_to_page:
            n += 1
        return n

    def pin_pages(self, pages: list[int]) -> None:
        for p in pages:
            if self._ref[p] == 0:
                self._evictable.pop(p, None)
            self._ref[p] += 1

    def unpin_pages(self, pages: list[int]) -> None:
        for p in pages:
            self._drop_ref(p)

    def flush_prefix_cache(self) -> None:
        """Drop every hash->page mapping and free the evictable pages.

        Required whenever the page BUFFERS are reallocated (engine
        recovery): the mappings would otherwise serve zeroed K/V to future
        prefix hits — silently wrong output, no error."""
        self._hash_to_page.clear()
        self._page_to_hash.clear()
        for h in list(self._snap_of):
            self._drop_snapshot(h)
        while self._evictable:
            page, _ = self._evictable.popitem(last=False)
            self._free.append(page)

    def register_pages(self, pairs: list[tuple[bytes, int]]) -> None:
        """Publish (hash, page) pairs into the prefix cache. First writer
        wins: a hash that is already mapped keeps its existing page (the
        new page stays private to its slot)."""
        for h, page in pairs:
            if h not in self._hash_to_page and page not in self._page_to_hash:
                self._hash_to_page[h] = page
                self._page_to_hash[page] = h

    @engine_thread_only
    def insert_prefix_pages(self, hashes: list[bytes],
                            content: dict) -> list[int]:
        """Import FETCHED prefix pages (fleet-global prefix cache): write
        ``content``'s page columns into freshly-taken free pages and
        publish them under ``hashes`` (column i <-> hashes[i]).

        First writer wins exactly like :meth:`register_pages`: a hash
        already cached here (a concurrent fetch or a local prefill raced
        us) keeps its existing page and the fetched copy for that
        position is discarded — the chain hash guarantees the content is
        identical, so either page serves the same K/V. A dry pool stops
        the insert early (partial import; the uncovered tail re-prefills)
        rather than evicting pages a resident request may be about to
        hit. Inserted pages enter the cache EVICTABLE (ref 0) — callers
        that need them to survive until a prefill must pin them under
        the same lock (the eviction-between-insert-and-pin race is the
        same one ``lookup_prefix`` documents).

        Returns the page ids actually claimed (not the skipped
        duplicates)."""
        self._pages_only("fleet prefix import (insert_prefix_pages)")
        n = self._validate_pages_content(content)
        if n < len(hashes):
            raise ValueError(
                f"insert_prefix_pages: payload carries {n} page(s) for "
                f"{len(hashes)} hash(es)")
        take_pos: list[int] = []
        pages: list[int] = []
        for i, h in enumerate(hashes):
            if h in self._hash_to_page:
                continue                   # duplicate: first writer wins
            if not self._free and not self._evictable:
                break                      # pool dry: partial import
            pages.append(self._take_free_page())
            take_pos.append(i)
        # flush queued demotions BEFORE the fetched content is written
        # into the taken pages — extracting after the write would file
        # the NEW content under the evicted pages' OLD hashes
        self._flush_demotions()
        if not pages:
            return []

        def part(data):
            if isinstance(data, dict):
                return {k: part(v) for k, v in data.items()}
            return np.ascontiguousarray(np.asarray(data)[:, take_pos])
        self._write_pages_idx(np.asarray(pages, np.int32),
                              part(content["k"]), part(content["v"]))
        for i, p in zip(take_pos, pages):
            self._hash_to_page[hashes[i]] = p
            self._page_to_hash[p] = hashes[i]
            self._evictable[p] = None      # ref 0 until a request pins it
        return pages

    def prefix_cache_pairs(self) -> list[tuple[bytes, int]]:
        """Every (hash, page) pair currently cached — the whole-inventory
        flush a draining/retiring replica demotes to the tiered fleet
        KV store so scale-down preserves the cluster cache."""
        return list(self._hash_to_page.items())

    def prefix_inventory(self, max_entries: int = 0) -> list[bytes]:
        """The page hashes currently cached here — the compact inventory
        a fleet replica advertises so the router can attach
        prefix-owner hints. ``max_entries > 0`` keeps only the NEWEST
        that many (dict order is registration order), bounding probe
        payloads; the hint is advisory, so a truncated inventory only
        costs missed fetch opportunities."""
        keys = list(self._hash_to_page.keys())
        if max_entries > 0:
            keys = keys[-max_entries:]
        return keys

    def stats(self) -> dict:
        return {
            "num_pages": self.num_pages,
            "free_pages": self.free_pages,
            "page_size": self.page_size,
            # one layer's K + V page as stored, and which rule sized it: a
            # stated ``kv_block_size``, or ``page_size_by_rows``
            "page_bytes": self.page_size * self.row_bytes,
            "page_size_stated": self.page_size_stated,
            "kv_quantization": self.quant_kind,
            # what a token costs the pool, and of which kind its rows are:
            # "kv" K and V of every kv head, "latent" ONE compressed row
            "kind": "latent" if self.cfg.is_latent else "kv",
            # (with window layers: of the FULL layers' pool, the one whose
            # pages these counts are; the ring pool's numbers are
            # ``engine.stats()["window"]``)
            "bytes_per_token": self.bytes_per_token,
            "hbm_bytes": self.hbm_bytes(),
            "state_bytes": self.state_bytes(),
            "snapshot_bytes": self.snapshot_bytes(),
            "slots_resident": len(self._owned),
            "prefix_cached_pages": len(self._hash_to_page),
            "prefix_hits": self.prefix_hits,
            "prefix_queries": self.prefix_queries,
            "prefix_hit_rate": round(
                self.prefix_hits / max(self.prefix_queries, 1), 4),
        }
