"""Decode and extend forwards over the paged KV cache.

Serving on TPU wants prefill and decode as separate compiled programs
(SURVEY §7.3.2): cold prefill is a large-matmul batch-1 pass through
``models.gpt.forward`` over a dense cache; decode, suffix and chunked
prefill and speculative verification are ``extend_step_forward`` — T tokens
for EVERY slot per call, static shapes, paged attention. Both run
``models.layers.decoder_block``: what this module adds is where K and V
live (the page pools, ``attend`` below) and how a quantized weight is
multiplied (``mm``).
"""

from __future__ import annotations

import collections
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..config.schema import ModelConfig
from ..models.gpt import (
    ExitState,
    cast_table_blocks,
    close_pass,
    head_logits,
    layer_experts,
    mtp_forward,
    mtp_head,
    mtp_layer_index,
    split_expert_stacks,
    table_layer,
    table_layers,
    table_period_and_tail,
    unembed,
)
from ..models.layers import (
    decoder_block,
    layer_kind,
    model_rope_frequencies,
    rope_scale,
    scaled,
)
from ..ops import kda, shortconv, ssm as ssm_ops
from ..ops.mla_paged_attention import mla_paged_attention
from ..ops.paged_attention import (
    pad_to_page_width,
    paged_attention_multi,
    write_window_to_pages,
)
from ..ops.quantization import cast_params, precast_params
from .kv_cache import ring_pages
from .sampling import sample_tokens, sample_tokens_with_prob, transfer_rows


PIECE_META = 4      # int32 columns before a piece's tokens


class Piece(NamedTuple):
    """Up to C rows of ONE pending prompt that ride a decode step: a
    page-aligned piece of it, prefilled by the step's own matmuls (the
    step streams every weight for its B rows anyway; the piece's rows are
    what is left of the MXU under those bytes). The engine lays a
    dispatch's pieces out as int32 rows ``[slot, start, live, stop,
    tokens (C)]``, one a scan step (``serve/engine.py _lay_pieces``)."""
    slot: jax.Array     # the prompt's slot: its block-table row, its key
    start: jax.Array    # position of tokens[0], a whole number of pages
    live: jax.Array     # live rows; the rest is padding; 0 = no piece
    stop: jax.Array     # > 0: the piece ends its prompt: its last live row
                        # samples the prompt's first token and the slot is
                        # armed, to decode up to this position (the first
                        # it may NOT write: prompt + max_tokens)
    tokens: jax.Array   # [C] int32

    @classmethod
    def unpack(cls, row: jax.Array) -> "Piece":
        return cls(*row[:PIECE_META], row[PIECE_META:])


class StepResult(NamedTuple):
    """What ONE forward over the pages returns, whatever the model. A field
    is None where a model has none, and None is an empty pytree: it adds no
    leaf to a jitted program's arguments or results, a scan's carry or a
    donation, so callers read fields by name and no program changes with
    the fields a model fills."""
    logits: jax.Array       # [B, T, V] fp32 ([B, V] of a decode step)
    k_pages: Any            # the K pool; a latent model's ONE pool
    v_pages: Any            # None: a latent model
    moe_stats: Any = None   # asked with ``return_moe_stats`` of an MoE model
    state: Any = None       # {"conv", "ssm"}: a recurrent model's pools


class StepWithStream(NamedTuple):
    """``StepResult`` and, asked for with ``return_stream``, the residual
    stream before the final norm [B, T, H] (what a next-token prediction
    module reads)."""
    logits: jax.Array
    k_pages: Any
    v_pages: Any
    moe_stats: Any
    state: Any
    stream: jax.Array


class DispatchResult(NamedTuple):
    """What one PROGRAM returns, whatever the model and the program (a
    decode dispatch, a speculative one, a prefill), None where it has none;
    in the order the programs' results always had, so that the flattened
    program is the same."""
    sampled: Any            # [K, B] tokens; a prefill's first token; a
                            # denoise dispatch's windows; a speculative
                            # one's (emitted, n_emit[, seq])
    tokens: Any = None      # [B]: the final carry, which a next dispatch
    positions: Any = None   # [B]  chains on
    k_pages: Any = None
    v_pages: Any = None
    moe_stats: Any = None   # summed over the steps
    state: Any = None
    firsts: Any = None      # [K]: the steps' first tokens of riding prompts
    counts: Any = None      # ``DENOISE_COUNTS`` of a denoise dispatch


def _windows(q, k, v, kp, vp, tables, starts, ok, li, attn_impl, block=0,
             window=0):
    """Write each slot's window of K and V into its pages at layer ``li``
    and attend the window over them: (out, (new k_pages, new v_pages)).
    ``block`` > 0: under the block rule (``ModelConfig.attention_block``).
    ``window`` > 0: a WINDOW layer over its pool's plane ``li``, ``tables``
    the slots' rings by logical page (``SplitPages.tables_of``): a row is
    written at its page's ring entry, over rows no query sees any more, and
    a query walks the last ``window`` keys alone."""
    # K and V live in pages. Every T stages a part of the pool and
    # merges the window in: the sublane tiles a window of 1 to 16 rows
    # touches (a decode step's one row: one tile a slot), whole pages
    # for a longer window, and for QuantPages and Int4Pages with
    # quantize-on-write fused into it. A ROW scatter lays
    # the pool out slot-major, the Pallas kernel reads it head-major,
    # and the WHOLE pool is copied between the two in every layer
    # (PERF.md 6, PR 26, has both step times)
    with jax.named_scope("kv_page_write"):
        new_k = write_window_to_pages(kp, k, tables, starts, ok, li)
        new_v = write_window_to_pages(vp, v, tables, starts, ok, li)
    out = paged_attention_multi(q, new_k, new_v, tables, starts,
                                impl=attn_impl, layer=li, block=block,
                                window=window)
    return out, (new_k, new_v)


# A decode program that rides holds TWO step bodies, the carrying and the
# plain one (``decode_scan``), and both start up with it: traced, lowered
# and read from the compile cache at every start. What the two bodies do at
# the SAME shapes (the B slots' page writes and T = 1 attention kernel, the
# sampler) goes through these jitted forms, so it is traced and lowered
# once and called twice, and the compiler meets identical computations
# (PERF.md 6, PR 36: the program's set-up).
_shared_windows = jax.jit(_windows, static_argnames=("attn_impl", "window"))
_shared_sampler = jax.jit(sample_tokens)
# ... and every recurrent layer's one-token update of all slots over the
# state pools, at whichever (traced) layer, by the layer's kind
_ssm_step = jax.jit(ssm_ops.step_pools, static_argnames=("s",))
_shared_recur_step = {
    "K": jax.jit(kda.step_pools, static_argnames=("kd",)),
    "M": _ssm_step, "P": _ssm_step,
    "C": jax.jit(shortconv.step_pools),
}
# a recurrent kind's module: each has ``recur_step`` / ``step_pools`` (T = 1
# over the pools), ``recur_chunk`` (a window of ONE slot from its state) and
# ``slot_state`` / ``write_slot_state`` / ``arm_slot_state`` for its own
# pools' layouts. The pools are the engine's ``state`` dict's values in
# order: a conv pool and a state pool (``M``, ``K``), or a ``C`` model's conv
# pool alone; a window's rows of them ride as a tuple of the same length
_RECURRENT = {"K": kda, "M": ssm_ops, "P": ssm_ops, "C": shortconv}


def recurrent_ops(cfg: ModelConfig):
    """The module of a recurrent model's kind (a table has ``M`` / ``P``,
    ``K`` or ``C`` layers, one kind): it owns the state pools' layout."""
    return _RECURRENT[cfg.recurrent_kind or "M"]


def can_carry(cfg: ModelConfig) -> bool:
    """Can a decode step of this model carry a ``Piece``? The uniform stack
    and a layer table of ``D`` / ``E`` / ``*`` layers over K/V pages or one
    latent pool: every sub-layer but ``attend`` and ``recur`` is per row,
    and both kinds of ``attend`` take a window of one slot. A recurrent
    layer runs a window of ONE slot from the slot's own state
    (``recur_chunk`` of ops/ssm.py and ops/kda.py), in the combinations that
    are served and tested: state-space (``M``) layers beside K/V pages
    (``P``: in ONE layer, the attention over the slot's pages and the scan
    from the slot's state), delta-rule (``K``) layers beside a latent pool
    or K/V pages, gated short-convolution (``C``) layers beside K/V pages
    (the piece's conv runs from the slot's two rows)."""
    if cfg.is_diffusion or cfg.mtp_layers:
        # its step is a window already (2 x ``block_length`` rows a slot; a
        # self-drafting model's last sure token and its draft), and a
        # ``Piece`` wants T == 1
        return False
    return "M" not in cfg.layer_pattern or not cfg.is_latent


def decode_step_forward(
    params: Any,
    tokens: jax.Array,        # [B] int32 — the newest token per slot
    positions: jax.Array,     # [B] int32 — position of that token
    k_pages: jax.Array,       # [L, NP, Nkv, PS, D]
    v_pages: jax.Array,
    block_tables: jax.Array,  # [B, maxP] int32
    cfg: ModelConfig,
    active: Any = None,       # [B] bool — inactive rows write scratch page
    attn_impl: str = "auto",
    w4_kernel_ok: bool = True,
    w8_kernel_ok: bool = False,
    return_moe_stats: bool = False,
    ssm_state: Any = None,
    ride: Any = None,         # a Piece: its rows join the step's B
    two_bodies: bool = False,  # one of a riding program's two step bodies
) -> StepResult:
    """Returns a ``StepResult``: logits [B, V] fp32, the new pools and,
    asked with ``return_moe_stats``, the live slots' expert choices, and
    given ``ssm_state`` the advanced state pools (see
    ``extend_step_forward``). With ``ride`` the logits are [B + 1, V]: the
    last row is the piece's last live row.

    The T=1 case of ``extend_step_forward`` (one layer-body implementation
    for both, so the paths can never diverge numerically). The new token's
    K/V are written into the pages *inside* the traced function. The
    returned pools ARE the argument buffers when the jit wrapper donates
    them (the engine does): the layer loop carries them and writes by
    layer index, and the compiled program holds no pool-sized temporary
    (tests/test_tpu_compile_uniform.py::
    test_decode_program_updates_pool_in_place).
    """
    write_ok = None if active is None else active[:, None]
    step = extend_step_forward(
        params, tokens[:, None], positions, k_pages, v_pages, block_tables,
        cfg, write_ok=write_ok, attn_impl=attn_impl,
        w4_kernel_ok=w4_kernel_ok, w8_kernel_ok=w8_kernel_ok,
        return_moe_stats=return_moe_stats, ssm_state=ssm_state, ride=ride,
        two_bodies=two_bodies)
    return step._replace(logits=step.logits[:, 0])


def extend_step_forward(
    params: Any,
    tokens: jax.Array,        # [B, T] int32 — T new tokens per slot
    start_positions: jax.Array,  # [B] int32 — position of tokens[:, 0]
    k_pages: jax.Array,       # [L, NP, Nkv, PS, D]
    v_pages: jax.Array,
    block_tables: jax.Array,  # [B, maxP] int32
    cfg: ModelConfig,
    write_ok: Any = None,     # [B, T] bool — False rows write scratch page 0
    attn_impl: str = "auto",  # forwarded to ops.paged_attention; the
                              # tensor-parallel engine forces "gather" (the
                              # Pallas kernel is opaque to GSPMD and would
                              # be replicated, gathering all pages per chip)
    w4_kernel_ok: bool = True,  # engine passes False under tensor-parallel:
                              # like the Pallas attention kernel, the W4
                              # matmul is a custom call GSPMD cannot
                              # partition — tp>1 must take the dequant path
                              # (same reason the engine forces attn gather)
    w8_kernel_ok: bool = False,  # OPT-IN (ServeConfig.int8_pallas_matmul):
                              # int8 dequant fuses in XLA, so the Pallas
                              # route needs a measured per-chip win first
    return_moe_stats: bool = False,
    ssm_state: Any = None,    # {"conv": [Lm, B, K-1, C], "ssm": [Lm, B, nh,
                              # P, N]}: the state-space layers' pools (a
                              # ``C`` model's: {"conv"} alone)
    ride: Any = None,         # a Piece (T == 1, ``can_carry`` models): its
                              # C rows join the B rows of the step
    two_bodies: bool = False,  # this step is one of the two bodies of a
                              # program that rides: the B slots' windows go
                              # through ``_shared_windows``, and a layer
                              # table's periodic part is walked by a loop
    state_slot: Any = None,   # int32 []: the ONE slot whose window this is
                              # (B == 1: chunked prefill through K layers)
    head_from: int = 0,       # static: the head runs over the window's rows
                              # from this one on (``denoise_scan``: its
                              # second half), logits [B, T - head_from, V]
    return_stream: bool = False,  # a layer table: also the residual stream
                              # before the final norm (what a next-token
                              # prediction module reads)
    live_rows: Any = None,    # [B, T] bool: the rows that are tokens, where
                              # they are not the rows written (a row computed
                              # again over a cached page writes nothing)
) -> StepResult:
    """Paged forward over T tokens per slot: the multi-token sibling of
    ``decode_step_forward``. Returns a ``StepResult``: logits [B, T, V]
    fp32, k_pages, v_pages and, asked with ``return_moe_stats`` (MoE
    models), ``moe_stats``: the [E + 1] int32 vector of the LIVE tokens'
    choices per expert summed over the layers (``write_ok`` rows; idle
    slots and padding get no expert) and, last, the (layer, expert) pairs
    that got any. A model with state-space layers takes ``ssm_state`` and
    returns it as ``state``, advanced in place for the rows ``write_ok``
    marks: its K/V pools hold the attention layers alone ([La, NP, ...]).
    A model with ``K`` (delta-rule linear attention) layers takes the same
    ``ssm_state`` (its pools under the same two names). Either kind takes,
    besides T = 1 over every slot, a WINDOW of one slot (B = 1 with
    ``state_slot``: a chunk of a prompt), which reads that slot's state and
    conv window, runs the chunked form from them and writes them back
    (``recur_chunk`` of ops/ssm.py and ops/kda.py; a window that starts its
    sequence, ``start_positions`` 0, takes them as zero).
    A model with LATENT attention keeps ONE pool: ``k_pages`` is the latent
    pool [La, NP, 1, PS, W] and ``v_pages`` is None, handed through.

    A model that generates by diffusion over blocks (``cfg.is_diffusion``)
    takes windows that start on a block and attends by the block rule: row
    j sees the paged prefix and its own WHOLE block of the window. Its
    denoise window is two blocks and only the second draws tokens
    (``head_from``).

    Token j sits at position ``start_positions + j`` and attends causally
    over the paged prefix *including* earlier tokens of this same call: all
    T tokens' K/V are written into the pages first, then attention runs
    with per-query length ``start + j + 1``. The pools are the layer
    loop's carry, written and read at ``pages[layer, page]``: nothing
    pool-sized is sliced out per layer or stacked back. A LOOPED stack
    (``cfg.num_passes`` > 1) walks its layers once a pass inside a loop over
    the passes, the pools the carry of both loops: layer l's weights, the
    pools' plane ``pass * L + l``. This one
    primitive powers both speculative-decode verification
    (serve/speculative.py: score K draft tokens in one weight-streaming
    pass — decode is HBM-bound on weights, so T<=8 tokens cost nearly
    the same as 1) and cached-prefix suffix
    prefill (only the un-cached tail of a prompt is computed).

    Attention goes through ops.paged_attention_multi: on TPU the
    head-folded Pallas kernel streams each page ONCE PER SLOT (all kv
    heads, all T queries); elsewhere a flattened [B*T]-row fallback of
    the single-token path (correct, but re-streams the prefix T-fold).

    ``ride`` (a ``Piece``; T = 1): the block runs ONCE over B + C flat
    rows, the B decode rows and the piece's C. Embedding, norms, matmuls,
    router and experts are per row and see one batch; only ``attend`` and
    ``recur`` tell the two apart: ``attend`` writes and attends the B rows
    as it does without a piece, then the piece as one window over its own
    slot's pages (what a chunked prefill's program does), and joins the
    outputs; ``recur`` moves the B slots' states one token and runs the
    piece as one window from its own slot's state (``recur_at``).
    The piece's padding (rows past ``ride.live``) writes the scratch page,
    reaches no expert and is not counted. The logits are then [B + 1, 1,
    V]: the B rows' and the piece's last live row's.
    """
    compute_dtype = jnp.dtype(cfg.dtype)
    B, T = tokens.shape
    positions = start_positions[:, None] + jnp.arange(T, dtype=jnp.int32)
    # [rows, T]: the rows that are tokens
    live = write_ok if live_rows is None else live_rows
    if ride is not None:
        if T != 1 or not can_carry(cfg):
            raise ValueError("a piece rides a decode step (T = 1) of a "
                             "model whose layers can carry one "
                             "(decode.can_carry)")
        offs = jnp.arange(ride.tokens.shape[0], dtype=jnp.int32)
        piece_ok = offs < ride.live
        tokens = jnp.concatenate([tokens, ride.tokens[:, None]])
        positions = jnp.concatenate([positions,
                                     (ride.start + offs)[:, None]])
        live = jnp.concatenate([
            jnp.ones((B, 1), bool) if write_ok is None else write_ok,
            piece_ok[:, None]])

    x = scaled(params["embed"]["embedding"][tokens].astype(compute_dtype),
               cfg.mup.embedding)                                 # [B,T,H]
    inv_freq = model_rope_frequencies(cfg)

    # W4A16 weights go through the in-kernel-dequant Pallas matmul on the
    # TPU: the XLA dequant chain writes the whole bf16 tensor to HBM and
    # reads it back, where the kernel streams the packed nibbles at their
    # 4-bit width. W8A16 can take the int8 sibling kernel
    # (ops.int8_matmul_pallas), but OPT-IN (ServeConfig.int8_pallas_matmul
    # -> w8_kernel_ok): XLA fuses the plain int8 dequant into the matmul.
    # Neither is measured on the attached chip: no cell has quantized
    # weights yet (ROADMAP A4 is the A/B).
    use_w4_kernel = w4_kernel_ok and jax.default_backend() == "tpu"
    use_w8_kernel = w8_kernel_ok and jax.default_backend() == "tpu"

    def mm(a, w):
        import math

        from ..ops.quantization import Quant4Tensor, QuantTensor
        # rows <= 64 keeps the Pallas kernels' whole-K activation blocks
        # in the 1-2 MB of VMEM they were designed for (decode T=1, verify
        # windows T<=8); long-T chunked/suffix prefill through those tiles
        # would blow VMEM — it takes the dequant path, where T amortises
        # the bf16 round trip anyway
        rows = math.prod(a.shape[:-1])
        if isinstance(w, QuantTensor):
            if (use_w8_kernel and rows <= 64
                    and w.shape[-1] % 128 == 0):
                from ..ops.int8_matmul_pallas import matmul_w8
                y = matmul_w8(a.reshape(rows, a.shape[-1]),
                              w.values, w.scale)
                return y.reshape(*a.shape[:-1], y.shape[-1])
            w = w.dequant(compute_dtype)
        if isinstance(w, Quant4Tensor):
            n_in, n_out = w.shape[-2], w.shape[-1]
            if (use_w4_kernel and rows <= 64 and n_out % 128 == 0
                    and n_in % w.group == 0):
                from ..ops.int4_matmul_pallas import matmul_w4
                y = matmul_w4(a.reshape(rows, a.shape[-1]), w.packed,
                              w.scale, w.chan, group=w.group)
                return y.reshape(*a.shape[:-1], y.shape[-1])
            w = w.dequant(compute_dtype)
        return a @ w

    blocks = precast_params(params["blocks"], compute_dtype)
    expert_stacks = None
    if cfg.is_moe:
        # plain expert kernels stay OUT of the layer scan, whole, and the
        # kernel takes them by layer index (models/gpt.py)
        blocks, expert_stacks = split_expert_stacks(blocks)
    return_moe_stats = return_moe_stats and cfg.is_moe

    def attend_pages(kp, vp, li, tables=block_tables, **window):
        """``attend`` over the page pools, written and read at layer
        ``li`` through ``tables`` (``window``: a window layer's, over its
        ring; ``_windows``)."""
        def attend(q, k, v):
            slots = _shared_windows if two_bodies else _windows
            if cfg.is_diffusion:
                # every window of such a model starts on a block and sees
                # by the block rule: the denoise window of one block, a
                # suffix or chunked prefill's of many
                return slots(q, k, v, kp, vp, tables, start_positions,
                             write_ok, li, attn_impl, cfg.attention_block)
            if ride is None:
                return slots(q, k, v, kp, vp, tables, start_positions,
                             write_ok, li, attn_impl, **window)
            # the B rows as ever, then the piece's C rows as ONE window
            # over its own slot's pages: [C, 1, N, D] -> [1, C, N, D]
            out, (new_k, new_v) = slots(
                q[:B], k[:B], v[:B], kp, vp, tables, start_positions,
                write_ok, li, attn_impl, **window)
            piece_out, state = _windows(
                *(a[B:, 0][None] for a in (q, k, v)), new_k, new_v,
                tables[ride.slot][None], ride.start[None],
                piece_ok[None], li, attn_impl, **window)
            return jnp.concatenate([out, piece_out[0][:, None]]), state
        return attend

    def head_rows(x):
        """The rows the head runs over: with a piece B + 1, the piece's
        last live row alone can become a token (its prompt's first, when
        the piece is final)."""
        if head_from:
            x = x[:, head_from:]
        if ride is None:
            return x
        return jnp.concatenate([x[:B], jax.lax.dynamic_slice_in_dim(
            x, B + jnp.maximum(ride.live - 1, 0), 1)])

    if cfg.layer_pattern:
        # a layer table: one parameter stack a kind, walked by a Python
        # loop; every pool (pages, conv tails, states) and every expert
        # stack stays whole and is addressed at its kind's layer index
        if cfg.is_recurrent and ssm_state is None:
            raise ValueError(f"a model with {cfg.recurrent_name} needs its "
                             "ssm_state pools")

        def recur_at(kind, pools, i, piece):
            ops = _RECURRENT.get(kind)
            if ops is None:
                return None
            # the B rows one token a slot over the pools; a program that
            # rides calls ONE jitted form from both its bodies
            step = ops.recur_step(
                cfg, *pools, i, write_ok,
                step=_shared_recur_step[kind] if two_bodies
                else ops.step_pools)
            if piece is None:
                return step
            # ONE slot's window [1, T] from that slot's own state: a chunk
            # of its prompt, or the piece a decode step carries
            chunk = ops.recur_chunk(cfg, *(a[i] for a in piece), piece_rows)

            def recur(*acts_and_layer):
                # (``M``: xBC, dt; ``K``: qkv, f, b; ``C``: z; then the
                # layer.) The state: (the pools, the window's (conv window,
                # state) after this layer)
                *acts, p = acts_and_layer
                if ride is None:        # the window is all the rows
                    out, after = chunk(*acts, p)
                    return out, (pools, after)
                # (the piece's slot is not armed: ``step`` leaves its rows
                # of the pools bit for bit)
                out, stepped = step(*(a[:B] for a in acts), p)
                piece_out, after = chunk(*(a[B:, 0][None] for a in acts), p)
                return (jnp.concatenate([out, piece_out[0][:, None]]),
                        (stepped, after))
            return recur
        blocks = cast_table_blocks(params["blocks"], compute_dtype)
        kp, vp = k_pages, v_pages
        if cfg.hc_mult > 1:
            # the residual STREAMS: copies of the embedding, summed before
            # the final norm
            x = jnp.broadcast_to(x[:, :, None], (*x.shape[:2], cfg.hc_mult,
                                                 x.shape[-1]))

        def attend_at(kp, vp, li):
            if cfg.is_latent:
                return attend_latent_pages(
                    cfg, kp, li, block_tables, start_positions, write_ok,
                    attn_impl, ride=ride, two_bodies=two_bodies)
            return attend_pages(kp, vp, li)
        # (the state dict's pools in its own order: conv, then ssm if any)
        pools = tuple(ssm_state.values()) if ssm_state is not None else ()
        # a window of ONE slot through the recurrent (``M``, ``K`` or ``C``)
        # layers, a chunk of its prompt or the piece a decode step carries:
        # the slot's rows of the pools are read here, once ((conv windows,
        # states), stacked [Lm | Lk, ...]), ride the layer walk's carry,
        # written at a layer's index (which a loop over the table's
        # periodic part traces), and are written back after the last
        # layer, once
        piece = piece_slot = None
        recurrent = recurrent_ops(cfg)
        if state_slot is not None:
            piece_slot, piece_start, piece_rows, piece_live = (
                state_slot, start_positions, write_ok, True)
        elif ride is not None and cfg.is_recurrent:
            piece_slot, piece_start, piece_rows, piece_live = (
                ride.slot, ride.start[None], piece_ok[None],
                ride.live > 0)      # False: a step that carries nothing
        if piece_slot is not None:
            piece = recurrent.slot_state(*pools, piece_slot, piece_start)
        stats = jnp.zeros((cfg.moe.stats_size,), jnp.int32)

        def sub_layer(carry, kind, i):
            x, kp, vp, pools, stats, piece = carry
            x, state, layer_stats = decoder_block(
                x, table_layer(blocks, kind, i), cfg, positions, inv_freq,
                attend_at(kp, vp, i) if kind in "*P" else None, matmul=mm,
                live=live, layer_index=i, kind=kind,
                recur=recur_at(kind, pools, i, piece))
            if kind == "P":
                # both mixers' states: the page pools, then the recurrent one
                (kp, vp), state = state
                kind = "M"
            if kind == "*":
                kp, vp = state
            elif kind in "MKC" and piece is not None:
                pools, after = state
                piece = tuple(a.at[i].set(new.astype(a.dtype))
                              for a, new in zip(piece, after))
            elif kind in "MKC":
                pools = state
            elif kind == "E":
                stats = stats + layer_stats
            return x, kp, vp, pools, stats, piece
        # A program that rides holds two step bodies, and a table walked by
        # a Python loop holds every layer's kernels once a body: the latent
        # cell's executable grew from 57 to 148 MB and its first call from
        # 10.9 to 16.4 s of every start (PERF.md 6, PR 41; the linear
        # cell's from 54 to 125 MB, 95 under the loop: PR 43). Its bodies
        # walk the table's periodic part (``*E`` x 6; ``KEKEKE*E`` x 2;
        # ``MEMEM*E`` x 2) by a
        # loop over traced layer indices, as the uniform stack's scan does;
        # the pools are the loop's carry, written in place. Any other
        # program walks it whole.
        head, unit, reps, tail = (table_period_and_tail(cfg) if two_bodies
                                  else (table_layers(cfg), [], 0, []))
        carry = (x, kp, vp, pools, stats, piece)
        for kind, i in head:
            carry = sub_layer(carry, kind, i)
        if reps:
            per_rep = collections.Counter(kind for kind, _ in unit)

            def period(r, carry):
                for kind, i in unit:
                    carry = sub_layer(carry, kind, i + r * per_rep[kind])
                return carry
            carry = jax.lax.fori_loop(0, reps, period, carry)
        for kind, i in tail:    # (a table that does not end in its period)
            carry = sub_layer(carry, kind, i)
        x, kp, vp, pools, stats, piece = carry
        if piece is not None:
            pools = recurrent.write_slot_state(
                *pools, piece_slot, *piece, piece_live)
        if cfg.hc_mult > 1:
            x = jnp.sum(x.astype(jnp.float32), axis=2).astype(compute_dtype)
        step = StepResult(
            unembed(params, head_rows(x), cfg), kp, vp,
            stats if return_moe_stats else None,
            dict(zip(ssm_state, pools)) if ssm_state is not None else None)
        return StepWithStream(*step, x) if return_stream else step

    def walk(x, kp, vp, stats, first_plane=None):
        """ONE walk of the L layers over the pools: layer l's weights, the
        pools' plane ``first_plane + l`` (l itself where the stack is
        walked once)."""
        def body(carry, layer_and_index):
            # the pools must stay a CARRY that every layer writes and reads
            # by its index: as scanned inputs and stacked outputs XLA slices
            # each layer's slab out (94 MB at mistral-7b, 715 pages), writes
            # it back, and copies the step's fresh pool whole
            x, kp, vp, stats = carry
            layer, li = layer_and_index
            plane = li if first_plane is None else first_plane + li
            # per-layer cast/dequant: quantized serving weights either stay
            # packed for the Pallas matmuls above (TPU) or materialise one
            # layer of bf16 at a time (ops.quantization)
            layer = cast_params(layer, compute_dtype, keep_w4=use_w4_kernel,
                                keep_w8=use_w8_kernel)
            moe_li = None
            if cfg.is_moe:
                # moe_block contracts expert weights directly (no matmul
                # injection) — a passed-through Quant[4]Tensor would hit
                # `a @ w` untyped; experts take the dequant path
                layer = dict(layer, moe=cast_params(layer["moe"],
                                                    compute_dtype))
                layer, moe_li = layer_experts(layer, expert_stacks, li)

            x, (kp, vp), layer_stats = decoder_block(
                x, layer, cfg, positions, inv_freq,
                attend_pages(kp, vp, plane), matmul=mm, live=live,
                layer_index=moe_li, rope_scale=rope_scale(cfg.rope))
            if stats is not None:
                stats = stats + layer_stats
            return (x, kp, vp, stats), None

        (x, kp, vp, stats), _ = jax.lax.scan(
            body, (x, kp, vp, stats),
            (blocks, jnp.arange(cfg.num_layers, dtype=jnp.int32)))
        return x, kp, vp, stats

    def walk_windowed(x, kp, vp, stats):
        """The walk of a stack with WINDOW layers: a scan over the PERIODS
        of ``cfg.layer_types`` (three window layers and a full one), a
        layer's kind static inside one. A full layer writes and walks the
        full pool's plane through the slot's chain, as every model's layer
        does; a window layer the window pool's plane through the slot's
        ring, from the first page that holds a visible key, under the
        kind's own rope. Both pools (``SplitPages``) are the scan's carry,
        written in place."""
        period = cfg.window_period
        reps = cfg.num_layers // len(period)
        ring = kp.ring
        T_rows = max(T, ride.tokens.shape[0] if ride is not None else 1)
        if ring < ring_pages(cfg.sliding_window, kp.shape[-2], T_rows):
            raise ValueError(
                f"{cfg.name}: a window of {T_rows} rows over a ring of "
                f"{ring} pages of {kp.shape[-2]} would overwrite rows its "
                f"own queries see (sliding_window {cfg.sliding_window}: "
                "kv_cache.ring_pages)")
        tables = dict(zip(("full", "window"), kp.tables_of(block_tables)))
        kinds = {k: layer_kind(cfg, k) for k in set(period)}
        # which plane of its pool the period's j-th layer is, from the
        # period's first plane of that pool
        before = [sum(k == kind for k in period[:j])
                  for j, kind in enumerate(period)]
        per_period = {k: period.count(k) for k in set(period)}

        def body(carry, layers_and_period):
            x, kp, vp, stats = carry
            layers, r = layers_and_period
            for j, kind in enumerate(period):
                layer = jax.tree_util.tree_map(lambda a: a[j], layers)
                li = r * len(period) + j
                layer = cast_params(layer, compute_dtype,
                                    keep_w4=use_w4_kernel,
                                    keep_w8=use_w8_kernel)
                moe_li = None
                if cfg.is_moe:
                    layer = dict(layer, moe=cast_params(layer["moe"],
                                                        compute_dtype))
                    layer, moe_li = layer_experts(layer, expert_stacks, li)
                plane = r * per_period[kind] + before[j]
                lk = kinds[kind]
                pool = "window" if kind == "sliding" else "full"
                attend = attend_pages(
                    getattr(kp, pool), getattr(vp, pool), plane, tables[pool],
                    **({"window": lk.window} if lk.window else {}))
                x, (new_k, new_v), layer_stats = decoder_block(
                    x, layer, cfg, positions, lk.inv_freq, attend, matmul=mm,
                    live=live, layer_index=moe_li, rope_scale=lk.rope_scale)
                kp, vp = (kp.replace(**{pool: new_k}),
                          vp.replace(**{pool: new_v}))
                if stats is not None:
                    stats = stats + layer_stats
            return (x, kp, vp, stats), None

        by_period = jax.tree_util.tree_map(
            lambda a: a.reshape(reps, len(period), *a.shape[1:]), blocks)
        (x, kp, vp, stats), _ = jax.lax.scan(
            body, (x, kp, vp, stats),
            (by_period, jnp.arange(reps, dtype=jnp.int32)))
        return x, kp, vp, stats

    stats0 = (jnp.zeros((cfg.moe.stats_size,), jnp.int32)
              if return_moe_stats else None)
    if cfg.has_window:
        x, new_k, new_v, stats = walk_windowed(x, k_pages, v_pages, stats0)
        return StepResult(unembed(params, head_rows(x), cfg), new_k, new_v,
                          stats)
    if not cfg.is_looped:
        x, new_k, new_v, stats = walk(x, k_pages, v_pages, stats0)
        return StepResult(unembed(params, head_rows(x), cfg), new_k, new_v,
                          stats)

    # a LOOPED stack: the same walk once a pass, the pools the carry of both
    # loops, pass t's planes t * L .. (t + 1) * L; ``close_pass`` norms the
    # stream for the next pass and keeps what the head reads
    def one_pass(t, carry):
        x, kp, vp, exits = carry
        with jax.named_scope("loop_pass"):
            x, kp, vp, _ = walk(x, kp, vp, None, t * cfg.num_layers)
        z, _, exits = close_pass(params, x, cfg, t, exits)
        return z, kp, vp, exits
    _, new_k, new_v, exits = jax.lax.fori_loop(
        0, cfg.num_passes, one_pass,
        (x, k_pages, v_pages, ExitState.start(x)))
    return StepResult(head_logits(params, head_rows(exits.out), cfg),
                      new_k, new_v, stats0)


def _latent_windows(q_lat, rows, pool, tables, starts, ok, li, scale,
                    value_width, attn_impl):
    """Write each slot's window of latent rows into its pages at layer
    ``li`` (zero-padded to the pool's row width) and let every head's
    absorbed query walk the slot's live pages once: (out, new pool)."""
    with jax.named_scope("mla_page_write"):
        rows = pad_to_page_width(rows, pool)
        new = write_window_to_pages(pool, rows[:, :, None, :], tables,
                                    starts, ok, li)
    out = mla_paged_attention(
        pad_to_page_width(q_lat, pool), new, tables, starts, scale=scale,
        value_width=value_width, impl=attn_impl, layer=li)
    return out, new


# the latent ``_shared_windows``: a riding program's two bodies AND its
# layers (a layer table is walked by a Python loop) call ONE function a
# window shape, the B slots' T = 1 and the piece's T = C
_shared_latent_windows = jax.jit(
    _latent_windows, static_argnames=("scale", "value_width", "attn_impl"))


def attend_latent_pages(cfg: ModelConfig, pool: jax.Array, li,
                        block_tables: jax.Array, start_positions: jax.Array,
                        write_ok: Any = None, attn_impl: str = "auto",
                        ride: Any = None, two_bodies: bool = False):
    """The latent ``attend`` (``layers.latent_attention_mixer``) over the ONE
    latent pool ``pool`` [La, NP, 1, PS, W], written and read at layer
    ``li``: the window's rows go in by ``write_window_to_pages`` as every
    window's do, then every head's absorbed query walks the slot's live
    pages once. With ``ride`` the B slots' rows go first, as ever, then the
    piece's C rows as ONE window over its own slot's pages (the multi-query
    kernel, what a suffix prefill's program runs). The state it returns is
    (the pool, None): there is no second pool."""
    windows = _shared_latent_windows if two_bodies else _latent_windows
    B = block_tables.shape[0]

    def attend(q_lat, rows, scale):
        how = dict(scale=scale, value_width=cfg.mla.kv_lora_rank,
                   attn_impl=attn_impl)
        if ride is None:
            out, new = windows(q_lat, rows, pool, block_tables,
                               start_positions, write_ok, li, **how)
            return out, (new, None)
        out, new = windows(q_lat[:B], rows[:B], pool, block_tables,
                           start_positions, write_ok, li, **how)
        # [C, 1, ...] -> [1, C, ...]; the padding goes to the scratch page
        piece_ok = jnp.arange(ride.tokens.shape[0]) < ride.live
        piece_out, new = windows(
            q_lat[B:, 0][None], rows[B:, 0][None], new,
            block_tables[ride.slot][None], ride.start[None], piece_ok[None],
            li, **how)
        return jnp.concatenate([out, piece_out[0][:, None]]), (new, None)
    attend.latent = True
    return attend


# -- self-drafting: the next-token prediction module over the latent pool ----
#
# The module's layer is one more layer of the latent pool. Its row for
# position i reads the embedding of token i + 1, so it is stored at cache
# index i + 1, the index of the token it read: a page then holds nothing that
# depends on a token after the page, and page-hash prefix reuse stays what it
# is for every other layer (``kv_cache.prefix_page_hashes``). Index 0 has no
# row; what stands there is a SENTINEL, zeros with ``MTP_SENTINEL`` in the
# first lane of the row's padding (``MLAConfig.page_width`` > ``latent_size``),
# and every query of the module carries a 1 in that lane: index 0 scores
# ``MTP_SENTINEL x scale`` (-2,165 at the published head size), its softmax
# weight is exactly 0 in float32, and every real row's padding lane is 0.
MTP_SENTINEL = -30000.0


def mtp_attend_pages(cfg: ModelConfig, pool: jax.Array,
                     block_tables: jax.Array, index_starts: jax.Array,
                     write_ok: Any = None, attn_impl: str = "auto"):
    """The module's ``attend``: ``attend_latent_pages`` at the pool's last
    layer with the window written from cache index ``index_starts`` (the
    rows' positions + 1) and index 0 masked by the sentinel's lane."""
    la, _ = mtp_layer_index(cfg)
    lane = cfg.mla.latent_size
    if pool.shape[-1] <= lane:
        raise ValueError(
            f"{cfg.name}: the prediction module masks cache index 0 through "
            f"a lane of the latent row's padding, and a row of {lane} values "
            f"is stored {pool.shape[-1]} wide: no lane is left")

    def attend(q_lat, rows, scale):
        q = pad_to_page_width(q_lat, pool).at[..., lane].set(1)
        out, new = _latent_windows(
            q, rows, pool, block_tables, index_starts, write_ok, la,
            scale=scale, value_width=cfg.mla.kv_lora_rank,
            attn_impl=attn_impl)
        return out, (new, None)
    attend.latent = True
    return attend


def mtp_sentinel_row(cfg: ModelConfig, pool: jax.Array) -> jax.Array:
    """What stands at cache index 0 of the module's layer: [W]."""
    return jnp.zeros((pool.shape[-1],), pool.dtype).at[
        cfg.mla.latent_size].set(MTP_SENTINEL)


def mtp_window(params, cfg: ModelConfig, next_tokens: jax.Array,
               stream: jax.Array, starts: jax.Array, pool: jax.Array,
               block_tables: jax.Array, write_ok: jax.Array,
               attn_impl: str = "auto", first: bool = False):
    """The module over a window of every slot: rows at positions ``starts``
    + j read ``next_tokens[:, j]`` (the token at position + 1) and
    ``stream[:, j]``, their latent rows go to cache index position + 1 of
    the pool's last layer (``write_ok`` rows alone) and each attends over
    indices 1 .. its own. ``first`` (a prefill window): a window that
    starts its sequence also writes the sentinel at index 0. Returns
    (z' [B, T, H], the pool, the expert layer's ``moe_stats``)."""
    B, T = next_tokens.shape
    la, _ = mtp_layer_index(cfg)
    if first:
        with jax.named_scope("mla_page_write"):
            pool = write_window_to_pages(
                pool, jnp.broadcast_to(mtp_sentinel_row(cfg, pool),
                                       (B, 1, 1, pool.shape[-1])),
                block_tables, jnp.zeros_like(starts),
                (starts == 0)[:, None], la)
    positions = starts[:, None] + jnp.arange(T, dtype=jnp.int32)
    z, (pool, _), stats = mtp_forward(
        params, cast_table_blocks(params["blocks"], jnp.dtype(cfg.dtype)),
        cfg, next_tokens, stream, positions, model_rope_frequencies(cfg),
        mtp_attend_pages(cfg, pool, block_tables, starts + 1, write_ok,
                         attn_impl),
        live=write_ok)
    return z, pool, stats


# a step's row of ``draft_verify_scan``'s record, a slot: the token the main
# stack made for the slot's next position, the one it made for the position
# after (emitted where the draft stood), how many of the two were emitted,
# and the draft the step verified
DRAFT_RECORD = ("token", "second", "n_emit", "draft")


def draft_verify_scan(params, tokens, positions, k_pages, v_pages,
                      block_tables, stop_positions, slot_keys, temperature,
                      top_k, top_p, cfg: ModelConfig, num_steps: int,
                      attn_impl: str = "auto",
                      return_moe_stats: bool = False) -> DispatchResult:
    """``decode_scan`` for a model that drafts for itself
    (``cfg.mtp_layers``; ``ServeConfig.speculative: mtp``): ``num_steps``
    draft-and-verify steps chained on the device, 1 or 2 tokens a slot a
    step.

    ``tokens`` is (tokens [B], drafts [B]): a slot's last sure token, at
    ``positions`` [B], and the module's draft of the token after it. One
    step, every slot, static shapes:

    - the main stack runs the WINDOW (token at p, draft at p + 1) over the
      slot's latent pages (``extend_step_forward`` at T = 2: the
      multi-query latent kernel) and ONE head matmul makes both rows'
      logits; g = the token at p + 1 (argmax, or for temperature > 0 the
      sample a plain step would draw: the slot's key folded by p + 1), and
      the draft STANDS where it is g, the request is greedy and p + 1 is a
      position the slot may write;
    - where it stands, g2 = argmax of the second row is emitted too and the
      slot moves 2; else it moves 1, and the draft's stale row at p + 1 is
      overwritten by the next step's window;
    - the module runs rows p (with the embedding of g) and p + 1 (with that
      of g2; dead, unwritten and expertless where the draft fell) over its
      own layer of the pool (``mtp_window``), and its head over the last
      row that stands makes the next draft.

    A slot at or past ``stop_positions`` writes the scratch page and keeps
    its carry. The greedy stream is plain greedy decoding of the main stack
    token for token (a draft is only ever emitted as the main stack's own
    argmax). Returns a ``DispatchResult``: ``sampled`` [K, B, 4] int32, a
    step's row a slot as ``DRAFT_RECORD`` has it; the final carry
    (``tokens`` = (tokens, drafts), ``positions``); the pool; the summed
    ``moe_stats`` of main stack and module."""
    if not (cfg.mtp_layers and cfg.is_latent):
        raise ValueError(f"{cfg.name}: draft_verify_scan needs a latent "
                         "model with a next-token prediction module")
    return_moe_stats = return_moe_stats and cfg.is_moe
    greedy_slot = temperature <= 0.0

    def one(carry, _):
        (toks, drafts), pos, pool, stats = carry
        live0, live1 = pos < stop_positions, pos + 1 < stop_positions
        step = extend_step_forward(
            params, jnp.stack([toks, drafts], axis=1), pos, pool, v_pages,
            block_tables, cfg, write_ok=jnp.stack([live0, live1], axis=1),
            attn_impl=attn_impl, return_moe_stats=return_moe_stats,
            return_stream=True)
        with jax.named_scope("draft_verify"):
            logits = step.logits                              # [B, 2, V]
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            keys = jax.vmap(jax.random.fold_in)(
                jax.vmap(jax.random.wrap_key_data)(slot_keys), pos + 1)
            with jax.named_scope("sampler"):
                sampled = sample_tokens(logits[:, 0], keys, temperature,
                                        top_k, top_p)
            g = jnp.where(greedy_slot, greedy[:, 0], sampled)
            g2 = greedy[:, 1]
            stands = greedy_slot & (greedy[:, 0] == drafts) & live1
        # the module's rows: p always (its index p + 1 is the slot's to
        # write while p + 1 is), p + 1 where the draft stood
        z, pool, mtp_stats = mtp_window(
            params, cfg, jnp.stack([g, g2], axis=1), step.stream, pos,
            step.k_pages, block_tables,
            jnp.stack([live1, stands & (pos + 2 < stop_positions)], axis=1),
            attn_impl)
        with jax.named_scope("draft_verify"):
            last = jnp.where(stands[:, None, None], z[:, 1:], z[:, :1])
        draft = jnp.argmax(mtp_head(params, last, cfg)[:, 0],
                           axis=-1).astype(jnp.int32)
        with jax.named_scope("draft_verify"):
            n_emit = 1 + stands.astype(jnp.int32)
            record = jnp.stack([g, g2, n_emit, drafts], axis=-1)
            toks = jnp.where(live0, jnp.where(stands, g2, g), toks)
            drafts = jnp.where(live0, draft, drafts)
            pos = jnp.where(live0, pos + n_emit, pos)
        if stats is not None:
            stats = stats + step.moe_stats + mtp_stats
        return ((toks, drafts), pos, pool, stats), record

    stats0 = (jnp.zeros((cfg.moe.stats_size,), jnp.int32)
              if return_moe_stats else None)
    (toks, pos, pool, stats), records = jax.lax.scan(
        one, (tokens, positions, k_pages, stats0), None, length=num_steps)
    return DispatchResult(records, toks, pos, pool, v_pages, stats)


def decode_multi_step(params, tokens, positions, k_pages, v_pages,
                      block_tables, stop_positions, slot_keys, temperature,
                      top_k, top_p, cfg: ModelConfig, num_steps: int,
                      attn_impl: str = "auto", w4_kernel_ok: bool = True,
                      w8_kernel_ok: bool = False) -> DispatchResult:
    """``decode_scan`` as the K-step program was first called: no routing
    counts, no state pools, no pieces (experiments/spec_profile.py)."""
    return decode_scan(
        params, tokens, positions, k_pages, v_pages, block_tables,
        stop_positions, slot_keys, temperature, top_k, top_p, cfg,
        num_steps, attn_impl, w4_kernel_ok, w8_kernel_ok)


def decode_scan(params, tokens, positions, k_pages, v_pages, block_tables,
                stop_positions, slot_keys, temperature, top_k, top_p,
                cfg: ModelConfig, num_steps: int, attn_impl: str = "auto",
                w4_kernel_ok: bool = True, w8_kernel_ok: bool = False,
                return_moe_stats: bool = False, ssm_state: Any = None,
                ride: Any = None) -> DispatchResult:
    """``num_steps`` decode+sample iterations in ONE compiled program: the
    engine's decode dispatch, and the tail of the fused speculative one
    (speculative.verify_and_decode). A host-driven single-step loop costs
    a host<->device round trip a token (dispatch + sync; not re-measured on
    a directly attached chip); K steps scanned on the device amortise it
    K-fold (vLLM-style multi-step scheduling, TPU-shaped: one XLA program,
    sampling included).

    ``tokens`` / ``positions`` [B]: each slot's newest token and its
    position; ``slot_keys`` [B, 2] uint32 key data. Per-slot stop handling:
    rows at/past ``stop_positions`` [B] (the first a slot must NOT write)
    redirect KV writes to scratch page 0 and re-emit their previous token.
    Slots that hit EOS mid-scan keep decoding into their (reserved) pages;
    the host trims trailing tokens — at most ``num_steps - 1`` wasted
    iterations per finished request. Sampling folds the per-slot key by
    position, so generations are bit-identical to ``num_steps=1``.

    Returns a ``DispatchResult``: ``sampled`` [K, B], the final carry
    (``tokens``, ``positions``) and the pools; with ``return_moe_stats``
    (MoE models) the steps' summed ``moe_stats`` (``extend_step_forward``),
    and given ``ssm_state`` (a recurrent model) the pools as ``state``.

    ``ride`` ([K, PIECE_META + C] int32, one ``Piece`` a step): each step
    also takes the rows of ONE pending prompt's piece through the block
    (``extend_step_forward``), and the result has ``firsts`` [K] too: a
    final piece's last live row samples its prompt's first token with the
    slot's key folded by the prompt's length, as the cold prefill program's
    caller folds it (``engine._sampling_args``), so the token is that
    program's token; 0 for any other step. That step also ARMS the slot in
    the carry (token, position, stop), so the slot decodes from the next
    step on, whatever the host knows. The carrying steps come first: the
    steps after the last live piece run the plain step (a step that carried
    C dead rows instead cost their time in every step: PERF.md 6, PR 36)."""
    return_moe_stats = return_moe_stats and cfg.is_moe

    def advance(toks, pos, stops, kp, vp, state, piece):
        """One step: (next tokens, first token, the step's ``StepResult``)."""
        act = pos < stops
        step = decode_step_forward(
            params, toks, pos, kp, vp, block_tables, cfg, active=act,
            attn_impl=attn_impl, w4_kernel_ok=w4_kernel_ok,
            w8_kernel_ok=w8_kernel_ok, return_moe_stats=return_moe_stats,
            ssm_state=state, ride=piece, two_bodies=ride is not None)
        logits = step.logits
        key_data, fold = slot_keys, pos + 1
        sampling = (temperature, top_k, top_p)
        if ride is not None:
            # row B: the piece's last live row, under its own slot's key
            # and sampling parameters. A plain step of a program that rides
            # samples B + 1 rows too (row 0 again, dropped): the two bodies
            # then hold ONE sampler (``_shared_sampler``), which is most of
            # a decode program's executable (3.1 of 3.5 MB at mistral-7b's
            # vocabulary)
            slot, at = ((0, fold[0]) if piece is None
                        else (piece.slot, piece.start + piece.live))
            if piece is None:
                logits = jnp.concatenate([logits, logits[:1]])
            key_data = jnp.concatenate([slot_keys, slot_keys[slot][None]])
            fold = jnp.append(fold, at)
            sampling = [jnp.append(a, a[slot]) for a in sampling]
            if cfg.layer_pattern and len(fold) % 8:
                # row B again, up to whole tiles of 8 rows: at the latent
                # cell's vocabulary of 131k the sampler's code is 18 MB
                # over 64 rows, 40 over 65 and 22 over 72, twice in the
                # executable (compiled for a described v5e, PERF.md 6, PR
                # 41). The uniform stack's programs keep the B + 1 rows
                # they were accepted with (3.1 MB at a vocabulary of 32k)
                def whole(a):
                    return jnp.concatenate(
                        [a, jnp.repeat(a[-1:], -len(a) % 8, axis=0)])
                logits, key_data, fold = map(whole, (logits, key_data, fold))
                sampling = [whole(a) for a in sampling]
        keys = jax.vmap(jax.random.fold_in)(
            jax.vmap(jax.random.wrap_key_data)(key_data), fold)
        with jax.named_scope("sampler"):
            nxt = (sample_tokens if ride is None else _shared_sampler)(
                logits, keys, *sampling)
        first = jnp.int32(0)
        if ride is not None:
            nxt = nxt[:len(toks) + 1]          # without the tile's padding
            nxt, last = nxt[:-1], nxt[-1]
            if piece is not None:
                first = jnp.where(piece.stop > 0, last, 0)
        return jnp.where(act, nxt, toks), first, step

    # the scan's carry: (tokens, positions, K pool, V pool, moe stats,
    # state pools), None where the model has none
    stats0 = (jnp.zeros((cfg.moe.stats_size,), jnp.int32)
              if return_moe_stats else None)
    carry0 = (tokens, positions, k_pages, v_pages, stats0, ssm_state)

    def one(carry, piece, stops=stop_positions):
        toks, pos, kp, vp, stats, state = carry
        nxt, first, step = advance(toks, pos, stops, kp, vp, state, piece)
        if stats is not None:
            stats = stats + step.moe_stats
        pos = pos + 1
        if piece is not None:
            # a piece that ends its prompt ARMS its slot on the device: the
            # first token, the prompt's length and the slot's stop go into
            # the carry, and the slot decodes from the next step on (and
            # from the next dispatch's first step, chained on this carry:
            # the host learns the token when it fetches this dispatch, and
            # nothing waits for that)
            def armed(row, value):
                return row.at[piece.slot].set(
                    jnp.where(piece.stop > 0, value, row[piece.slot]))
            nxt, pos, stops = (armed(nxt, first),
                               armed(pos, piece.start + piece.live),
                               armed(stops, piece.stop))
        return ((nxt, pos, step.k_pages, step.v_pages, stats, step.state),
                stops, (nxt, first))

    def result(carry, sampled, firsts=None):
        toks, pos, kp, vp, stats, state = carry
        return DispatchResult(sampled, toks, pos, kp, vp, stats, state,
                              firsts)

    if ride is None:
        def plain(carry, _):
            carry, _stops, (nxt, _no_first) = one(carry, None)
            return carry, nxt
        return result(*jax.lax.scan(plain, carry0, None, length=num_steps))

    # the steps that carry a piece come FIRST (the engine lays a dispatch's
    # pieces from its first step on): one loop over them, then one over the
    # plain steps, each of a trip count read off ``ride``. Two loops and no
    # ``cond``: a loop carries the pools in place, where the branches of a
    # ``cond`` each got a copy of both; and side by side, not inside a scan
    # over the steps, so that what XLA hoists out of the parent's step loop
    # (the q / k / v stacks' re-layouts, 2.4 ms) it hoists out of these.
    live = Piece.unpack(ride.T).live > 0               # [K]
    n_carry = jnp.max(jnp.where(live, jnp.arange(num_steps) + 1, 0))

    def steps(lo, hi, loop, carrying):
        def body(i, loop):
            carry, stops, (seq, firsts) = loop
            carry, stops, (nxt, first) = one(
                carry, Piece.unpack(ride[i]) if carrying else None, stops)
            return carry, stops, (seq.at[i].set(nxt),
                                  firsts.at[i].set(first))
        return jax.lax.fori_loop(lo, hi, body, loop)

    out0 = (jnp.zeros((num_steps, tokens.shape[0]), tokens.dtype),
            jnp.zeros((num_steps,), jnp.int32))
    loop = steps(0, n_carry, (carry0, stop_positions, out0), carrying=True)
    carry, _stops, out = steps(n_carry, num_steps, loop, carrying=False)
    return result(carry, *out)


# ``fixed_at`` of a window row that is still to fix (a row of the prompt
# holds -1, a fixed one its denoise step): what "masked" MEANS. The row's
# token is the mask token, but so may a prompt's be
UNFIXED = -2
# what a denoise dispatch counts on the device, summed over its forwards
# and its live slots (``denoise_scan``; ``stats()["diffusion"]``)
DENOISE_COUNTS = ("slot_forwards", "commit_slot_forwards", "masked_rows",
                  "tokens_fixed", "threshold_fixed", "blocks_committed",
                  "live_pages", "fused_commits")


def denoise_scan(params, window, starts, k_pages, v_pages, block_tables,
                 stop_positions, slot_keys, temperature, top_k, top_p,
                 cfg: ModelConfig, num_steps: int, attn_impl: str = "auto",
                 w4_kernel_ok: bool = True, w8_kernel_ok: bool = False):
    """``decode_scan`` for a model that generates by diffusion over blocks:
    ``num_steps`` forwards of every slot's WINDOW chained on the device, the
    transfer rule and the commit inside the program.

    A slot's window is ``2 * block_length`` rows from one block BEFORE the
    block it is denoising: the block it finished last (its final tokens),
    then the current block (``starts`` [B]: its first position), whose rows
    see the first half and each other. ``window`` is (tokens [B, 2 Bd]
    int32, the mask token on the rows still to fix; fixed_at [B, Bd] int32
    of the CURRENT block, the denoise step at which a row was fixed, -1 for
    a row of the prompt, ``UNFIXED`` for a row still to fix; step [B]
    int32, the denoise forwards the block has had; pending [B] bool, the
    first half's K/V are still to store). A slot is live while ``starts <
    stop_positions``. One forward of every window
    (``extend_step_forward`` at T = 2 Bd, the head over the second half
    alone), then by slot, one uniform body under ``jnp.where``:

    - the first half is LIVE where ``pending``: the one forward after the
      fix of that block's last mask. Its K/V go to the pages before
      attention runs, so the second half sees them: the COMMIT rides the
      next block's first denoise forward. In every other forward it is
      dead: it writes the scratch page, reaches no expert, is not counted,
      and the second half sees the K/V that stand in the pages (a prefill
      program's, after an admission);
    - the second half's K/V are written every time, a later forward
      overwrites them. Every masked row draws a token and its probability
      (``sample_tokens_with_prob``; key: the slot's, folded by the row's
      position then by the step) and ``transfer_rows`` fixes some of them;
      ``step`` goes on by one;
    - the step that fixes a block's LAST mask emits it (its tokens are
      final): ``starts`` moves on by Bd, the block becomes the first half,
      pending, and the second half is masks again. No forward runs on a
      window without masks: a reply's last block is never stored, and
      nothing reads its pages (serve/engine.py ``_preempt`` publishes a
      slot's pages up to the last STORED block).

    Returns ((window, starts, k_pages, v_pages, [moe_stats,] counts),
    out [K, B, 2 Bd + 1] int32): a step's row of ``out`` holds the slot's
    current block AFTER the step, tokens then ``fixed_at``, and whether the
    step emitted it. ``counts``: ``DENOISE_COUNTS`` summed over the steps
    and the live slots."""
    f = cfg.diffusion
    Bd, mask_id = f.block_length, f.mask_token_id
    B = starts.shape[0]
    schedule = jnp.asarray(f.transfer_schedule, jnp.int32)
    offs = jnp.arange(Bd, dtype=jnp.int32)
    PS = k_pages.shape[-2]

    def one(carry, _):
        (toks, fixed_at, step, pending), starts, kp, vp, *stats, counts = carry
        live = starts < stop_positions
        store = live & pending
        walked = jnp.where(live, (starts + Bd - 1) // PS + 1, 0)
        with jax.named_scope("denoise_step"):
            step_out = extend_step_forward(
                params, toks, starts - Bd, kp, vp, block_tables, cfg,
                write_ok=jnp.repeat(jnp.stack([store, live], axis=1), Bd,
                                    axis=1),
                attn_impl=attn_impl, w4_kernel_ok=w4_kernel_ok,
                w8_kernel_ok=w8_kernel_ok, return_moe_stats=True,
                head_from=Bd)
            logits, kp, vp = (step_out.logits, step_out.k_pages,
                              step_out.v_pages)
            layer_stats = ([] if step_out.moe_stats is None
                           else [step_out.moe_stats])
            # a row's key: the slot's, by its position, then by the step
            keys = jax.vmap(jax.random.wrap_key_data)(slot_keys)
            keys = jax.vmap(lambda key, start, s: jax.vmap(
                lambda p: jax.random.fold_in(jax.random.fold_in(key, p), s))(
                    start + offs))(keys, starts, step)

            def rows(a):
                return jnp.repeat(a, Bd)
            # the mask token is never drawn: a row fixed TO it would read
            # as masked for ever (random weights put it first once in a
            # vocabulary's worth of rows)
            logits = logits.at[..., mask_id].set(-jnp.inf)
            x0, prob = sample_tokens_with_prob(
                logits.reshape(B * Bd, -1), keys.reshape(B * Bd),
                rows(temperature), rows(top_k), rows(top_p))
            x0, prob = x0.reshape(B, Bd), prob.reshape(B, Bd)
        with jax.named_scope("unmask"):
            masked = fixed_at == UNFIXED
            has_mask = masked.any(axis=-1)
            wanted = schedule[jnp.minimum(step, len(f.transfer_schedule) - 1)]
            fix, beyond = transfer_rows(
                prob, masked, wanted, f.remasking_strategy,
                f.confidence_threshold)
            block = jnp.where(fix, x0, toks[:, Bd:])
            block_at = jnp.where(fix, step[:, None], fixed_at)
            done = live & ~(masked & ~fix).any(axis=-1)
            moved = done[:, None]
            masks = jnp.full_like(block, mask_id)
            new = (jnp.where(moved, jnp.concatenate([block, masks], axis=1),
                             jnp.concatenate([toks[:, :Bd], block], axis=1)),
                   jnp.where(moved, UNFIXED, block_at),
                   jnp.where(done, 0, step + 1),
                   # (a live slot's forward stored what was pending)
                   jnp.where(live, done, pending))
            starts = jnp.where(done, starts + Bd, starts)
        counts = counts + jnp.stack([
            jnp.sum(live), jnp.sum(live & ~has_mask),
            jnp.sum(masked & live[:, None]), jnp.sum(fix & live[:, None]),
            jnp.sum(jnp.where(live, beyond, 0)), jnp.sum(store),
            jnp.sum(walked), jnp.sum(store & has_mask),
        ]).astype(jnp.int32)
        out = jnp.concatenate(
            [block, block_at, done[:, None].astype(jnp.int32)], axis=-1)
        stats = [a + b for a, b in zip(stats, layer_stats)]
        return (new, starts, kp, vp, *stats, counts), out

    carry0 = (window, starts, k_pages, v_pages,
              *([jnp.zeros((cfg.moe.stats_size,), jnp.int32)]
                if cfg.is_moe else []),
              jnp.zeros((len(DENOISE_COUNTS),), jnp.int32))
    return jax.lax.scan(one, carry0, None, length=num_steps)
