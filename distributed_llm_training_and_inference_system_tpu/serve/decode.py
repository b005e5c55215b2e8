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

from typing import Any

import jax
import jax.numpy as jnp

from ..config.schema import ModelConfig
from ..models.gpt import (
    cast_table_blocks,
    layer_experts,
    split_expert_stacks,
    table_layer,
    table_layers,
    unembed,
)
from ..models.layers import decoder_block, model_rope_frequencies
from ..ops.paged_attention import (
    paged_attention_multi,
    write_window_to_pages,
)
from ..ops.quantization import cast_params, precast_params


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
) -> tuple:
    """Returns (logits [B, V] fp32, new k_pages, new v_pages) and, asked
    with ``return_moe_stats``, the live slots' expert choices, and given
    ``ssm_state`` the advanced state pools (see ``extend_step_forward``).

    The T=1 case of ``extend_step_forward`` (one layer-body implementation
    for both, so the paths can never diverge numerically). The new token's
    K/V are written into the pages *inside* the traced function. The
    returned pools ARE the argument buffers when the jit wrapper donates
    them (the engine does): the layer loop carries them and writes by
    layer index, and the compiled program holds no pool-sized temporary
    (tests/test_tpu_compile.py::test_decode_program_updates_pool_in_place).
    """
    write_ok = None if active is None else active[:, None]
    logits, *rest = extend_step_forward(
        params, tokens[:, None], positions, k_pages, v_pages, block_tables,
        cfg, write_ok=write_ok, attn_impl=attn_impl,
        w4_kernel_ok=w4_kernel_ok, w8_kernel_ok=w8_kernel_ok,
        return_moe_stats=return_moe_stats, ssm_state=ssm_state)
    return (logits[:, 0], *rest)


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
                              # P, N]}: the state-space layers' pools
) -> tuple:
    """Paged forward over T tokens per slot: the multi-token sibling of
    ``decode_step_forward``. Returns (logits [B, T, V] fp32, k_pages,
    v_pages) and, asked with ``return_moe_stats`` (MoE models), a fourth:
    the [E + 1] int32 vector of the LIVE tokens' choices per expert summed
    over the layers (``write_ok`` rows; idle slots and padding get no
    expert) and, last, the (layer, expert) pairs that got any. A model
    with state-space layers takes ``ssm_state`` and returns it LAST,
    advanced in place for the rows ``write_ok`` marks: its K/V pools hold
    the attention layers alone ([La, NP, ...]) and it takes T = 1 only.
    A model with LATENT attention keeps ONE pool: ``k_pages`` is the latent
    pool [La, NP, 1, PS, W] and ``v_pages`` is None, handed through.

    Token j sits at position ``start_positions + j`` and attends causally
    over the paged prefix *including* earlier tokens of this same call: all
    T tokens' K/V are written into the pages first, then attention runs
    with per-query length ``start + j + 1``. The pools are the layer
    loop's carry, written and read at ``pages[layer, page]``: nothing
    pool-sized is sliced out per layer or stacked back. This one
    primitive powers both speculative-decode verification
    (serve/speculative.py: score K draft tokens in one weight-streaming
    pass — decode is HBM-bound on weights, so T<=8 tokens cost nearly
    the same as 1) and cached-prefix suffix
    prefill (only the un-cached tail of a prompt is computed).

    Attention goes through ops.paged_attention_multi: on TPU the
    head-folded Pallas kernel streams each page ONCE PER SLOT (all kv
    heads, all T queries); elsewhere a flattened [B*T]-row fallback of
    the single-token path (correct, but re-streams the prefix T-fold).
    """
    compute_dtype = jnp.dtype(cfg.dtype)
    T = tokens.shape[1]
    positions = start_positions[:, None] + jnp.arange(T, dtype=jnp.int32)

    x = params["embed"]["embedding"][tokens].astype(compute_dtype)  # [B,T,H]
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

    def attend_pages(kp, vp, li):
        """``attend`` over the page pools, written and read at layer
        ``li``."""
        def attend(q, k, v):
            # K and V live in pages. Every T takes the whole-page merge
            # (T == 1: one page a slot), QuantPages and Int4Pages with
            # quantize-on-write fused into it: a row scatter lays the pool
            # out slot-major, the Pallas kernel reads it head-major, and
            # the WHOLE pool is copied between the two in every layer
            # (PERF.md 6, PR 26, has both step times)
            with jax.named_scope("kv_page_write"):
                new_k = write_window_to_pages(kp, k, block_tables,
                                              start_positions, write_ok, li)
                new_v = write_window_to_pages(vp, v, block_tables,
                                              start_positions, write_ok, li)
            out = paged_attention_multi(q, new_k, new_v, block_tables,
                                        start_positions, impl=attn_impl,
                                        layer=li)
            return out, (new_k, new_v)
        return attend

    if cfg.layer_pattern:
        # a layer table: one parameter stack a kind, walked by a Python
        # loop; every pool (pages, conv tails, states) and every expert
        # stack stays whole and is addressed at its kind's layer index
        from ..ops.ssm import recur_step
        if cfg.is_recurrent and ssm_state is None:
            raise ValueError("a model with state-space layers needs its "
                             "ssm_state pools")
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
                    attn_impl)
            return attend_pages(kp, vp, li)
        conv, ssm = (ssm_state["conv"], ssm_state["ssm"]) \
            if ssm_state is not None else (None, None)
        stats = jnp.zeros((cfg.moe.stats_size,), jnp.int32)
        for kind, i in table_layers(cfg):
            x, state, layer_stats = decoder_block(
                x, table_layer(blocks, kind, i), cfg, positions, inv_freq,
                attend_at(kp, vp, i) if kind == "*" else None, matmul=mm,
                live=write_ok, layer_index=i, kind=kind,
                recur=(recur_step(cfg, conv, ssm, i, write_ok)
                       if kind == "M" else None))
            if kind == "*":
                kp, vp = state
            elif kind == "M":
                conv, ssm = state
            elif kind == "E":
                stats = stats + layer_stats
        if cfg.hc_mult > 1:
            x = jnp.sum(x.astype(jnp.float32), axis=2).astype(compute_dtype)
        return (unembed(params, x, cfg), kp, vp,
                *([stats] if return_moe_stats else []),
                *([{"conv": conv, "ssm": ssm}] if ssm_state is not None
                  else []))

    def body(carry, layer_and_index):
        # the pools must stay a CARRY that every layer writes and reads
        # by its index: as scanned inputs and stacked outputs XLA slices
        # each layer's slab out (94 MB at mistral-7b, 715 pages), writes
        # it back, and copies the step's fresh pool whole
        x, kp, vp, *stats = carry
        layer, li = layer_and_index
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
            layer = dict(layer, moe=cast_params(layer["moe"], compute_dtype))
            layer, moe_li = layer_experts(layer, expert_stacks, li)

        x, (kp, vp), layer_stats = decoder_block(
            x, layer, cfg, positions, inv_freq, attend_pages(kp, vp, li),
            matmul=mm, live=write_ok, layer_index=moe_li)
        stats = [total + layer_stats for total in stats]
        return (x, kp, vp, *stats), None

    stats0 = ([jnp.zeros((cfg.moe.stats_size,), jnp.int32)]
              if return_moe_stats else [])
    (x, new_k, new_v, *stats), _ = jax.lax.scan(
        body, (x, k_pages, v_pages, *stats0),
        (blocks, jnp.arange(cfg.num_layers, dtype=jnp.int32)))

    return (unembed(params, x, cfg), new_k, new_v, *stats)


def attend_latent_pages(cfg: ModelConfig, pool: jax.Array, li,
                        block_tables: jax.Array, start_positions: jax.Array,
                        write_ok: Any = None, attn_impl: str = "auto"):
    """The latent ``attend`` (``layers.latent_attention_mixer``) over the ONE
    latent pool ``pool`` [La, NP, 1, PS, W], written and read at layer
    ``li``: the window's rows go in by the whole-page merge every window
    takes, then every head's absorbed query walks the slot's live pages
    once. The state it returns is (the pool, None): there is no second
    pool."""
    from ..ops.mla_paged_attention import mla_paged_attention
    pad = pool.shape[-1] - cfg.mla.latent_size

    def attend(q_lat, rows, scale):
        with jax.named_scope("mla_page_write"):
            rows = jnp.pad(rows, ((0, 0), (0, 0), (0, pad)))
            new = write_window_to_pages(
                pool, rows[:, :, None, :], block_tables, start_positions,
                write_ok, li)
        out = mla_paged_attention(
            jnp.pad(q_lat, ((0, 0), (0, 0), (0, 0), (0, pad))), new,
            block_tables, start_positions, scale=scale,
            value_width=cfg.mla.kv_lora_rank, impl=attn_impl, layer=li)
        return out, (new, None)
    attend.latent = True
    return attend


def decode_multi_step(
    params: Any,
    tokens: jax.Array,          # [B] int32 — newest token per slot
    positions: jax.Array,       # [B] int32 — its position
    k_pages: jax.Array,         # [L, NP, Nkv, PS, D]
    v_pages: jax.Array,
    block_tables: jax.Array,    # [B, maxP]
    stop_positions: jax.Array,  # [B] — first position a slot must NOT write
    slot_keys: jax.Array,       # [B, 2] uint32 PRNG key data
    temperature: jax.Array,     # [B]
    top_k: jax.Array,           # [B]
    top_p: jax.Array,           # [B]
    cfg: ModelConfig,
    num_steps: int,
    attn_impl: str = "auto",
    w4_kernel_ok: bool = True,
    w8_kernel_ok: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Run ``num_steps`` decode+sample iterations in ONE compiled program.

    The host-driven single-step loop costs one host<->device round trip per
    generated token: dispatch + sync per token, next to a few ms of decode
    compute (the round trip is not re-measured on a directly attached
    chip). Scanning K steps on device amortises that Kx
    (vLLM-style multi-step scheduling, TPU-shaped: the scan is one XLA
    program, sampling included).

    Per-slot stop handling: rows at/past ``stop_positions`` redirect KV
    writes to scratch page 0 and re-emit their previous token. Slots that
    hit EOS mid-scan keep decoding into their (reserved) pages; the host
    trims trailing tokens — at most ``num_steps - 1`` wasted iterations per
    finished request. Sampling folds the per-slot key by position exactly
    like the single-step path, so generations are bit-identical to
    ``num_steps=1``.

    Returns ([K, B] sampled tokens, new k_pages, new v_pages).
    """
    (_, _, k_pages, v_pages), toks_seq = decode_scan(
        params, tokens, positions, k_pages, v_pages, block_tables,
        stop_positions, slot_keys, temperature, top_k, top_p, cfg,
        num_steps, attn_impl, w4_kernel_ok, w8_kernel_ok)
    return toks_seq, k_pages, v_pages


def decode_scan(params, tokens, positions, k_pages, v_pages, block_tables,
                stop_positions, slot_keys, temperature, top_k, top_p,
                cfg: ModelConfig, num_steps: int, attn_impl: str = "auto",
                w4_kernel_ok: bool = True, w8_kernel_ok: bool = False,
                return_moe_stats: bool = False, ssm_state: Any = None):
    """The decode+sample scan shared by ``decode_multi_step`` and the fused
    speculative dispatch (speculative.verify_and_decode). Returns
    ((tokens, positions, k_pages, v_pages), toks_seq [K, B]); with
    ``return_moe_stats`` (MoE models) the carry ends in the steps' summed
    ``moe_stats`` (see ``extend_step_forward``), and given ``ssm_state``
    (a model with state-space layers) in the state pools after that."""
    from .sampling import sample_tokens
    return_moe_stats = return_moe_stats and cfg.is_moe
    n_stats = int(return_moe_stats)

    def one(carry, _):
        toks, pos, kp, vp, *rest = carry
        stats, state = rest[:n_stats], rest[n_stats:]
        act = pos < stop_positions
        logits, kp, vp, *out = decode_step_forward(
            params, toks, pos, kp, vp, block_tables, cfg, active=act,
            attn_impl=attn_impl, w4_kernel_ok=w4_kernel_ok,
            w8_kernel_ok=w8_kernel_ok, return_moe_stats=return_moe_stats,
            ssm_state=state[0] if state else None)
        keys = jax.vmap(jax.random.fold_in)(
            jax.vmap(jax.random.wrap_key_data)(slot_keys), pos + 1)
        nxt = sample_tokens(logits, keys, temperature, top_k, top_p)
        nxt = jnp.where(act, nxt, toks)
        stats = [a + b for a, b in zip(stats, out[:n_stats])]
        return (nxt, pos + 1, kp, vp, *stats, *out[n_stats:]), nxt

    stats0 = ([jnp.zeros((cfg.moe.stats_size,), jnp.int32)]
              if return_moe_stats else [])
    state0 = [] if ssm_state is None else [ssm_state]
    return jax.lax.scan(
        one, (tokens, positions, k_pages, v_pages, *stats0, *state0),
        None, length=num_steps)
