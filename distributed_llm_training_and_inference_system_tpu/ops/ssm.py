"""Mamba-2 state-space mixer: the depthwise conv, the scan and the gated norm.

A Mamba-2 head ``i`` keeps a state ``h[i]`` [P, N] (P channels of the head,
N the state size) and moves it one token at a time:

    h_t[i] = exp(dt_t[i] A[i]) h_{t-1}[i] + dt_t[i] outer(x_t[i], B_t[g(i)])
    y_t[i] = h_t[i] C_t[g(i)] + D[i] x_t[i]

with ``dt_t = softplus(raw + dt_bias)``, ``A = -exp(A_log)`` and B, C shared
by the heads of a group ``g(i)``. Two forms of the same recurrence:

- ``ssm_scan_prefill``: the chunked ("state-space duality") form for a
  window of tokens: inside a chunk of ``Q`` tokens the outputs are matmuls
  (C B^T masked by the decays, times x), between chunks a [P, N] state is
  carried. A position with ``dt = 0`` decays by 1 and adds nothing: that
  is how a bucket's padding is kept out of the state.
- ``ssm_decode``: the one-step update over ``[slots]``, elementwise in
  float32; the step reads and writes every live slot's state once
  (``ssm_decode_pool``: the same step as one Pallas kernel over the state
  pool in place, the chip's form, which visits the live slots alone).

``recur_window`` / ``recur_step`` / ``recur_chunk`` say where the state
lives, as ``attend`` does for K and V (models/layers.py ``decoder_block``):
from zeros over a window (training-free forward, cold prefill), in the
engine's state pools ``[state-space layer, slot, ...]`` (decode), or, for a
window of ONE slot behind tokens it has run already (the piece a decode
step carries, a chunk of a prompt), from that slot's own conv tail and
state (``slot_state`` reads them, ``write_slot_state`` puts them back).

The scopes (``ssm_conv``, ``ssm_scan_prefill``, ``ssm_decode``,
``ssm_gated_norm``) are what a device trace names these operations by.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..utils.platform import report_impl


def ssm_conv(xbc: jax.Array, kernel: jax.Array, bias: jax.Array,
             tail: Optional[jax.Array] = None
             ) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal conv of width K over xbc [B, S, C], then silu.

    ``kernel`` [K, C], ``bias`` [C]; ``tail`` [B, K-1, C] holds the K-1
    PRE-activation columns before the window (None: zeros, a sequence's
    start). Returns (activated [B, S, C], padded [B, K-1+S, C]: tail and
    window, from which the caller cuts the next tail)."""
    B, S, C = xbc.shape
    K = kernel.shape[0]
    if tail is None:
        tail = jnp.zeros((B, K - 1, C), xbc.dtype)
    with jax.named_scope("ssm_conv"):
        padded = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
        acc = bias.astype(jnp.float32)
        for j in range(K):
            acc = acc + (padded[:, j:j + S].astype(jnp.float32)
                         * kernel[j].astype(jnp.float32))
        return jax.nn.silu(acc).astype(xbc.dtype), padded


def ssm_scan_prefill(x: jax.Array, dt: jax.Array, A: jax.Array,
                     Bm: jax.Array, Cm: jax.Array, D: jax.Array,
                     chunk: int, h0: Optional[jax.Array] = None
                     ) -> tuple[jax.Array, jax.Array]:
    """The chunked scan, from the state ``h0`` [B, nh, P, N] (float32; the
    state before the window's first token) or, given none, from ZERO.

    x [B, S, nh, P] and Bm, Cm [B, S, G, N] in the compute dtype; dt
    [B, S, nh] float32, already softplus'd and 0 at positions that must
    not enter the state; A (negative) and D [nh] float32. Returns
    (y [B, S, nh, P] in x's dtype, the state after the window
    [B, nh, P, N] float32). Matmul operands are the compute dtype with
    float32 accumulation; decays and the carried state are float32."""
    B, S, nh, P = x.shape
    G, N = Bm.shape[-2:]
    r = nh // G                                  # heads a group
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        x, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                     for a in (x, Bm, Cm))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    nc = (S + pad) // Q
    report_impl("ssm_scan_prefill", "xla",
                f"x{tuple(x.shape)} chunks {nc}x{Q}")
    f32 = jnp.float32
    with jax.named_scope("ssm_scan_prefill"):
        a = (dt * A).reshape(B, nc, Q, G, r)                 # log decays <= 0
        cum = jnp.cumsum(a, axis=2)                          # inclusive
        xdt = (x.astype(f32) * dt[..., None]).astype(x.dtype).reshape(
            B, nc, Q, G, r, P)
        Bc = Bm.reshape(B, nc, Q, G, N)
        Cc = Cm.reshape(B, nc, Q, G, N)
        # inside a chunk: y_t += sum_{s<=t} exp(cum_t - cum_s) (C_t.B_s) xdt_s
        cb = jnp.einsum("bcqgn,bckgn->bcgqk", Cc, Bc,
                        preferred_element_type=f32)          # [B,nc,G,Q,Q]
        seg = cum[:, :, :, None] - cum[:, :, None]           # [B,nc,Q,Q,G,r]
        causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
        decay = jnp.exp(jnp.where(causal[:, :, None, None], seg, -jnp.inf))
        m = cb.transpose(0, 1, 3, 4, 2)[..., None] * decay   # [B,nc,Q,Q,G,r]
        y = jnp.einsum("bcqkgr,bckgrp->bcqgrp", m.astype(x.dtype), xdt,
                       preferred_element_type=f32)
        # each chunk's own contribution to the state at its end
        to_end = jnp.exp(cum[:, :, -1:] - cum)               # [B,nc,Q,G,r]
        chunk_state = jnp.einsum(
            "bcqgrp,bcqgn->bcgrpn",
            (xdt.astype(f32) * to_end[..., None]).astype(x.dtype), Bc,
            preferred_element_type=f32)                      # [B,nc,G,r,P,N]
        chunk_decay = jnp.exp(cum[:, :, -1])                 # [B,nc,G,r]

        def carry(h, c):
            state_c, decay_c = c
            return h * decay_c[..., None, None] + state_c, h

        h_last, h_in = jax.lax.scan(
            carry, (jnp.zeros((B, G, r, P, N), f32) if h0 is None
                    else h0.astype(f32).reshape(B, G, r, P, N)),
            (chunk_state.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)))
        h_in = h_in.swapaxes(0, 1)                           # [B,nc,G,r,P,N]
        # what the state carried into the chunk adds: exp(cum_t) C_t . h_in
        y = y + jnp.einsum("bcqgn,bcgrpn->bcqgrp", Cc, h_in.astype(x.dtype),
                           preferred_element_type=f32) \
            * jnp.exp(cum)[..., None]
        y = y.reshape(B, S + pad, nh, P)[:, :S]
        y = y + D[:, None] * x[:, :S].astype(f32)
        return y.astype(x.dtype), h_last.reshape(B, nh, P, N)


def ssm_decode(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
               Cm: jax.Array, D: jax.Array, h: jax.Array
               ) -> tuple[jax.Array, jax.Array]:
    """One token a slot: x [S, nh, P], dt [S, nh] float32, Bm, Cm
    [S, G, N], h [S, nh, P, N] (the cached dtype). Returns (y [S, nh, P]
    in x's dtype, the new state in h's dtype). Float32 throughout."""
    S, nh, P = x.shape
    G, N = Bm.shape[-2:]
    r = nh // G
    f32 = jnp.float32
    report_impl("ssm_decode", "xla", f"h{tuple(h.shape)} {h.dtype}")
    with jax.named_scope("ssm_decode"):
        xf = x.astype(f32).reshape(S, G, r, P)
        dtg = dt.reshape(S, G, r)
        hg = h.astype(f32).reshape(S, G, r, P, N)
        decay = jnp.exp(dtg * A.reshape(G, r))
        new = (hg * decay[..., None, None]
               + (dtg[..., None] * xf)[..., None]
               * Bm.astype(f32)[:, :, None, None, :])
        y = jnp.sum(new * Cm.astype(f32)[:, :, None, None, :], axis=-1) \
            + D.reshape(G, r)[..., None] * xf
        return (y.reshape(S, nh, P).astype(x.dtype),
                new.reshape(S, nh, P, N).astype(h.dtype))


_BLOCK_BYTES = 1 << 20      # of state a grid step holds (in + out, double-
                            # buffered: 4 of the 16 MB of scoped VMEM)
_ROW = 128                  # (head, channel) pairs a row of the step's tiles


def _heads_a_block(nh: int, P: int, N: int, G: int) -> int:
    """Heads of one slot a grid step of ``ssm_decode_pool``: the most whose
    state [hb, P, N] float32 is at most ``_BLOCK_BYTES``, that divide the
    heads, that lie inside one B/C group or hold whole ones, and whose
    (head, channel) pairs are whole rows of 128 a unit of heads sharing a
    group (8 heads of [128, 256] at the parallel cell's shapes, inside one
    group of 16; 32 heads of [64, 128] = 4 groups at the hybrid cell's).
    0: the kernel does not take these shapes (``step_pools`` then keeps
    the XLA form)."""
    if N % 128 or P % 8 or nh % G:
        return 0
    r = nh // G
    for hb in range(min(max(_BLOCK_BYTES // (P * N * 4), 1), nh), 0, -1):
        if (nh % hb == 0 and (hb % r == 0 or r % hb == 0)
                and (min(hb, r) * P) % _ROW == 0
                and (P % _ROW == 0 or _ROW % P == 0)
                and hb * P // _ROW <= _ROW):
            return hb
    return 0


def _decode_pool_kernel(layer_ref, ids_ref, count_ref, d_ref, x_ref, b_ref,
                        c_ref, s_ref, y_ref, new_ref):
    """One grid step: a block of ``hb`` heads of one LIVE slot. The state
    block [hb, P, N] is walked as rows of 128 (head, channel) pairs, a tile
    [128, N] each: read once, ``new = h decay + (dt x) B`` written once and
    ``y = sum_N(new C)`` taken from the same tile. ``d_ref`` [hb, N] holds a
    head's decay along the lanes, ``b_ref`` / ``c_ref`` [units, N] the B
    and C of each unit of heads that share a group; ``x_ref`` [R, 128]
    holds ``dt x`` a row and is turned into columns by ONE transpose of
    its padded [128, 128] tile. The sum over ``N`` lies on the lanes: the
    lane tiles of ``new C`` are added, the [128, 128] that is left is
    transposed and summed over its sublanes, which leaves the row's pairs
    on the lanes as ``y_ref`` holds them. (Alone on the chip this form,
    a cross-lane reduce a vreg row with the columns put back by one more
    transpose, the MXU against C, and ``dt x`` by a transpose a row all
    read within 1 % at the parallel pool, 76-77 % of the HBM peak, and
    within 4 % at the hybrid pool, where this one and the MXU's lead:
    the stream decides, not the reduce; PERF.md 6, PR 50.) A grid step
    past the live slots' count skips all of it: its blocks are the last
    live step's (nothing is fetched), and what that step wrote is what the
    pipeline writes back."""
    from jax.experimental import pallas as pl
    i, h = pl.program_id(0), pl.program_id(1)
    hb, P, N = s_ref.shape
    R, units = x_ref.shape[0], b_ref.shape[0]
    f32 = jnp.float32

    @pl.when(i < count_ref[0])
    def _live():
        xt = x_ref[...]
        if R < _ROW:
            xt = jnp.concatenate([xt, jnp.zeros((_ROW - R, _ROW), f32)], 0)
        xt = xt.T                                   # [pair of a row, row]
        rows = []
        for j in range(R):
            u = j * units // R
            if P >= _ROW:       # a row is a slice of one head's channels
                head, c0 = j * _ROW // P, j * _ROW % P
                at = (head, slice(c0, c0 + _ROW))
                decay = d_ref[head:head + 1, :]
            else:               # a row is a few whole heads
                at = slice(j * _ROW // P, (j + 1) * _ROW // P)
                decay = jnp.broadcast_to(d_ref[at][:, None, :],
                                         (_ROW // P, P, N)).reshape(_ROW, N)
            tile = s_ref[at]
            new = (tile.reshape(_ROW, N) * decay
                   + xt[:, j:j + 1] * b_ref[u:u + 1, :])
            new_ref[at] = new.reshape(tile.shape)
            weighed = new * c_ref[u:u + 1, :]
            fold = weighed[:, :_ROW]
            for t in range(_ROW, N, _ROW):
                fold = fold + weighed[:, t:t + _ROW]
            rows.append(jnp.sum(fold.T, axis=0, keepdims=True))
        y_ref[...] = jnp.concatenate(rows, axis=0)

    # no live slot at all: the pipeline still writes the one block it
    # holds back; hand it what it fetched
    @pl.when((count_ref[0] == 0) & (i == 0) & (h == 0))
    def _nobody():
        new_ref[...] = s_ref[...]


def ssm_decode_pool(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                    Cm: jax.Array, D: jax.Array, pool: jax.Array, layer,
                    write_ok: Optional[jax.Array] = None,
                    interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """``ssm_decode`` as ONE Pallas kernel over ``pool[layer]`` in place
    (``pool`` [Lm, slots, nh, P, N] float32, aliased to the output), for
    the slots ``write_ok`` [slots] marks (None: all): a live slot's state
    is read once and written once, and ``y`` is summed from the tile that
    is written (the XLA form at a state of 256 read the new state again for
    ``y``, and both its passes ran over every slot: 9.3 ms a step of the
    parallel cell against a floor of 3.9; PERF.md 6, PR 50). A slot that is
    not marked costs no bytes: the live slots' ids and their count reach
    the block specs as prefetched scalars, the grid walks the live ids
    first, and a step past the count names the block the last live step
    left in VMEM and skips its body. Such a slot's state stays bit for bit
    and its ``y`` is 0. ``layer`` may be traced (an argument of the jitted
    ``step_pools`` every layer of a riding program calls). x [slots, nh,
    P], dt [slots, nh] float32, Bm, Cm [slots, G, N], A, D [nh] float32.
    Returns (y [slots, nh, P] in x's dtype, the pool). Float32
    throughout, as ``ssm_decode``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, nh, P = x.shape
    G, N = Bm.shape[-2:]
    hb = _heads_a_block(nh, P, N, G)
    if not hb or pool.dtype != jnp.float32:
        raise ValueError(f"ssm_decode_pool: {nh} heads of [{P}, {N}] in {G} "
                         f"groups, state {pool.dtype}")
    r = nh // G
    unit = min(hb, r)           # heads of a block that share B and C
    nhb, units, R = nh // hb, hb // unit, hb * P // _ROW
    f32 = jnp.float32
    report_impl("ssm_decode", "pallas-interpret" if interpret else "pallas",
                f"h{tuple(pool.shape)} {hb} heads a block")

    def block_of(i, h, ly, ids, count):
        return ids[i], jnp.where(i < count[0], h, nhb - 1)
    small = lambda *block: pl.BlockSpec(
        (None, None, *block), lambda *at: (*block_of(*at), 0, 0))
    state = pl.BlockSpec((None, None, hb, P, N),
                         lambda *at: (at[2][0], *block_of(*at), 0, 0))
    with jax.named_scope("ssm_decode"):
        xf = x.astype(f32)
        ok = (jnp.ones((S,), bool) if write_ok is None
              else write_ok.reshape(S))
        # the live slots' ids first, in order; past their count the last
        # live one again (0 where nobody is live)
        rank = jnp.cumsum(ok, dtype=jnp.int32) - 1
        count = rank[-1] + 1
        slots = jnp.arange(S, dtype=jnp.int32)
        ids = jnp.sum(jnp.where(
            ok & (rank == jnp.minimum(slots, count - 1)[:, None]), slots, 0),
            axis=1, dtype=jnp.int32)
        decay = jnp.broadcast_to(jnp.exp(dt * A)[..., None], (S, nh, N))
        by_unit = lambda m: jnp.repeat(m.astype(f32), r // unit, axis=1
                                       ).reshape(S, nhb, units, N)
        y, pool = pl.pallas_call(
            _decode_pool_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,      # the layer, live ids, their count
                grid=(S, nhb),
                in_specs=[small(hb, N), small(R, _ROW), small(units, N),
                          small(units, N), state],
                out_specs=[small(R, _ROW), state]),
            out_shape=[jax.ShapeDtypeStruct((S, nhb, R, _ROW), f32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            input_output_aliases={7: 1},
            # in order: a step past the count leans on the one before it
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="ssm_decode",
        )(jnp.asarray(layer, jnp.int32).reshape(1), ids, count.reshape(1),
          decay.reshape(S, nhb, hb, N),
          (dt[..., None] * xf).reshape(S, nhb, R, _ROW),
          by_unit(Bm), by_unit(Cm), pool)
        y = jnp.where(ok[:, None, None],
                      y.reshape(S, nh, P) + D[:, None] * xf, 0.0)
        return y.astype(x.dtype), pool


def ssm_gated_norm(y: jax.Array, z: jax.Array, scale: jax.Array,
                   groups: int, eps: float) -> jax.Array:
    """``y * silu(z)``, THEN an RMS norm over each of ``groups`` equal
    channel groups, times ``1 + scale`` (the program's norm-weight
    convention). y, z [..., d_in]."""
    with jax.named_scope("ssm_gated_norm"):
        g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        gg = g.reshape(*g.shape[:-1], groups, g.shape[-1] // groups)
        gg = gg * jax.lax.rsqrt(
            jnp.mean(jnp.square(gg), axis=-1, keepdims=True) + eps)
        return (gg.reshape(g.shape)
                * (1.0 + scale.astype(jnp.float32))).astype(y.dtype)


# ---------------------------------------------------------------------------
# Where the state lives
# ---------------------------------------------------------------------------

def _split(xbc_act: jax.Array, s):
    """The activated conv output [.., C] as x [.., nh, P], B, C [.., G, N]
    (``s``: the model's ``SSMConfig``)."""
    d_in, gn = s.inner_size, s.n_groups * s.state_size
    lead = xbc_act.shape[:-1]
    return (xbc_act[..., :d_in].reshape(*lead, s.num_heads, s.head_dim),
            xbc_act[..., d_in:d_in + gn].reshape(*lead, s.n_groups,
                                                 s.state_size),
            xbc_act[..., d_in + gn:].reshape(*lead, s.n_groups,
                                             s.state_size))


def _step_sizes(dt_raw: jax.Array, p: dict) -> tuple[jax.Array, jax.Array,
                                                     jax.Array]:
    """(softplus(dt + dt_bias), A = -exp(A_log), D), float32."""
    f32 = jnp.float32
    return (jax.nn.softplus(dt_raw.astype(f32) + p["dt_bias"].astype(f32)),
            -jnp.exp(p["A_log"].astype(f32)), p["D"].astype(f32))


def recur_window(cfg, live: Optional[jax.Array] = None,
                 tail: Optional[jax.Array] = None,
                 h0: Optional[jax.Array] = None):
    """``recur`` for a window that starts a sequence (a forward with no
    cache, cold prefill): the conv from a zero tail, the chunked scan from
    a zero state; or, given them, from the conv ``tail`` [B, K-1, C] and
    the state ``h0`` [B, nh, P, N] before the window (``recur_chunk``).
    ``live`` [B, S] (bool, or segment ids with 0 = padding)
    marks the real tokens, which must be a PREFIX of each row: padding
    takes ``dt = 0`` and the state returned is the one after the last live
    token, (the K-1 pre-activation conv columns before position
    ``length`` [B, K-1, C], h [B, nh, P, N] float32)."""
    K = cfg.ssm.conv_kernel

    def recur(xbc, dt_raw, p):
        B, S, _ = xbc.shape
        act, padded = ssm_conv(xbc, p["conv"]["kernel"], p["conv"]["bias"],
                               tail)
        x, Bm, Cm = _split(act, cfg.ssm)
        dt, A, D = _step_sizes(dt_raw, p)
        if live is None:
            length = jnp.full((B,), S, jnp.int32)
        else:
            alive = live if live.dtype == jnp.bool_ else live != 0
            dt = jnp.where(alive[..., None], dt, 0.0)
            length = jnp.sum(alive, axis=1, dtype=jnp.int32)
        y, h = ssm_scan_prefill(x, dt, A, Bm, Cm, D, cfg.ssm.chunk_size, h0)
        # position p is padded[p + K - 1]: the columns length-K+1..length-1
        # (a window of fewer than K-1 live tokens keeps the tail's last)
        idx = length[:, None] + jnp.arange(K - 1, dtype=jnp.int32)
        new_tail = jnp.take_along_axis(padded, idx[..., None], axis=1)
        return y.reshape(B, S, -1), (new_tail, h)
    return recur


def step_pools(xbc: jax.Array, dt_raw: jax.Array, p: dict,
               conv_pool: jax.Array, ssm_pool: jax.Array, layer,
               write_ok: Optional[jax.Array], s
               ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One decode step of every slot over the state pools ``conv_pool``
    [Lm, slots, K-1, C] and ``ssm_pool`` [Lm, slots, nh, P, N], read and
    written at ``[layer]`` (an int, or traced): what ``recur_step``'s
    ``recur`` does, with everything it reads an argument (``s``: the
    ``SSMConfig``), so that a program's two step bodies and its ``M``
    layers can call ONE jitted form of it (serve/decode.py)."""
    B, T, _ = xbc.shape
    if T != 1:
        raise ValueError(
            "every slot advances one token over the state pools; a "
            f"window of {T} tokens a slot (speculative verification) is "
            "not supported: a prompt's window goes through recur_chunk, "
            "one slot at a time")
    tail = conv_pool[layer]
    act, padded = ssm_conv(xbc, p["conv"]["kernel"], p["conv"]["bias"], tail)
    x, Bm, Cm = _split(act[:, 0], s)
    dt, A, D = _step_sizes(dt_raw[:, 0], p)
    # the chip takes the kernel wherever its tiles fit the heads (both
    # cells' do; a test template's 16-wide state does not)
    kernel = (jax.default_backend() == "tpu"
              and ssm_pool.dtype == jnp.float32
              and _heads_a_block(s.num_heads, s.head_dim, s.state_size,
                                 s.n_groups) > 0)
    if kernel:
        y, new_pool = ssm_decode_pool(x, dt, A, Bm, Cm, D, ssm_pool, layer,
                                      write_ok)
    else:
        old = ssm_pool[layer]
        y, new = ssm_decode(x, dt, A, Bm, Cm, D, old)
    new_tail = padded[:, 1:].astype(conv_pool.dtype)
    # (under the update's scope: XLA fuses the update into the pool's
    # in-place write, and a trace names the fusion by its ROOT)
    with jax.named_scope("ssm_decode"):
        if write_ok is not None:
            ok = write_ok.reshape(B)
            if not kernel:
                new = jnp.where(ok[:, None, None, None], new, old)
            new_tail = jnp.where(ok[:, None, None], new_tail, tail)
        y = y.reshape(B, 1, -1)
        conv_pool = conv_pool.at[layer].set(new_tail)
        if not kernel:
            new_pool = ssm_pool.at[layer].set(new)
        return y, (conv_pool, new_pool)


def recur_step(cfg, conv_pool: jax.Array, ssm_pool: jax.Array, layer,
               write_ok: Optional[jax.Array] = None, step=step_pools):
    """``recur`` for one decode step of every slot over the state pools
    ``conv_pool`` [Lm, slots, K-1, C] and ``ssm_pool``
    [Lm, slots, nh, P, N], read and written at ``[layer]``. A slot with
    ``write_ok`` [slots, 1] False (idle, or past its stop position) leaves
    its state as it is. Returns the two pools as the state. (``step``: a
    jitted ``step_pools``, where a program calls it from many places.)"""
    def recur(xbc, dt_raw, p):
        return step(xbc, dt_raw, p, conv_pool, ssm_pool, layer, write_ok,
                    s=cfg.ssm)
    return recur


def slot_state(conv_pool: jax.Array, ssm_pool: jax.Array, slot: jax.Array,
               start: jax.Array) -> tuple[jax.Array, jax.Array]:
    """ONE slot's rows of the state pools in every ``M`` layer, read once
    before a window's layers run: (conv tails [Lm, K-1, C], states
    [Lm, nh, P, N] float32), taken as ZERO where the window starts its
    sequence (``start`` [1] == 0: whatever a former occupant of the slot
    left there is not read).

    The conv tails are the sum over the slots of the pool masked to the
    one slot: the same rows (every other term is 0). A SLICE of that pool
    hands the layout its consumer likes (the tail's 3 columns on the
    lanes) back to the whole pool inside the step loop: a copy of 14 MB
    into 600 MB of padding a step at the hybrid cell's shapes (compiled
    for a described v5e, PERF.md 6, PR 44; ``ops/kda.py slot_state`` found
    the same)."""
    fresh = start[0] == 0
    mine = (jnp.arange(conv_pool.shape[1]) == slot)[:, None, None]
    tails = jnp.sum(jnp.where(mine, conv_pool, 0), axis=1,
                    dtype=conv_pool.dtype)
    return (jnp.where(fresh, 0, tails),
            jnp.where(fresh, 0.0, ssm_pool[:, slot].astype(jnp.float32)))


def write_slot_state(conv_pool: jax.Array, ssm_pool: jax.Array,
                     slot: jax.Array, tails: jax.Array, states: jax.Array,
                     live) -> tuple[jax.Array, jax.Array]:
    """The pools with ``slot``'s rows of every ``M`` layer overwritten by a
    window's (conv tails [Lm, K-1, C], states [Lm, nh, P, N]): ONE write a
    pool, after the window's last layer. ``live`` (bool []) False keeps
    the rows as the pools hold them (a decode step that carries no piece
    names slot 0). The conv tails go in by a select over the whole pool
    (2 x 14 MB at the hybrid cell's shapes), for ``slot_state``'s
    reason."""
    mine = (jnp.arange(conv_pool.shape[1]) == slot)[:, None, None] & live
    conv_pool = jnp.where(mine, tails.astype(conv_pool.dtype)[:, None],
                          conv_pool)
    states = jnp.where(live, states.astype(ssm_pool.dtype),
                       ssm_pool[:, slot])
    return conv_pool, ssm_pool.at[:, slot].set(states)


def arm_slot_state(conv_pool: jax.Array, ssm_pool: jax.Array,
                   slot: jax.Array, tails: jax.Array, states: jax.Array
                   ) -> tuple[jax.Array, jax.Array]:
    """The pools with ``slot``'s rows of every ``M`` layer SET to a cold
    prefill's (conv tails [Lm, 1, K-1, C], states [Lm, 1, nh, P, N], from a
    zero state), whatever a former occupant left there. A set at the slot,
    where ``write_slot_state`` selects over the whole pool: a cold program
    carries the pools through no loop."""
    return (conv_pool.at[:, slot].set(tails[:, 0].astype(conv_pool.dtype)),
            ssm_pool.at[:, slot].set(states[:, 0].astype(ssm_pool.dtype)))


def recur_chunk(cfg, tail: jax.Array, h0: jax.Array,
                live: Optional[jax.Array] = None):
    """``recur`` for a window of ONE slot's prompt behind tokens the slot
    has run already (the piece a decode step carries, a chunk of a prompt:
    the window is [1, T]): the conv from the slot's cached ``tail``
    [K-1, C] and the chunked scan from the slot's cached state ``h0``
    [nh, P, N] (``slot_state``'s rows of this layer). The state it returns
    is (the conv tail [K-1, C], the state [nh, P, N] float32) after the
    window's last live token (``live`` [1, T], a prefix; fewer than K-1
    live tokens keep the last of ``tail``), which the caller writes back
    (``write_slot_state``)."""
    window = recur_window(cfg, live, tail[None], h0[None])

    def recur(xbc, dt_raw, p):
        if xbc.shape[0] != 1:
            raise ValueError("a chunk is one slot's window: [1, T]")
        y, (new_tail, h) = window(xbc, dt_raw, p)
        return y, (new_tail[0], h[0])
    return recur
