"""Kimi Delta Attention: the short conv, the gated delta rule and the head norm.

A head keeps a state ``S`` [dk, dv] in float32 and moves it one token at a
time by the gated delta rule, with one decay a CHANNEL of the key
(``alpha_t = exp(g_t)`` in (0, 1)^dk; Mamba-2 and the gated delta net have
one a head):

    S'  = Diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T          o_t = S_t^T q_t

``ops/ssm.py`` is a diagonal recurrence (decay and add); this one subtracts
what the state already holds for ``k_t``, a rank-1 correction. Two forms of
the same recurrence, as ``ops/ssm.py`` has them:

- ``kda_chunk_prefill``: the chunked (WY / UT-transform) form over a window,
  state in, state out. With ``G`` the cumulative log decays inside a chunk
  and ``u_t = beta_t (v_t - S'^T_t k_t)`` the rows the delta rule writes,

      (I + A) U = beta V - (beta K exp(G)) S_0,
      A[t, s]   = beta_t sum_d k_t[d] k_s[d] exp(G_t[d] - G_s[d])   (s < t)

  is ONE unit-lower-triangular solve of ``chunk`` x ``chunk`` a head (solved
  once against ``[beta V | beta K exp(G)]``: it is linear in ``S_0``;
  ``unit_lower_inverse``), and
  between chunks ``S`` is carried. The pair decays are formed as
  ``exp(G_t - G_s)`` with ``s <= t``: never the exponential of a positive
  number, so no strength of decay overflows (dividing by ``exp(G_s)`` would).
  Inside a sub-block of 16 rows that is one exponential a pair a channel,
  on the vector unit; for ``s`` in an earlier sub-block than ``t`` it is
  the product ``exp(G_t - G_r) exp(G_r - G_s)`` through the first row ``r``
  of ``t``'s sub-block, ``s < r <= t``, so both exponents are <= 0 too (a
  factor that underflows to 0 stands for a product that is smaller still),
  and the sum over the channels is a float32 matmul (``pair_products``).
  A position with ``beta = 0`` and ``g = 0`` leaves the state as it was:
  that is how a bucket's padding is kept out of it.
- ``kda_decode``: the one-step update over ``[slots]``, elementwise in
  float32.

``recur_window`` / ``recur_step`` / ``recur_chunk`` say where the state
lives, as ``attend`` does for K and V (models/layers.py ``decoder_block``):
from zeros over a window (a forward with no cache, cold prefill), in the
engine's state pools ``[K layer, slot, ...]`` for one token of every slot
(decode), or in ONE slot's rows of those pools for a window of that slot
(chunked prefill, and a prompt's piece that rides a decode step beside the
slots' one token each: the window reads the state and the conv window the
one before it left, and writes its own).

The scopes (``kda_conv``, ``kda_chunk_prefill``, ``kda_decode``,
``kda_gated_norm``) are what a device trace names these operations by.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..utils.platform import report_impl

L2_EPS = 1e-6       # q and k are divided by sqrt(sum of squares + this)
# tokens a chunk of the prefill form: one 64 x 64 unit-lower solve a head,
# the state carried between chunks (the value does not change the result)
CHUNK = 64


def kda_conv(x: jax.Array, kernel: jax.Array,
             tail: Optional[jax.Array] = None
             ) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal conv of width K over x [B, S, C] (q, k and v side
    by side), no bias, then silu. ``kernel`` [K, C]; ``tail`` [B, K-1, C]
    holds the K-1 PRE-activation columns before the window (None: zeros, a
    sequence's start). Returns (activated [B, S, C], padded [B, K-1+S, C]:
    tail and window, from which the caller cuts the next tail)."""
    B, S, C = x.shape
    K = kernel.shape[0]
    if tail is None:
        tail = jnp.zeros((B, K - 1, C), x.dtype)
    with jax.named_scope("kda_conv"):
        padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
        acc = jnp.zeros((), jnp.float32)
        for j in range(K):
            acc = acc + (padded[:, j:j + S].astype(jnp.float32)
                         * kernel[j].astype(jnp.float32))
        return jax.nn.silu(acc).astype(x.dtype), padded


def kda_conv_step(x: jax.Array, kernel: jax.Array, tail: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
    """``kda_conv`` for ONE token of every slot over the pool's layout: x
    [B, C], ``tail`` [K-1, B, C] (the window's columns lead, so that a
    layer's slab is whole (8, 128) tiles of [slots, C]: a [slots, K-1, C]
    slab pads its 3 columns to 16 sublanes, five times the bytes a step).
    Returns (activated [B, C], the next tail [K-1, B, C])."""
    K = kernel.shape[0]
    with jax.named_scope("kda_conv"):
        acc = x.astype(jnp.float32) * kernel[K - 1].astype(jnp.float32)
        for j in range(K - 1):
            acc = acc + (tail[j].astype(jnp.float32)
                         * kernel[j].astype(jnp.float32))
        return (jax.nn.silu(acc).astype(x.dtype),
                jnp.concatenate([tail[1:], x[None].astype(tail.dtype)]))


def l2norm(x: jax.Array) -> jax.Array:
    """x / sqrt(sum(x^2) + ``L2_EPS``) over the last axis, float32."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(
        jnp.sum(jnp.square(xf), axis=-1, keepdims=True) + L2_EPS)


# rows a sub-block of a chunk: a diagonal block of the triangular inverse, and
# of the pair products (``unit_lower_inverse``, ``pair_products``)
_SUB_BLOCK = 16


def unit_lower_inverse(A: jax.Array) -> jax.Array:
    """(I + A)^-1 for a batch of STRICTLY lower-triangular A [..., Q, Q],
    float32. The diagonal blocks of ``_SUB_BLOCK`` rows are inverted by
    forward substitution, all of them at once (row t of a block's inverse is
    e_t - A[t, :t] T[:t]: ``_SUB_BLOCK`` steps whatever the batch), and
    pairs of inverted blocks are merged by two matmuls a level,

        [[A11, 0], [A21, A22]]^-1 = [[T11, 0], [-T22 A21 T11, T22]],

    as the published kernels do it. XLA's own ``triangular_solve`` on the
    chip took 1.4 ms a K layer for the 512 systems of a 1,024-row window,
    a fifth of the chunk program (PERF.md 6, PR 40)."""
    Q = A.shape[-1]
    base = _SUB_BLOCK
    nb = Q // base
    if Q % base or nb & (nb - 1):
        base, nb = Q, 1                 # one block: substitution alone
    lead = A.shape[:-2]
    eye = jnp.eye(base, dtype=A.dtype)
    diag = jnp.stack([A[..., i * base:(i + 1) * base,
                        i * base:(i + 1) * base] for i in range(nb)], -3)

    def row(t, T):                      # T [..., nb, base, base]
        a = jax.lax.dynamic_slice_in_dim(diag, t, 1, axis=-2)   # [.., 1, b]
        new = eye[t] - jnp.sum(jnp.swapaxes(a, -1, -2) * T, axis=-2)
        return jax.lax.dynamic_update_slice_in_dim(
            T, new[..., None, :], t, axis=-2)
    T = jax.lax.fori_loop(0, base, row,
                          jnp.zeros((*lead, nb, base, base), A.dtype))
    blocks = [(T[..., i, :, :], i * base, base) for i in range(nb)]
    hi = jax.lax.Precision.HIGHEST
    while len(blocks) > 1:
        merged = []
        for (T1, at, n), (T2, _, _) in zip(blocks[::2], blocks[1::2]):
            A21 = A[..., at + n:at + 2 * n, at:at + n]
            T21 = -jnp.matmul(jnp.matmul(T2, A21, precision=hi), T1,
                              precision=hi)
            top = jnp.concatenate([T1, jnp.zeros_like(T1)], axis=-1)
            merged.append((jnp.concatenate(
                [top, jnp.concatenate([T21, T2], axis=-1)], axis=-2),
                at, 2 * n))
        blocks = merged
    return blocks[0][0]


def _pairs_on_the_vector_unit(a: jax.Array, k: jax.Array, G: jax.Array
                              ) -> tuple[jax.Array, jax.Array]:
    """(kk, ak) [..., n, n] over EVERY pair of the n rows of k, a, G
    [..., n, dk]: ``sum_d x_t[d] k_s[d] exp(G_t[d] - G_s[d])`` for x = k and
    x = a where s <= t, 0 elsewhere. A multiply-reduce over [..., n, n, dk]
    with an exponential a pair a channel."""
    n = G.shape[-2]
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    pair = jnp.exp(jnp.where(
        causal[:, :, None], G[..., :, None, :] - G[..., None, :, :],
        -jnp.inf))                                       # [..., n, n, dk]
    kk = jnp.sum(k[..., :, None, :] * k[..., None, :, :] * pair, -1)
    ak = jnp.sum(a[..., :, None, :] * k[..., None, :, :] * pair, -1)
    return kk, ak


def _pair_block(Q: int) -> int:
    """Rows a block of a chunk's pair products: ``_SUB_BLOCK`` where the
    chunk of Q rows is a whole number of them, else the chunk is one."""
    return Q if Q % _SUB_BLOCK else _SUB_BLOCK


def pair_products(a: jax.Array, k: jax.Array, G: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
    """The chunk's pair products (kk, ak) [..., Q, Q], float32:

        xk[t, s] = sum_d x_t[d] k_s[d] exp(G_t[d] - G_s[d])   (s <= t, else 0)

    for x = k and x = a (the queries), from k, a and the cumulative log
    decays G [..., Q, dk] (float32, non-increasing along Q). The chunk is
    cut into sub-blocks of ``_SUB_BLOCK`` rows. A pair inside ONE sub-block
    is formed on the vector unit (``_pairs_on_the_vector_unit``). For s in
    an EARLIER sub-block than t, with r the first row of t's sub-block
    (s < r <= t),

        exp(G_t - G_s) = exp(G_t - G_r) * exp(G_r - G_s),

    both exponents <= 0, so the product over the channels is a matmul of
    ``x_t exp(G_t - G_r)`` [Q/R, R, dk] against ``k_s exp(G_r - G_s)``
    [Q/R, Q, dk] (one scaling of the chunk's keys a sub-block, 0 where
    s >= r; shared by kk and ak), float32 at full precision: kk enters the
    triangular inverse. A factor that underflows to 0 stands for a product
    that is smaller still. A chunk that is no whole number of sub-blocks (a
    window shorter than a chunk) is one block."""
    Q, dk = G.shape[-2:]
    R = _pair_block(Q)
    if R == Q:
        return _pairs_on_the_vector_unit(a, k, G)
    nb, lead = Q // R, G.shape[:-2]
    blocks = lambda x: x.reshape(*lead, nb, R, dk)
    ab, kb, Gb = blocks(a), blocks(k), blocks(G)
    kk_in, ak_in = _pairs_on_the_vector_unit(ab, kb, Gb)   # [..., nb, R, R]
    first = Gb[..., :1, :]                               # G_r [..., nb, 1, dk]
    since = jnp.exp(Gb - first)                          # t side, t >= r
    earlier = jnp.arange(Q)[None, :] < R * jnp.arange(nb)[:, None]
    before = k[..., None, :, :] * jnp.exp(jnp.where(
        earlier[..., None], first - G[..., None, :, :], -jnp.inf))
    hi = jax.lax.Precision.HIGHEST                       # [..., nb, Q, dk]
    kk, ak = (jnp.einsum("...itd,...isd->...its", x * since, before,
                         precision=hi, preferred_element_type=jnp.float32)
              for x in (kb, ab))                         # [..., nb, R, Q]
    own = jnp.eye(nb, dtype=bool)[:, None, :, None]      # s in t's sub-block
    whole = lambda out, ins: jnp.where(
        own, ins[..., :, :, None, :], out.reshape(*lead, nb, R, nb, R)
    ).reshape(*lead, Q, Q)
    return whole(kk, kk_in), whole(ak, ak_in)


def kda_chunk_prefill(q: jax.Array, k: jax.Array, v: jax.Array,
                      g: jax.Array, beta: jax.Array, state: jax.Array,
                      chunk: int) -> tuple[jax.Array, jax.Array]:
    """The chunked gated delta rule over a window, state in, state out.

    q, k [B, S, nh, dk] (normalised, q scaled) and v [B, S, nh, dv] in the
    compute dtype; g [B, S, nh, dk] float32 log decays (<= 0, and 0 where a
    position must not enter the state); beta [B, S, nh] float32 (0 there
    too); ``state`` [B, nh, dk, dv] float32. Returns (o [B, S, nh, dv] in
    v's dtype, the state after the window, float32). Matmul operands are
    the compute dtype with float32 accumulation; decays, the pair products
    (``pair_products``), the solve and the carried state are float32."""
    B, S, nh, dk = q.shape
    dv = v.shape[-1]
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    nc = (S + pad) // Q
    R = _pair_block(Q)
    report_impl("kda_chunk_prefill", "xla",
                f"q{tuple(q.shape)} chunks {nc}x{Q} pairs {R}x{R}"
                + (" + matmul" if R < Q else ""))
    f32, dt = jnp.float32, v.dtype

    def chunks(a):          # [B, S, nh, ...] -> [nc, B, nh, Q, ...]
        a = a.reshape(B, nc, Q, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    with jax.named_scope("kda_chunk_prefill"):
        qc, kc, vc = (chunks(a).astype(f32) for a in (q, k, v))
        bc = chunks(beta[..., None])                     # [nc,B,nh,Q,1]
        G = jnp.cumsum(chunks(g), axis=3)                # inclusive, <= 0
        kk, qk = pair_products(qc, kc, G)                # [nc,B,nh,Q,Q]
        strict = jnp.arange(Q)[:, None] > jnp.arange(Q)[None, :]
        A = jnp.where(strict, bc * kk, 0.0)              # [nc,B,nh,Q,Q]
        decay = jnp.exp(G)                               # [nc,B,nh,Q,dk]
        rhs = jnp.concatenate([bc * vc, bc * kc * decay], axis=-1)
        sol = jnp.matmul(unit_lower_inverse(A), rhs,
                         precision=jax.lax.Precision.HIGHEST)
        Uv, W = sol[..., :dv], sol[..., dv:]
        q_in = (qc * decay).astype(dt)                   # reads S_0
        to_end = (kc * jnp.exp(G[..., -1:, :] - G)).astype(dt)
        end_decay = decay[..., -1, :]                    # [nc,B,nh,dk]

        def carry(S0, c):
            Uv_c, W_c, qk_c, q_c, k_c, e_c = c
            S0c = S0.astype(dt)
            U = Uv_c - jnp.einsum("bhqk,bhkv->bhqv", W_c.astype(dt), S0c,
                                  preferred_element_type=f32)
            o = (jnp.einsum("bhqk,bhkv->bhqv", q_c, S0c,
                            preferred_element_type=f32)
                 + jnp.einsum("bhqs,bhsv->bhqv", qk_c.astype(dt),
                              U.astype(dt), preferred_element_type=f32))
            S1 = S0 * e_c[..., None] + jnp.einsum(
                "bhqk,bhqv->bhkv", k_c, U.astype(dt),
                preferred_element_type=f32)
            return S1, o

        S_last, o = jax.lax.scan(
            carry, state.astype(f32), (Uv, W, qk, q_in, to_end, end_decay))
        # [nc, B, nh, Q, dv] -> [B, S, nh, dv]
        o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(
            B, S + pad, nh, dv)[:, :S]
        return o.astype(dt), S_last


def kda_decode(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
               beta: jax.Array, S: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One token a slot: q, k [slots, nh, dk] (normalised), v
    [slots, nh, dv], g [slots, nh, dk] float32 log decays, beta [slots, nh]
    float32, S [slots, nh, dk, dv] (the cached dtype). Returns (o
    [slots, nh, dv] in v's dtype, the new state in S's dtype). Float32
    throughout."""
    f32 = jnp.float32
    report_impl("kda_decode", "xla", f"S{tuple(S.shape)} {S.dtype}")
    with jax.named_scope("kda_decode"):
        qf, kf, vf = (a.astype(f32) for a in (q, k, v))
        Sd = S.astype(f32) * jnp.exp(g)[..., None]
        u = beta[..., None] * (vf - jnp.sum(Sd * kf[..., None], axis=2))
        new = Sd + kf[..., None] * u[..., None, :]
        o = jnp.sum(new * qf[..., None], axis=2)
        return o.astype(v.dtype), new.astype(S.dtype)


_HEADS_A_BLOCK = 16     # heads of one slot a grid step: 1 MB of state


def _decode_kernel(layer_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref,
                   o_ref, new_ref):
    """One grid step: ``_HEADS_A_BLOCK`` heads of one slot. The state block
    [hb, dk, dv] is read once and written once; q, k and the decays arrive
    as rows [hb, dk] and are turned into columns (constant along dv) by a
    transpose of their sublane broadcast. (``layer_ref``: the scalar the
    block specs place the state block by.)"""
    S = s_ref[...]

    def col(x):                 # [hb, dk] -> [hb, dk, dv]
        return jnp.swapaxes(jnp.broadcast_to(x[:, None, :], S.shape), 1, 2)
    k = col(k_ref[...])
    Sd = S * col(jnp.exp(g_ref[...]))
    u = beta_ref[...] * (v_ref[...] - jnp.sum(Sd * k, axis=1))   # [hb, dv]
    new = Sd + k * u[:, None, :]
    o_ref[...] = jnp.sum(new * col(q_ref[...]), axis=1)
    new_ref[...] = new


def kda_decode_pool(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                    beta: jax.Array, pool: jax.Array, layer,
                    interpret: bool = False
                    ) -> tuple[jax.Array, jax.Array]:
    """``kda_decode`` as ONE Pallas kernel over ``pool[layer]`` in place
    (``pool`` [Lk, slots, nh, dk, dv] float32, aliased to the output): a
    head's 64 KB of state is read once and written once, where the XLA form
    reads it for the prediction ``S'^T k`` and again for the update (the
    linear cell's trace: 13.8 ms a step against a floor of 6.1; PERF.md 6,
    PR 40). A slot with ``beta = 0`` and ``g = 0`` keeps its state bit for
    bit. ``layer`` may be traced (an argument of the jitted ``step_pools``
    every ``K`` layer of a riding program calls): it reaches the block
    specs as a prefetched scalar. Returns (o [slots, nh, dv] in v's dtype,
    the pool)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, nh, dk = q.shape
    dv = v.shape[-1]
    hb = min(_HEADS_A_BLOCK, nh)
    if nh % hb or pool.dtype != jnp.float32:
        raise ValueError(f"kda_decode_pool: {nh} heads in blocks of {hb}, "
                         f"state {pool.dtype}")
    f32 = jnp.float32
    row = lambda w: pl.BlockSpec((None, hb, w), lambda b, h, ly: (b, h, 0))
    state = pl.BlockSpec((None, None, hb, dk, dv),
                         lambda b, h, ly: (ly[0], b, h, 0, 0))
    report_impl("kda_decode", "pallas-interpret" if interpret else "pallas",
                f"S{tuple(pool.shape)}")
    with jax.named_scope("kda_decode"):
        o, pool = pl.pallas_call(
            _decode_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,      # the layer
                grid=(B, nh // hb),
                in_specs=[row(dk), row(dk), row(dv), row(dk), row(dv), state],
                out_specs=[row(dv), state]),
            out_shape=[jax.ShapeDtypeStruct((B, nh, dv), f32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            input_output_aliases={6: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name="kda_decode",
        )(jnp.asarray(layer, jnp.int32).reshape(1),
          q.astype(f32), k.astype(f32), v.astype(f32), g.astype(f32),
          jnp.broadcast_to(beta.astype(f32)[..., None], (B, nh, dv)), pool)
    return o.astype(v.dtype), pool


def kda_gated_norm(o: jax.Array, gate: jax.Array, scale: jax.Array,
                   heads: int, eps: float) -> jax.Array:
    """RMS norm over each head's values times ``1 + scale`` (the program's
    norm-weight convention; ``scale`` [head_dim] is shared by the heads),
    times ``sigmoid(gate)``. o, gate [..., heads * head_dim]."""
    with jax.named_scope("kda_gated_norm"):
        of = o.astype(jnp.float32)
        oh = of.reshape(*of.shape[:-1], heads, of.shape[-1] // heads)
        oh = oh * jax.lax.rsqrt(
            jnp.mean(jnp.square(oh), axis=-1, keepdims=True) + eps)
        oh = oh * (1.0 + scale.astype(jnp.float32))
        return (oh.reshape(of.shape)
                * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(o.dtype)


# ---------------------------------------------------------------------------
# Where the state lives
# ---------------------------------------------------------------------------

def _heads(act: jax.Array, f: jax.Array, b: jax.Array, p: dict, kd,
           alive: Optional[jax.Array] = None):
    """(q, k, v, g, beta) a head from the activated conv output ``act``
    [.., 3 * d_in], the decay's second projection ``f`` [.., d_in] and the
    beta logits ``b`` [.., nh]: q and k normalised (q times dk^-1/2), the
    log decays ``-exp(A_log) softplus(f + dt_bias)`` a channel and
    ``sigmoid(b)`` (times 2 where ``kd.allow_neg_eigval``: the factor
    ``I - beta k k^T`` may then reflect), both float32 and 0 where ``alive``
    is False. ``kd``: the model's ``KDAConfig``."""
    nh, hd, d_in = kd.num_heads, kd.head_dim, kd.inner_size
    lead, f32 = act.shape[:-1], jnp.float32
    q, k, v = (act[..., i * d_in:(i + 1) * d_in].reshape(*lead, nh, hd)
               for i in range(3))
    q = (l2norm(q) * hd ** -0.5).astype(act.dtype)
    k = l2norm(k).astype(act.dtype)
    g = (-jnp.exp(p["A_log"].astype(f32))[:, None]
         * jax.nn.softplus(f.astype(f32) + p["dt_bias"].astype(f32)
                           ).reshape(*lead, nh, hd))
    beta = jax.nn.sigmoid(b.astype(f32))
    if kd.allow_neg_eigval:
        beta = 2.0 * beta
    if alive is not None:
        g = jnp.where(alive[..., None, None], g, 0.0)
        beta = jnp.where(alive[..., None], beta, 0.0)
    return q, k, v, g, beta


def _tail_after(padded: jax.Array, length: jax.Array, K: int) -> jax.Array:
    """The K-1 pre-activation columns before position ``length`` [B] of a
    window whose ``padded`` [B, K-1+S, C] begins with the tail before it
    (position p is ``padded[p + K - 1]``)."""
    idx = length[:, None] + jnp.arange(K - 1, dtype=jnp.int32)
    return jnp.take_along_axis(padded, idx[..., None], axis=1)


def _alive(live: Optional[jax.Array], B: int, S: int):
    """(bool [B, S] or None, live lengths [B]) of ``live`` (bool, or
    segment ids with 0 = padding; the live tokens a PREFIX of each row)."""
    if live is None:
        return None, jnp.full((B,), S, jnp.int32)
    alive = live if live.dtype == jnp.bool_ else live != 0
    return alive, jnp.sum(alive, axis=1, dtype=jnp.int32)


def recur_window(cfg, live: Optional[jax.Array] = None):
    """``recur`` for a window that starts a sequence (a forward with no
    cache, cold prefill): the conv from a zero tail, the chunked form from
    a zero state. Padding takes ``beta = 0, g = 0`` and the state returned
    is the one after the last live token: (the K-1 pre-activation conv
    columns before position ``length`` [B, K-1, C], S [B, nh, dk, dv]
    float32)."""
    kd = cfg.kda

    def recur(qkv, f, b, p):
        B, S, _ = qkv.shape
        alive, length = _alive(live, B, S)
        act, padded = kda_conv(qkv, p["conv"]["kernel"])
        q, k, v, g, beta = _heads(act, f, b, p, kd, alive)
        zero = jnp.zeros((B, kd.num_heads, kd.head_dim, kd.head_dim),
                         jnp.float32)
        o, S1 = kda_chunk_prefill(q, k, v, g, beta, zero, CHUNK)
        return o.reshape(B, S, -1), (
            _tail_after(padded, length, kd.conv_kernel), S1)
    return recur


def step_pools(qkv: jax.Array, f: jax.Array, b: jax.Array, p: dict,
               conv_pool: jax.Array, state_pool: jax.Array, layer,
               write_ok: Optional[jax.Array], kd
               ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One decode step of every slot over the state pools ``conv_pool``
    [Lk, K-1, slots, C] and ``state_pool`` [Lk, slots, nh, dk, dv], read and
    written at ``[layer]`` (an int, or traced): what ``recur_step``'s
    ``recur`` does, with everything it reads an argument (``kd``: the
    ``KDAConfig``), so that a program's two step bodies and its ``K``
    layers can call ONE jitted form of it (serve/decode.py)."""
    B, T, _ = qkv.shape
    if T != 1:
        raise ValueError(
            "every slot advances one token over the state pools; a "
            f"window of {T} tokens a slot (speculative verification) "
            "is not supported: a prompt's window goes through "
            "recur_chunk, one slot at a time")
    tail = conv_pool[layer]
    act, new_tail = kda_conv_step(qkv[:, 0], p["conv"]["kernel"], tail)
    ok = None if write_ok is None else write_ok.reshape(B)
    # a slot that must not move takes beta = 0, g = 0 and keeps its
    # state bit for bit (S * 1 + k * 0), in both forms
    q, k, v, g, beta = _heads(act, f[:, 0], b[:, 0], p, kd, ok)
    if jax.default_backend() == "tpu":
        o, new_pool = kda_decode_pool(q, k, v, g, beta, state_pool, layer)
    else:
        o, new = kda_decode(q, k, v, g, beta, state_pool[layer])
        new_pool = state_pool.at[layer].set(new)
    with jax.named_scope("kda_conv"):
        if ok is not None:
            new_tail = jnp.where(ok[None, :, None], new_tail, tail)
        return (o.reshape(B, 1, -1),
                (conv_pool.at[layer].set(new_tail), new_pool))


def recur_step(cfg, conv_pool: jax.Array, state_pool: jax.Array, layer,
               write_ok: Optional[jax.Array] = None, step=step_pools):
    """``recur`` for one decode step of every slot over the state pools
    ``conv_pool`` [Lk, K-1, slots, C] and ``state_pool``
    [Lk, slots, nh, dk, dv], read and written at ``[layer]``. A slot with
    ``write_ok`` [slots, 1] False (idle, or past its stop position) leaves
    its state as it is. Returns the two pools as the state. (``step``: a
    jitted ``step_pools``, where a program calls it from many places.)"""
    def recur(qkv, f, b, p):
        return step(qkv, f, b, p, conv_pool, state_pool, layer, write_ok,
                    kd=cfg.kda)
    return recur


def slot_state(conv_pool: jax.Array, state_pool: jax.Array, slot: jax.Array,
               start: jax.Array) -> tuple[jax.Array, jax.Array]:
    """ONE slot's rows of the state pools in every ``K`` layer, read once
    before a window's layers run: (conv windows [Lk, K-1, C], states
    [Lk, nh, dk, dv] float32), taken as ZERO where the window starts its
    sequence (``start`` [1] == 0: whatever a former occupant of the slot
    left there is not read). (Read a layer at a time between a layer's
    writes, the compiler keeps the pool as it came beside the pool it
    writes: 2.4 GB at the linear cell's shapes.)

    The conv windows are the sum over the slots of the pool masked to the
    one slot: the same rows (every other term is 0). A SLICE of that pool
    hands the layout its consumer likes (the window's 3 columns on the
    lanes) back to the whole pool wherever a loop carries it, 3.4 GB of
    padding over 81 MB in a decode step at the linear cell's shapes
    (compiled for a described v5e, PERF.md 6, PR 43); the sum hands nothing
    back, in a chunk program or in a step."""
    fresh = start[0] == 0
    mine = (jnp.arange(conv_pool.shape[2]) == slot)[:, None]
    tails = jnp.sum(jnp.where(mine, conv_pool, 0), axis=2,
                    dtype=conv_pool.dtype)
    return (jnp.where(fresh, 0, tails),
            jnp.where(fresh, 0.0, state_pool[:, slot].astype(jnp.float32)))


def write_slot_state(conv_pool: jax.Array, state_pool: jax.Array,
                     slot: jax.Array, tails: jax.Array, states: jax.Array,
                     live) -> tuple[jax.Array, jax.Array]:
    """The pools with ``slot``'s rows of every ``K`` layer overwritten by a
    window's (conv windows [Lk, K-1, C], states [Lk, nh, dk, dv]): ONE
    write a pool, after the window's last layer. ``live`` (bool []) False
    keeps the rows as the pools hold them (a decode step that carries no
    piece names slot 0). The conv windows go in by a select over the whole
    pool, for ``slot_state``'s reason: where a step's loops carry it the
    pool lies with the slots on the lanes, and a slice written there cost
    0.80 ms a step at the linear cell's shapes against ~0.2 for the
    2 x 81 MB of a select (my chip run, PR 43, call 1)."""
    mine = (jnp.arange(conv_pool.shape[2]) == slot)[:, None] & live
    conv_pool = jnp.where(mine, tails.astype(conv_pool.dtype)[:, :, None],
                          conv_pool)
    states = jnp.where(live, states.astype(state_pool.dtype),
                       state_pool[:, slot])
    return conv_pool, state_pool.at[:, slot].set(states)


def arm_slot_state(conv_pool: jax.Array, state_pool: jax.Array,
                   slot: jax.Array, tails: jax.Array, states: jax.Array
                   ) -> tuple[jax.Array, jax.Array]:
    """The pools with ``slot``'s rows of every ``K`` layer SET to a cold
    prefill's (conv windows [Lk, 1, K-1, C], states [Lk, 1, nh, dk, dv],
    from a zero state), whatever a former occupant left there; the conv
    pool lies [Lk, K-1, slot, C]. A set at the slot, where
    ``write_slot_state`` selects over the whole pool: a cold program
    carries the pools through no loop."""
    return (conv_pool.at[:, :, slot].set(tails[:, 0].astype(conv_pool.dtype)),
            state_pool.at[:, slot].set(
                states[:, 0].astype(state_pool.dtype)))


def recur_chunk(cfg, tail: jax.Array, S0: jax.Array,
                live: Optional[jax.Array] = None):
    """``recur`` for a window of ONE slot's prompt (chunked prefill: the
    window is [1, T]): the conv from the slot's cached window ``tail``
    [K-1, C] and the chunked form from the slot's cached state ``S0``
    [nh, dk, dv] (``slot_state``'s rows of this layer). The state it
    returns is (the conv window [K-1, C], the state [nh, dk, dv] float32)
    after the window's last live token (``live`` [1, T], a prefix), which
    the caller writes back (``write_slot_state``)."""
    kd = cfg.kda

    def recur(qkv, f, b, p):
        B, T, _ = qkv.shape
        if B != 1:
            raise ValueError("a chunk is one slot's window: [1, T]")
        alive, length = _alive(live, B, T)
        act, padded = kda_conv(qkv, p["conv"]["kernel"],
                               tail[None].astype(qkv.dtype))
        q, k, v, g, beta = _heads(act, f, b, p, kd, alive)
        o, S1 = kda_chunk_prefill(q, k, v, g, beta, S0[None], CHUNK)
        new_tail = _tail_after(padded, length, kd.conv_kernel)
        return o.reshape(B, T, -1), (new_tail[0], S1[0])
    return recur


# ---------------------------------------------------------------------------
# Snapshots of a slot's state (prefix reuse through the recurrent state)
# ---------------------------------------------------------------------------

def snapshot_pools(conv_pool: jax.Array, state_pool: jax.Array,
                   entries: int) -> dict:
    """Zeroed snapshot pools in the state pools' own layout, an ENTRY where
    those have a slot: ``conv`` [Lk, K-1, entries, C], ``ssm``
    [Lk, entries, nh, dk, dv] float32."""
    return {
        "conv": jnp.zeros((*conv_pool.shape[:2], entries,
                           conv_pool.shape[3]), conv_pool.dtype),
        "ssm": jnp.zeros((state_pool.shape[0], entries,
                          *state_pool.shape[2:]), state_pool.dtype),
    }


def kda_snapshot_take(conv_pool: jax.Array, state_pool: jax.Array,
                      snap_conv: jax.Array, snap_state: jax.Array,
                      slot: jax.Array, entry: jax.Array
                      ) -> tuple[jax.Array, jax.Array]:
    """The snapshot pools with ``entry``'s rows SET to ``slot``'s rows of
    the state pools in every ``K`` layer (a program of its own between two
    dispatches: it carries the pools through no loop)."""
    with jax.named_scope("kda_snapshot_take"):
        return (snap_conv.at[:, :, entry].set(conv_pool[:, :, slot]),
                snap_state.at[:, entry].set(state_pool[:, slot]))


def kda_snapshot_arm(conv_pool: jax.Array, state_pool: jax.Array,
                     snap_conv: jax.Array, snap_state: jax.Array,
                     slot: jax.Array, entry: jax.Array
                     ) -> tuple[jax.Array, jax.Array]:
    """The state pools with ``slot``'s rows SET to ``entry``'s rows of the
    snapshot pools: the slot then stands where the snapshot's token prefix
    ends, and a window that starts there (``slot_state`` with ``start`` >
    0) reads it."""
    with jax.named_scope("kda_snapshot_arm"):
        return (conv_pool.at[:, :, slot].set(snap_conv[:, :, entry]),
                state_pool.at[:, slot].set(snap_state[:, entry]))
