"""Flash (block-streaming) causal attention as a Pallas TPU kernel.

The reference has no attention kernel at all: its `flash_attention` flag is
dead config (reference autotuning.py:140 validates-but-ignores it; preset
llama-7b-a100x8.toml:62 is read by nothing — SURVEY §5.7), and its serve
path recomputes full-prefix attention every token (server.py:199-204). This
module supplies the real thing, TPU-shaped:

- **Forward**: q-block x kv-block streaming with online softmax; scores/
  accumulators live in VMEM fp32 scratch; the [S, S] matrix is never
  materialised in HBM. Dots keep bf16 operands (full MXU rate) with fp32
  accumulation; softmax math is fp32.
- **Masking by explicit position arrays**: causal and packed-segment masks
  come from [*, 1, S] position/segment refs streamed alongside q/k — NOT
  from grid iota. That lets the same kernels serve (a) plain causal
  attention, (b) GQA with query-head groups FOLDED into the q-row axis (KV
  streams once per KV head, no jnp.repeat), and (c) ring-attention chunks
  whose kv carry arbitrary global positions (ops/ring_attention.py drives
  the raw `_fwd`/`_bwd_impl` entry points around its ppermute ring).
  Causal block-skipping stays: a block runs only when its first kv
  position <= its last q position (data-dependent pl.when).
- **Backward**: the standard two-pass flash backward (delta = rowsum(dO*O)
  precomputed; one kernel for dq, one for dk/dv), wired via jax.custom_vjp,
  so 32k-context training is S-linear in memory.
- Numerics are validated against models.layers.dot_product_attention in
  tests (interpret mode on CPU, compiled on TPU).

Layout notes: heads are folded into the grid's batch dimension; tiles are
[block, head_dim] with head_dim typically 64/128 — lane-dim aligned for the
MXU; fp32 accumulation per the guide's preferred_element_type rule. Block
sizes shrink to the largest divisor of the sequence length so blocks never
straddle a padded tail (callers keep S a multiple of a small power of two).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _fit_block(requested: int, size: int) -> int:
    """Largest block <= requested that divides size (so no block straddles
    the array edge — masking comes from position/segment refs, not bounds
    checks)."""
    b = min(requested, size)
    while size % b:
        b //= 2
    return max(b, 1)


def _mask_for(qseg_ref, kseg_ref, qpos_ref, kpos_ref, causal: bool):
    qseg = qseg_ref[0, :]                         # [bq]
    kseg = kseg_ref[0, :]                         # [bk]
    mask = (qseg[:, None] == kseg[None, :]) & (kseg[None, :] != 0)
    if causal:
        qpos = qpos_ref[0, :]
        kpos = kpos_ref[0, :]
        mask = mask & (qpos[:, None] >= kpos[None, :])
    return mask


def _block_runs(qpos_ref, kpos_ref, causal: bool, block_q: int):
    """Causal block pruning. A block is dead iff every kv position exceeds
    every q position: then no (q, k) pair passes the causal test regardless
    of segments. Uses true block min/max — packed batches restart positions
    at document boundaries (io/data.py), so positions are NOT monotonic
    within a block and first/last-element bounds would skip live blocks."""
    del block_q
    if not causal:
        return True
    return jnp.min(kpos_ref[0, :]) <= jnp.max(qpos_ref[0, :])


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, qseg_ref, kseg_ref, qpos_ref, kpos_ref,
                o_ref, lse_ref,
                acc_scratch, m_scratch, l_scratch,
                *, causal: bool, block_q: int, scale: float):
    ki = pl.program_id(2)   # kv block index

    @pl.when(ki == 0)
    def _init():
        acc_scratch[:] = jnp.zeros_like(acc_scratch)
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)

    @pl.when(_block_runs(qpos_ref, kpos_ref, causal, block_q))
    def _body():
        # dots stay in the input dtype (bf16 on TPU -> full MXU rate) with
        # fp32 ACCUMULATION; softmax math is fp32 throughout
        q = q_ref[...]                               # [bq, d]
        k = k_ref[...]                               # [bk, d]
        v = v_ref[...]                               # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        mask = _mask_for(qseg_ref, kseg_ref, qpos_ref, kpos_ref, causal)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[...]                       # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1
        p = jnp.exp(jnp.where(m_new > NEG_INF / 2, s - m_new, NEG_INF))
        alpha = jnp.exp(jnp.where(m_new > NEG_INF / 2, m_prev - m_new, 0.0))
        l_new = alpha * l_scratch[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scratch[...] = m_new
        l_scratch[...] = l_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scratch[...]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[...] = (acc_scratch[...] / safe_l).astype(o_ref.dtype)
        lse = m_scratch[...] + jnp.log(safe_l)
        lse_ref[...] = jnp.where(l > 0, lse, NEG_INF).astype(jnp.float32)


def _fwd(q, k, v, q_segments, kv_segments, q_positions, kv_positions,
         causal, block_q, block_k, scale):
    """q: [BH, S, D] (heads folded into batch); segments/positions:
    [BH, 1, S]. Returns (out [BH, S, D], lse [BH, S, 1] fp32)."""
    BH, S, D = q.shape
    Skv = k.shape[1]
    bq = _fit_block(block_q, S)
    bk = _fit_block(block_k, Skv)
    grid = (BH, S // bq, Skv // bk)

    kernel = functools.partial(_fwd_kernel, causal=causal, block_q=bq,
                               scale=scale)

    with jax.named_scope("flash_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((None, 1, bk), lambda b, i, j: (b, 0, j)),
                pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((None, 1, bk), lambda b, i, j: (b, 0, j)),
            ],
            out_specs=[
                pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, bq, 1), lambda b, i, j: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BH, S, D), q.dtype),
                jax.ShapeDtypeStruct((BH, S, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, D), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
            interpret=_interpret(),
            name="flash_fwd",
        )(q, k, v, q_segments, kv_segments, q_positions, kv_positions)
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels (two-pass flash backward)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, qseg_ref, kseg_ref, qpos_ref,
                   kpos_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scratch,
                   *, causal, block_q, scale):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scratch[...] = jnp.zeros_like(dq_scratch)

    @pl.when(_block_runs(qpos_ref, kpos_ref, causal, block_q))
    def _body():
        # bf16 dot operands / fp32 accumulation, as in the forward kernel
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...]                            # [bq, 1]
        delta = delta_ref[...]                        # [bq, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _mask_for(qseg_ref, kseg_ref, qpos_ref, kpos_ref, causal)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)    # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_scratch[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[...] = dq_scratch[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, qseg_ref, kseg_ref, qpos_ref,
                    kpos_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    dk_scratch, dv_scratch,
                    *, causal, block_q, scale):
    qi = pl.program_id(2)   # q block (inner loop dim)

    @pl.when(qi == 0)
    def _init():
        dk_scratch[...] = jnp.zeros_like(dk_scratch)
        dv_scratch[...] = jnp.zeros_like(dv_scratch)

    @pl.when(_block_runs(qpos_ref, kpos_ref, causal, block_q))
    def _body():
        # bf16 dot operands / fp32 accumulation, as in the forward kernel
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...]
        delta = delta_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _mask_for(qseg_ref, kseg_ref, qpos_ref, kpos_ref, causal)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_scratch[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_scratch[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[...] = dk_scratch[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scratch[...].astype(dv_ref.dtype)


def _bwd_impl(q, k, v, q_segments, kv_segments, q_positions, kv_positions,
              do, lse, delta, causal, block_q, block_k, scale):
    """Raw flash backward given (possibly GLOBAL) lse/delta per q row —
    also driven per-chunk by the ring-attention backward ring."""
    BH, S, D = q.shape
    Skv = k.shape[1]
    bq = _fit_block(block_q, S)
    bk = _fit_block(block_k, Skv)
    do = do.astype(q.dtype)

    with jax.named_scope("flash_bwd_dq"):
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, causal=causal, block_q=bq,
                              scale=scale),
            grid=(BH, S // bq, Skv // bk),
            in_specs=[
                pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((None, 1, bk), lambda b, i, j: (b, 0, j)),
                pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((None, 1, bk), lambda b, i, j: (b, 0, j)),
                pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, bq, 1), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, bq, 1), lambda b, i, j: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
            interpret=_interpret(),
            name="flash_bwd_dq",
        )(q, k, v, q_segments, kv_segments, q_positions, kv_positions, do, lse,
          delta)

    with jax.named_scope("flash_bwd_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, causal=causal, block_q=bq,
                              scale=scale),
            grid=(BH, Skv // bk, S // bq),
            in_specs=[
                pl.BlockSpec((None, bq, D), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((None, bk, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((None, bk, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((None, 1, bq), lambda b, j, i: (b, 0, i)),
                pl.BlockSpec((None, 1, bk), lambda b, j, i: (b, 0, j)),
                pl.BlockSpec((None, 1, bq), lambda b, j, i: (b, 0, i)),
                pl.BlockSpec((None, 1, bk), lambda b, j, i: (b, 0, j)),
                pl.BlockSpec((None, bq, D), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((None, bq, 1), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((None, bq, 1), lambda b, j, i: (b, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, bk, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((None, bk, D), lambda b, j, i: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BH, Skv, D), k.dtype),
                jax.ShapeDtypeStruct((BH, Skv, D), v.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)],
            interpret=_interpret(),
            name="flash_bwd_dkv",
        )(q, k, v, q_segments, kv_segments, q_positions, kv_positions, do, lse,
          delta)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _flash(q, k, v, q_segments, kv_segments, q_positions, kv_positions,
           causal, block_q, block_k, scale):
    out, _ = _fwd(q, k, v, q_segments, kv_segments, q_positions,
                  kv_positions, causal, block_q, block_k, scale)
    return out


def _flash_fwd(q, k, v, q_segments, kv_segments, q_positions, kv_positions,
               causal, block_q, block_k, scale):
    out, lse = _fwd(q, k, v, q_segments, kv_segments, q_positions,
                    kv_positions, causal, block_q, block_k, scale)
    return out, (q, k, v, q_segments, kv_segments, q_positions, kv_positions,
                 out, lse)


def _flash_bwd(causal, block_q, block_k, scale, res, dout):
    q, k, v, qseg, kseg, qpos, kpos, out, lse = res
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    dq, dk, dv = _bwd_impl(q, k, v, qseg, kseg, qpos, kpos, dout, lse, delta,
                           causal, block_q, block_k, scale)
    return dq, dk, dv, None, None, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def fold_gqa(q, k, v, segs, pos):
    """Fold [B, S, N, D] tensors into the kernel's head-in-batch layout,
    stacking GQA query-head groups along the q-row axis so each KV block
    streams into VMEM once per KV head (not once per query head).

    Returns (qf [B*Nkv, G*S, D], kf, vf [B*Nkv, Skv, D],
    segs_q/pos_q [B*Nkv, 1, G*S], segs_kv/pos_kv [B*Nkv, 1, Skv],
    unfold(out) -> [B, S, Nq, D]).
    """
    B, S, Nq, D = q.shape
    Skv, Nkv = k.shape[1], k.shape[2]
    groups = Nq // Nkv

    # q head n = h*G + g (the kv-repeat convention)
    qf = q.reshape(B, S, Nkv, groups, D).transpose(0, 2, 3, 1, 4)
    qf = qf.reshape(B * Nkv, groups * S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Nkv, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Nkv, Skv, D)
    segs_q = jnp.repeat(jnp.tile(segs, (1, groups)), Nkv, axis=0)[:, None, :]
    pos_q = jnp.repeat(jnp.tile(pos, (1, groups)), Nkv, axis=0)[:, None, :]
    segs_kv = jnp.repeat(segs, Nkv, axis=0)[:, None, :]
    pos_kv = jnp.repeat(pos, Nkv, axis=0)[:, None, :]

    def unfold(out):
        out = out.reshape(B, Nkv, groups, S, D).transpose(0, 3, 1, 2, 4)
        return out.reshape(B, S, Nq, D)

    return qf, kf, vf, segs_q, pos_q, segs_kv, pos_kv, unfold


def flash_attention(
    q: jax.Array,                      # [B, S, Nq, D]
    k: jax.Array,                      # [B, Skv, Nkv, D]
    v: jax.Array,
    segment_ids: Optional[jax.Array] = None,   # [B, S]
    positions: Optional[jax.Array] = None,     # [B, S] global positions
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
) -> jax.Array:
    """Flash attention with GQA-folded KV streaming and packed segments.

    Matches models.layers.dot_product_attention numerics (fp32 softmax);
    see the module docstring for the masking/GQA design.
    """
    B, S, Nq, D = q.shape
    assert k.shape[1] == S, "flash_attention is for self-attention (Skv==S)"
    if segment_ids is None:
        segs = jnp.ones((B, S), jnp.int32)
    else:
        segs = segment_ids.astype(jnp.int32)
    if positions is None:
        pos = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, axis=0)
    else:
        pos = positions.astype(jnp.int32)
    scale = 1.0 / float(D) ** 0.5

    # a q block must never straddle a head-group boundary in the folded
    # layout (positions reset there, breaking the causal block-prune bound)
    block_q = _fit_block(block_q, S)
    qf, kf, vf, segs_q, pos_q, segs_kv, pos_kv, unfold = fold_gqa(
        q, k, v, segs, pos)
    out = _flash(qf, kf, vf, segs_q, segs_kv, pos_q, pos_kv, causal,
                 block_q, block_k, scale)
    return unfold(out).astype(q.dtype)
