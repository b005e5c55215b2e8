"""Pallas TPU kernel: W4A16 matmul with IN-KERNEL dequantization.

Round-3 measured int4 decode at 24.8 tok/s vs bf16's 104 (BASELINE.md):
the XLA dequant chain (nibble unpack -> stack -> reshape -> scale) defeats
dequant-into-matmul fusion, so the full bf16 weight tensor round-trips
through HBM every step — 2.5x the traffic bf16 itself pays. The verdict
(r3 weak #5) noted a dequant-in-kernel matmul had not even been costed.
This kernel is that costing: packed nibbles stream HBM->VMEM at 4-bit
width and expand to bf16 in registers, so per-step weight traffic is
0.25x bf16 / 0.5x int8.

Layout contract (ops.quantization.quantize_int4_groupwise, "kernel"
orientation): packed uint8 [in/2, out] with input-channel nibble pair
(2i, 2i+1) at row i; scales fp32 [in/group, out]; chan fp32 [in].

Interleave avoidance: x @ W = x_even @ W_even + x_odd @ W_odd, so the
kernel never reassembles nibble pairs — the low-nibble plane multiplies
the even input channels and the high plane the odd ones, two MXU dots per
(k, out) tile. The AWQ channel statistic folds into the ACTIVATIONS once
per call (x * 1/chan), not into the weight tiles.

Constraints: in % (2*block_k) == 0, out % block_out == 0, block_k == group
(one scale row per k tile). CPU fallback/interpret mode for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _unnib(v):
    """4-bit two's-complement sign extension on int32 lanes.

    Same encoding as ops.quantization._unnibble (which is pinned to int8
    lanes — int8 VPU arithmetic is what the XLA dequant paths want, but
    inside Mosaic the int32 form lowers more robustly).
    tests/test_int4_matmul_pallas.py asserts the two never diverge."""
    return jnp.where(v >= 8, v - 16, v)


def _make_kernel(wdtype):
    # whole reduction dim resident per out-tile (1-2 MB VMEM at 7B
    # shapes): one unpack + one dot pair per tile, no k-grid — the first
    # k-tiled version used (Bp, group/2) x-blocks whose 64-lane trailing
    # dim Mosaic rejects (blocks must end in a multiple of 128 or the
    # full array dim). wdtype: bf16 on TPU; f32 under interpret (the
    # XLA:CPU dot thunk lacks bf16 x bf16 -> f32)
    def _kernel(xe_ref, xo_ref, packed_ref, scale_ref, out_ref):
        p = packed_ref[:].astype(jnp.int32)            # [in/2, bo]
        s = scale_ref[:].astype(jnp.float32)           # [G, bo]
        half_group = p.shape[0] // s.shape[0]
        # per-pair-row scale: group g covers packed rows [g*group/2,
        # (g+1)*group/2) — a broadcast + relabel, no data movement
        srow = jnp.repeat(s, half_group, axis=0)
        wlo = (_unnib(p & 0xF).astype(jnp.float32) * srow).astype(wdtype)
        whi = (_unnib(p >> 4).astype(jnp.float32) * srow).astype(wdtype)
        out_ref[:] = (
            jnp.dot(xe_ref[:], wlo, preferred_element_type=jnp.float32)
            + jnp.dot(xo_ref[:], whi, preferred_element_type=jnp.float32))
    return _kernel


@functools.partial(jax.jit, static_argnames=("group", "block_out",
                                             "interpret"))
def matmul_w4(x: jax.Array, packed: jax.Array, scale: jax.Array,
              chan: jax.Array, group: int = 128, block_out: int = 0,
              interpret: bool = False) -> jax.Array:
    """y = x @ dequant(packed, scale, chan) with in-kernel dequant.

    x [B, in] (any float dtype; compute is bf16 x bf16 -> f32),
    packed uint8 [in/2, out], scale [in/group, out], chan [in].
    Returns [B, out] in x.dtype. B is padded to 8 MXU sublanes.
    """
    B, n_in = x.shape
    n_out = packed.shape[-1]
    if packed.shape[-2] * 2 != n_in:
        raise ValueError(f"packed rows {packed.shape[-2]} != in/2")
    if n_in % group:
        raise ValueError(f"in={n_in} not divisible by group={group}")
    if block_out == 0:
        # largest standard tile dividing n_out (gpt-7b's FFN 11008 =
        # 86*128 divides 256 but not 512 — a fixed 512 crashed the serve
        # trace, round-4 review) whose VMEM residents fit: the packed
        # tile [in/2, bo] expands to TWO bf16 planes in-kernel (~5x the
        # packed bytes live at once), and in=11008 with bo=512 failed
        # Mosaic compilation outright (round-5 kernel bench — the same
        # shape gpt-7b serving routes through for the FFN down-proj).
        # Fall back to the whole dim only for tiny no-128-divisor outs.
        budget = 2**20
        block_out = next((b for b in (512, 256, 128)
                          if n_out % b == 0 and (n_in // 2) * b <= budget),
                         128 if n_out % 128 == 0 else n_out)
    bo = min(block_out, n_out)
    if n_out % bo:
        raise ValueError(f"out={n_out} not divisible by block_out={bo}")

    wdtype = jnp.float32 if interpret else jnp.bfloat16
    xf = (x.astype(jnp.float32) / chan.astype(jnp.float32))
    # bf16 round-trip either way so interpret numerics track the TPU path
    xf = xf.astype(jnp.bfloat16).astype(wdtype)
    Bp = ((B + 7) // 8) * 8            # every batch to a sublane multiple
    if Bp != B:
        xf = jnp.pad(xf, ((0, Bp - B), (0, 0)))
    xe, xo = xf[:, 0::2], xf[:, 1::2]              # [Bp, in/2]

    n_groups = n_in // group
    with jax.named_scope("int4_matmul"):
        out = pl.pallas_call(
            _make_kernel(wdtype),
            grid=(n_out // bo,),
            in_specs=[
                pl.BlockSpec((Bp, n_in // 2), lambda i: (0, 0)),
                pl.BlockSpec((Bp, n_in // 2), lambda i: (0, 0)),
                pl.BlockSpec((n_in // 2, bo), lambda i: (0, i)),
                pl.BlockSpec((n_groups, bo), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((Bp, bo), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((Bp, n_out), jnp.float32),
            interpret=interpret,
            name="int4_matmul",
        )(xe, xo, packed, scale)
    return out[:B].astype(x.dtype)
