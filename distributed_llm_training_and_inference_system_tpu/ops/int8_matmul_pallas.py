"""Pallas TPU kernel: W8A16 matmul with IN-KERNEL dequantization.

Status: OPT-IN (ServeConfig.int8_pallas_matmul), MEASURED NEGATIVE
end-to-end — keep it off. Round-5 verdict in full: at the ISOLATED
kernel level this kernel (incl. its k-split wide-reduction path) beats
XLA's fused int8 dequant at every gpt-7b decode shape (e.g. ffn
up-proj 0.061 vs 0.224-0.474 ms across runs; attn 0.023 vs 0.026+) —
but the wins do NOT compose: serve-level A/B measured 105.8 tok/s /
52.7 ms decode step vs the XLA route's 145.3 / 36.1 at gpt-7b c8, and
127.9 vs 133.0 at gpt-1b c4. Seven opaque custom calls per layer x 32
layers serialize scheduling XLA otherwise overlaps and block the
fusion of neighbouring elementwise work. The kernel stays for
per-chip costing (experiments/int4_kernel_bench.py, "int8-pallas")
and as the measured record of WHY the fused-XLA default is right —
unlike int4, whose unpack chain genuinely defeats fusion and whose
Pallas kernel is a measured end-to-end win. It streams int8 HBM->VMEM
at 1-byte width and converts to bf16 in registers, so weight traffic
is the int8 bytes alone.

Layout contract (ops.quantization.quantize_int8 with the default
axis=-1 over a [in, out] kernel): values int8 [in, out], scale fp32
[in, 1] — one scale per INPUT row. Because the scale multiplies rows
of W, it folds into the ACTIVATIONS once per call (x * scale), exactly
like the W4 kernel's AWQ channel statistic: the kernel itself is a
pure convert-and-dot, no per-tile scale arithmetic.

Constraints: out % block_out == 0 (block_out auto-picks a standard
tile). Narrow reductions keep the whole reduction dim resident per
out-tile under a ~2 MB int8 budget; WIDE reductions (where that budget
would force the out tile below 512 — e.g. gpt-7b's FFN down-proj,
in=11008) take a k-split accumulating kernel instead, keeping a wide
out tile with bounded k tiles. CPU fallback/interpret mode for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _make_kernel(wdtype):
    # wdtype: bf16 on TPU; f32 under interpret (the XLA:CPU dot thunk
    # lacks bf16 x bf16 -> f32, same workaround as the W4 kernel)
    def _kernel(x_ref, w_ref, out_ref):
        w = w_ref[:].astype(wdtype)                    # int8 -> compute
        out_ref[:] = jnp.dot(x_ref[:], w,
                             preferred_element_type=jnp.float32)
    return _kernel


def _make_ksplit_kernel(wdtype):
    # k-tiled variant: grid (out, k) with k minor, accumulating into the
    # revisited out block. Lifts the whole-K VMEM constraint that forced
    # a 128-wide out tile at gpt-7b FFN width (in=11008) — measured
    # 52 GB/s there vs 512 GB/s at the whole-K-friendly attn shapes.
    def _kernel(x_ref, w_ref, out_ref):
        @pl.when(pl.program_id(1) == 0)
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)
        w = w_ref[:].astype(wdtype)
        out_ref[:] += jnp.dot(x_ref[:], w,
                              preferred_element_type=jnp.float32)
    return _kernel


@functools.partial(jax.jit, static_argnames=("block_out", "interpret"))
def matmul_w8(x: jax.Array, values: jax.Array, scale: jax.Array,
              block_out: int = 0, interpret: bool = False) -> jax.Array:
    """y = x @ (values * scale) with in-kernel int8->bf16 dequant.

    x [B, in] (any float dtype; compute is bf16 x bf16 -> f32),
    values int8 [in, out], scale fp32 [in, 1] (quantize_int8 axis=-1
    layout; [in] also accepted). Returns [B, out] in x.dtype. B is
    padded to 8 MXU sublanes.
    """
    B, n_in = x.shape
    if values.shape[-2] != n_in:
        raise ValueError(f"values rows {values.shape[-2]} != in={n_in}")
    n_out = values.shape[-1]
    budget = 2 * 2**20
    auto_tile = block_out == 0
    if block_out == 0:
        # largest standard tile whose int8 block stays <= ~2 MB: the
        # converted bf16 tile is 2x the int8 bytes and Mosaic double-
        # buffers the streamed input, so bigger tiles blow VMEM at the
        # gpt-7b FFN shapes (in=11008). When even 128 exceeds the budget
        # (n_in > 16K) 128 is still the least-bad dividing tile — the
        # whole-dim fallback would be the LARGEST tile exactly when VMEM
        # is tightest; it stays reserved for tiny no-128-divisor outputs
        block_out = next((b for b in (512, 256, 128)
                          if n_out % b == 0 and n_in * b <= budget),
                         128 if n_out % 128 == 0 else n_out)
    bo = min(block_out, n_out)
    if n_out % bo:
        raise ValueError(f"out={n_out} not divisible by block_out={bo}")

    wdtype = jnp.float32 if interpret else jnp.bfloat16
    # per-input-row scale folds into the activations (see module doc);
    # bf16 round-trip either way so interpret numerics track the TPU path
    s = scale.reshape(-1) if scale.ndim > 1 else scale
    xf = (x.astype(jnp.float32) * s.astype(jnp.float32))
    xf = xf.astype(jnp.bfloat16).astype(wdtype)
    Bp = ((B + 7) // 8) * 8            # every batch to a sublane multiple
    if Bp != B:
        xf = jnp.pad(xf, ((0, Bp - B), (0, 0)))

    # wide reductions take the k-split kernel: a 512-wide out tile with
    # a bounded k tile, instead of shrinking the out tile to fit the
    # whole reduction in VMEM (which cut the FFN-width tile to 128 and
    # the measured stream rate 10x)
    bk = next((k for k in (2048, 1024, 512, 256)
               if n_in % k == 0 and k < n_in), 0)
    bo_k = next((b for b in (512, 256, 128) if n_out % b == 0), 0)
    # k-split whenever the VMEM budget forced the whole-K auto pick
    # below a 512-wide tile (i.e. the reduction is too wide to afford
    # the tile width the MXU wants) and the dims tile cleanly
    # no clean k tile AND the whole-K block blows the budget (n_in > 16K
    # at bo=128): a real-TPU launch would fail at Mosaic compile time (or
    # worse, thrash VMEM) where interpret-mode tests can't see it — take
    # the XLA dequant route loudly instead (ADVICE r5 #2). The scale is
    # already folded into the activations, so the fallback is a plain
    # bf16 dot over converted weights — same math as the kernel.
    if auto_tile and n_in * bo > budget and not (bk and bo_k > bo):
        import warnings

        from ..utils.platform import report_impl
        report_impl("matmul_w8", "xla-dequant",
                    f"[{n_in}, {n_out}]: no k tile fits the VMEM budget")
        warnings.warn(
            f"matmul_w8: reduction dim {n_in} has no clean k tile and a "
            f"whole-K [{n_in}, {bo}] block exceeds the ~2 MB VMEM budget "
            "— falling back to the XLA dequant route for this shape",
            RuntimeWarning, stacklevel=2)
        out = jnp.dot(xf, values.astype(wdtype),
                      preferred_element_type=jnp.float32)
        return out[:B].astype(x.dtype)
    if (auto_tile and bo < 512 and n_in * 512 > budget and bk
            and bo_k > bo):
        bo = bo_k
        with jax.named_scope("int8_matmul"):
            out = pl.pallas_call(
                _make_ksplit_kernel(wdtype),
                grid=(n_out // bo, n_in // bk),
                in_specs=[
                    pl.BlockSpec((Bp, bk), lambda i, j: (0, j)),
                    pl.BlockSpec((bk, bo), lambda i, j: (j, i)),
                ],
                out_specs=pl.BlockSpec((Bp, bo), lambda i, j: (0, i)),
                out_shape=jax.ShapeDtypeStruct((Bp, n_out), jnp.float32),
                interpret=interpret,
                name="int8_matmul",
            )(xf, values)
        return out[:B].astype(x.dtype)

    with jax.named_scope("int8_matmul"):
        out = pl.pallas_call(
            _make_kernel(wdtype),
            grid=(n_out // bo,),
            in_specs=[
                pl.BlockSpec((Bp, n_in), lambda i: (0, 0)),
                pl.BlockSpec((n_in, bo), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((Bp, bo), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((Bp, n_out), jnp.float32),
            interpret=interpret,
            name="int8_matmul",
        )(xf, values)
    return out[:B].astype(x.dtype)
