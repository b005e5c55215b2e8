"""Fused RMSNorm Pallas kernel.

One pass over the row: mean-of-squares reduction, rsqrt, scale — fused so
the activation is read once from HBM instead of XLA's (already decent)
fusion; mainly exists as the tuning target for `llmctl tune kernels` and a
simple reference Pallas op. Numerics identical to models.layers.rms_norm
(fp32 statistics).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                 # [rows, H]
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    normed = x * jax.lax.rsqrt(var + eps)
    scale = 1.0 + scale_ref[...].astype(jnp.float32)   # [H]
    o_ref[...] = (normed * scale[None, :]).astype(o_ref.dtype)


def rms_norm_pallas(x: jax.Array, scale: jax.Array, eps: float = 1e-5,
                    block_rows: int = 256) -> jax.Array:
    """x: [..., H], scale: [H]."""
    orig_shape = x.shape
    H = orig_shape[-1]
    rows = 1
    for d in orig_shape[:-1]:
        rows *= d
    x2 = x.reshape(rows, H)
    br = min(block_rows, rows)
    grid = (pl.cdiv(rows, br),)
    with jax.named_scope("rmsnorm"):
        out = pl.pallas_call(
            functools.partial(_kernel, eps=eps),
            grid=grid,
            in_specs=[
                pl.BlockSpec((br, H), lambda i: (i, 0)),
                pl.BlockSpec((H,), lambda i: (0,)),
            ],
            out_specs=pl.BlockSpec((br, H), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((rows, H), x.dtype),
            interpret=jax.default_backend() != "tpu",
            name="rmsnorm",
        )(x2, scale)
    return out.reshape(orig_shape)
