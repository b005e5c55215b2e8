"""Fused AdamW apply: clip + moments + bias correction + decay + param write
in one pass over HBM per parameter leaf.

Why this exists: the optax chain (scale_by_adam → add_decayed_weights →
scale_by_learning_rate → apply_updates) is semantically one elementwise pass,
but measured ~79 ms on the gpt-750m step vs a ~50 ms HBM-bound floor
(BASELINE.md round-2 ablation) — XLA materialises the clipped-grads tree and
the updates tree as separate HBM round trips. Here each leaf is updated by a
single kernel that reads (p, g, mu, nu) once and writes (p', mu', nu') once:
24 B/param of traffic at fp32 params / bf16 mu / fp32 nu, the floor.

Numerics match the optax chain exactly (same op order, fp32 arithmetic, mu
stored back in ``moment_dtype``); equivalence is asserted in
tests/test_exec.py. The reference hardcodes torch AdamW
(reference llmctl/runtime/engine.py:217-256) and never fuses.

Two implementations, same math:
  - Pallas (TPU): per-leaf elementwise kernel, in-place via
    input_output_aliases, scalars (lr, bias corrections, clip scale) in SMEM.
  - jnp fallback (CPU/interpret): one fused expression per leaf.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _leaf_math(p, g, mu, nu, lr, om1, om2, clip_scale, *, b1, b2, eps, wd,
               mu_dtype, nu_dtype=jnp.float32):
    """The shared fp32 update formula (optax order, see module docstring).
    om1/om2 are (1 - b^t): dividing (as optax.bias_correction does) rather
    than multiplying by a reciprocal keeps the result bitwise-equal to the
    optax chain (asserted in tests/test_exec.py)."""
    g32 = g.astype(jnp.float32) * clip_scale
    # b1*mu in mu's native dtype (weak-typed scalar), exactly as optax's
    # update_moment does — upcasting mu first would round differently
    mu32 = (1.0 - b1) * g32 + b1 * mu
    nu32 = (1.0 - b2) * (g32 * g32) + b2 * nu.astype(jnp.float32)
    mu_hat = mu32 / om1
    nu_hat = nu32 / om2
    upd = mu_hat / (jnp.sqrt(nu_hat) + eps)
    p32 = p.astype(jnp.float32)
    if wd:
        upd = upd + wd * p32
    new_p = (p32 - lr * upd).astype(p.dtype)
    return new_p, mu32.astype(mu_dtype), nu32.astype(nu_dtype)


def _adamw_kernel(s_ref, p_ref, g_ref, mu_ref, nu_ref,
                  op_ref, omu_ref, onu_ref, *, b1, b2, eps, wd, mu_dtype,
                  nu_dtype):
    lr, om1, om2, clip_scale = s_ref[0], s_ref[1], s_ref[2], s_ref[3]
    new_p, new_mu, new_nu = _leaf_math(
        p_ref[...], g_ref[...], mu_ref[...], nu_ref[...],
        lr, om1, om2, clip_scale, b1=b1, b2=b2, eps=eps, wd=wd,
        mu_dtype=mu_dtype, nu_dtype=nu_dtype)
    op_ref[...] = new_p
    omu_ref[...] = new_mu
    onu_ref[...] = new_nu


def _update_leaf_pallas(p, g, mu, nu, scalars, *, b1, b2, eps, wd,
                        block_rows=256, block_cols=512):
    """One-pass AdamW update of a single >=2D leaf on TPU."""
    shape = p.shape
    C = shape[-1]
    R = p.size // C
    p2, g2, mu2, nu2 = (x.reshape(R, C) for x in (p, g, mu, nu))
    bc = min(block_cols, C)
    br = min(block_rows, R)
    grid = (pl.cdiv(R, br), pl.cdiv(C, bc))
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    with jax.named_scope("fused_adamw"):
        out = pl.pallas_call(
            functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps, wd=wd,
                              mu_dtype=mu.dtype, nu_dtype=nu.dtype),
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),   # scalars, whole array
                spec, spec, spec, spec,
            ],
            out_specs=(spec, spec, spec),
            out_shape=(
                jax.ShapeDtypeStruct((R, C), p.dtype),
                jax.ShapeDtypeStruct((R, C), mu.dtype),
                jax.ShapeDtypeStruct((R, C), nu.dtype),
            ),
            # in-place: p -> p', mu -> mu', nu -> nu' (0 is the scalar vector)
            input_output_aliases={1: 0, 3: 1, 4: 2},
            interpret=jax.default_backend() != "tpu",
            name="fused_adamw",
        )(scalars, p2, g2, mu2, nu2)
    new_p, new_mu, new_nu = out
    return (new_p.reshape(shape), new_mu.reshape(shape),
            new_nu.reshape(shape))


def fused_adamw_apply(params: Any, grads: Any, mu: Any, nu: Any,
                      count: jax.Array, *, lr: jax.Array, b1: float,
                      b2: float, eps: float, weight_decay: float,
                      decay_mask: Any, clip_scale: jax.Array,
                      use_pallas: bool = True):
    """Apply one AdamW step; returns (new_params, new_mu, new_nu).

    ``count`` is the optax step count BEFORE this update (bias correction
    uses count+1, matching optax.scale_by_adam). ``clip_scale`` is the
    global-norm clip factor applied to every grad leaf (1.0 = no clip).
    ``decay_mask`` is a pytree of bools (True = apply weight decay).
    """
    count_inc = count + 1
    om1 = 1.0 - b1 ** count_inc.astype(jnp.float32)
    om2 = 1.0 - b2 ** count_inc.astype(jnp.float32)
    lr = jnp.asarray(lr, jnp.float32)
    scalars = jnp.stack([lr, om1, om2,
                         jnp.asarray(clip_scale, jnp.float32)])

    def update_leaf(p, g, m, v, decayed):
        wd = weight_decay if decayed else 0.0
        # Pallas for the big matmul kernels; tiny 1D leaves (norm scales,
        # biases) aren't worth a kernel launch and stay in fused XLA
        if use_pallas and p.ndim >= 2 and p.size >= 1 << 16:
            return _update_leaf_pallas(p, g, m, v, scalars,
                                       b1=b1, b2=b2, eps=eps, wd=wd)
        return _leaf_math(p, g, m, v, lr, om1, om2,
                          jnp.asarray(clip_scale, jnp.float32),
                          b1=b1, b2=b2, eps=eps, wd=wd, mu_dtype=m.dtype,
                          nu_dtype=v.dtype)

    from ..parallel.sharding import current_mesh
    from ..utils.platform import kernel_impl, report_impl
    flat_p, treedef = jax.tree_util.tree_flatten(params)
    mesh = current_mesh()
    if use_pallas and mesh is not None and mesh.size > 1:
        # the kernel is a custom call GSPMD cannot partition ("Mosaic
        # kernels cannot be automatically partitioned" on the TPU
        # lowering), and the leaves' shardings are not known here: on a
        # multi-device mesh the same math runs as fused XLA elementwise
        # code, which partitions trivially — said out loud below
        use_pallas = False
        detail = (f"fused adamw, {dict(mesh.shape)} mesh: the Mosaic kernel "
                  "cannot be partitioned by GSPMD")
    else:
        n_kernel = sum(use_pallas and p.ndim >= 2 and p.size >= 1 << 16
                       for p in flat_p)
        use_pallas = bool(n_kernel)
        detail = (f"fused adamw, {n_kernel}/{len(flat_p)} leaves through "
                  "the kernel")
    report_impl("optimizer_update",
                kernel_impl() if use_pallas else "jnp", detail)
    flat_g = treedef.flatten_up_to(grads)
    flat_mu = treedef.flatten_up_to(mu)
    flat_nu = treedef.flatten_up_to(nu)
    flat_mask = treedef.flatten_up_to(decay_mask)
    out = [update_leaf(p, g, m, v, d) for p, g, m, v, d in
           zip(flat_p, flat_g, flat_mu, flat_nu, flat_mask)]
    new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_mu = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    new_nu = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
    return new_p, new_mu, new_nu
