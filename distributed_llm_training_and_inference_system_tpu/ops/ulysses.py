"""Ulysses (DeepSpeed-style) sequence parallelism: all-to-all head scatter.

The second context-parallel scheme SURVEY §5.7 names (the reference has
neither; grep finds zero hits for ulysses/ring). Complementary to ring
attention (ops/ring_attention.py):

- **ring**: KV chunks rotate sp times over neighbour ICI links; memory is
  S-linear per device; comm volume ~ sp * local KV. Best at very long S.
- **ulysses**: ONE all_to_all re-partitions [B, S/sp, N, D] activations
  into [B, S, N/sp, D] — each device then runs FULL-sequence attention
  over its head subset, and a second all_to_all restores the sequence
  sharding. Two collectives total (plus their transposes in backward),
  no per-step ring latency; requires num heads % sp == 0 and holds the
  full sequence per device inside attention (fine to ~32k; the
  [S, D]-per-head working set still streams blockwise through the flash
  kernel, so only q/k/v/o activations are full-S).

Positions/segments for the full sequence are rebuilt with an all_gather
over 'sp' (tiny [B, S] int32 arrays). Differentiates through jax
collectives + the flash custom-vjp — no hand-written backward needed.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .attention import flash_attention


def _ulysses_body(q, k, v, pos, seg, axis_name, block_q, block_k):
    """Per-shard body. q/k/v: [B, S_local, N, D]; pos/seg: [B, S_local]."""
    sp = lax.axis_size(axis_name)
    B, S_local, Nq, D = q.shape
    Nkv = k.shape[2]

    def scatter_heads(x):
        # [B, s, n, D] -> [B, s*sp, n/sp, D]: concat sequence chunks from
        # every rank, keep 1/sp of the heads
        n_local = x.shape[2] // sp
        # split heads into sp groups along a new leading axis for a2a
        xg = x.reshape(B, S_local, sp, n_local, D)
        # all_to_all: exchange the head-group axis for the sequence axis
        xg = lax.all_to_all(xg, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
        return xg.reshape(B, S_local * sp, n_local, D)

    def gather_heads(x):
        # inverse: [B, S, n/sp, D] -> [B, S/sp, n, D]
        S = x.shape[1]
        xg = x.reshape(B, sp, S // sp, x.shape[2], D)
        xg = lax.all_to_all(xg, axis_name, split_axis=1, concat_axis=3,
                            tiled=True)
        return xg.reshape(B, S // sp, x.shape[2] * sp, D)

    qf = scatter_heads(q)
    kf = scatter_heads(k)
    vf = scatter_heads(v)
    pos_full = lax.all_gather(pos, axis_name, axis=1, tiled=True)   # [B, S]
    seg_full = lax.all_gather(seg, axis_name, axis=1, tiled=True)

    out = flash_attention(qf, kf, vf, segment_ids=seg_full,
                          positions=pos_full, causal=True,
                          block_q=block_q, block_k=block_k)
    return gather_heads(out)


def ulysses_attention(
    q: jax.Array,                      # [B, S_local, Nq, D] (seq on 'sp')
    k: jax.Array,
    v: jax.Array,
    positions: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    axis_name: str = "sp",
    block_q: int = 512,
    block_k: int = 512,
) -> jax.Array:
    """Causal Ulysses attention under the ambient mesh; with no mesh or
    sp == 1 it reduces to plain flash attention."""
    from ..parallel.sharding import current_mesh

    B, S, Nq, D = q.shape
    Nkv = k.shape[2]
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, axis=0)
    if segment_ids is None:
        segment_ids = jnp.ones((B, S), jnp.int32)
    positions = positions.astype(jnp.int32)
    segment_ids = segment_ids.astype(jnp.int32)

    mesh = current_mesh()
    sp = 1 if mesh is None else mesh.shape.get(axis_name, 1)
    if sp == 1:
        return flash_attention(q, k, v, segment_ids=segment_ids,
                               positions=positions, causal=True,
                               block_q=block_q, block_k=block_k)
    if Nq % sp or Nkv % sp:
        raise ValueError(
            f"ulysses needs heads divisible by sp={sp} (got Nq={Nq}, "
            f"Nkv={Nkv}); use attn_impl='ring' for this mesh")

    qspec = P(("dp", "fsdp"), axis_name, None, None)
    sspec = P(("dp", "fsdp"), axis_name)

    def body(q_, k_, v_, pos_, seg_):
        return _ulysses_body(q_, k_, v_, pos_, seg_, axis_name,
                             block_q, block_k)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(qspec, qspec, qspec, sspec, sspec),
        out_specs=qspec, check_vma=False)
    return fn(q, k, v, positions, segment_ids)
