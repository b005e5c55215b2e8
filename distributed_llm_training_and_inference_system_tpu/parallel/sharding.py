"""Sharding rules: param-pytree path -> PartitionSpec.

This is the executable form of what the reference only *plans*
(SURVEY §2.2: TP/PP/ZeRO exist solely as cost-model dimensions in
plan.py:73-125). Megatron-style tensor parallelism as data layout:

- column-parallel kernels (q/k/v, mlp gate/up): output dim on tp
- row-parallel kernels (o, mlp down): input dim on tp
- embedding: vocab on fsdp, hidden on tp (see PARAM_RULES comment)
- every 2D block kernel additionally shards its other dim on fsdp
  (ZeRO-3-style)
- untied lm_head: vocabulary-parallel, its output (vocab) dim on fsdp AND
  tp, hidden unsharded (see PARAM_RULES comment)
- MoE expert kernels put their leading E axis on ep
- stacked-layer leading axis goes on pp (when pipeline_parallel > 1 the
  pipeline runner re-slices it; for pp=1 it is just unsharded)

XLA/GSPMD then inserts the all-gathers/psums the reference would have had
to hand-write with NCCL.
"""

from __future__ import annotations

import math
import re
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (path regex, spec WITHOUT the stacked-layer axis). First match wins.
# Paths are dotted: e.g. "blocks.q.kernel", "embed.embedding".
PARAM_RULES: list[tuple[str, P]] = [
    # Embedding: vocab on fsdp, hidden on tp. The hidden dim must NOT carry
    # fsdp: activations shard batch on fsdp, so a hidden-fsdp gather output
    # forces GSPMD into "Involuntary full rematerialization" when resharding
    # to the activation spec (observed round 1 on the fsdp x sp x ep mesh).
    # Vocab-on-fsdp partitions the gather as mask+psum and the tied-logits
    # einsum ("bsh,vh->bsv", tie_word_embeddings) as a plain contraction —
    # verified warning-free on both dryrun regimes
    # (tests/test_parallel.py::test_no_involuntary_remat).
    (r"embed\.embedding$",        P("fsdp", "tp")),
    # The untied head [H, V] is vocabulary-parallel for the same reason:
    # fsdp on H would put it on the CONTRACTION of "bsh,hv->bsv" while the
    # rows shard on fsdp too, and GSPMD then moves the weight — inside the
    # chunked loss's scans, the whole head gathered and its whole gradient
    # all-reduced in every chunk (PERF.md 6, PR 34: 379 MB x 3 a chunk at
    # InternLM2's vocabulary). With the vocabulary on fsdp the loss gathers
    # a chunk's rows and reduces [B, chunk] softmax statistics.
    (r"lm_head\.kernel$",         P(None, ("fsdp", "tp"))),
    (r"final_norm\.scale$",       P(None)),
    # a layer table's stacks (one a layer KIND: blocks.ssm / .attn / .moe).
    # The state-space mixer's W_in is [z | xBC | dt] side by side, which tp
    # cannot cut head by head: fsdp alone, as W_out; its vectors replicate.
    (r"blocks\.ssm\.in_proj\.kernel$",  P("fsdp", None)),
    (r"blocks\.ssm\.out_proj\.kernel$", P(None, "fsdp")),
    (r"blocks\.ssm\.",                  P(None)),
    (r"blocks\.attn\.(q|k|v)\.kernel$", P("fsdp", "tp")),
    (r"blocks\.attn\.o\.kernel$",       P("tp", "fsdp")),
    (r"blocks\.moe\.shared\.up\.kernel$",   P("fsdp", "tp")),
    (r"blocks\.moe\.shared\.down\.kernel$", P("tp", "fsdp")),
    (r"blocks\.moe\.router\.bias$",    P(None)),
    (r"blocks\.(q|k|v)\.kernel$", P("fsdp", "tp")),
    (r"blocks\.(q|k|v)\.bias$",   P("tp")),
    # the q / k projection norm's scale lies along the projection's output
    # axis, which tp shards: GSPMD turns the norm's mean into an all-reduce
    (r"blocks\.(q|k)_norm\.scale$", P("tp")),
    (r"blocks\.o\.kernel$",       P("tp", "fsdp")),
    (r"blocks\.mlp\.(gate|up)\.kernel$", P("fsdp", "tp")),
    (r"blocks\.mlp\.down\.kernel$",      P("tp", "fsdp")),
    (r"blocks\.moe\.router\.kernel$",    P("fsdp", None)),
    (r"blocks\.moe\.(gate|up)\.kernel$", P("ep", "fsdp", "tp")),
    (r"blocks\.moe\.down\.kernel$",      P("ep", "tp", "fsdp")),
    (r"blocks\..*norm\.scale$",   P(None)),
    (r".*", P(None)),  # fallback: replicate
]

# Activation specs (logical names used by sharding constraints).
ACTIVATION_RULES: dict[str, P] = {
    # [B, S, H]: batch over dp+fsdp, sequence over sp
    "activations": P(("dp", "fsdp"), "sp", None),
    # [B, S, V]: logits vocab dim over tp
    "logits": P(("dp", "fsdp"), "sp", "tp"),
    # inside models.loss.chunked_next_token_loss, a chunk's logits
    # [B, chunk, V] and its rows [B, chunk, H] / targets [B, chunk]: the
    # vocabulary on the head's axes (PARAM_RULES lm_head), the rows on dp
    # alone. fsdp cannot shard both, and the rows are the small side: they
    # are gathered over fsdp, never the weight. "logits" above is the full
    # [B, S, V] of evaluation and tp serving and keeps its meaning.
    "loss_logits": P("dp", "sp", ("fsdp", "tp")),
    "loss_rows": P("dp", "sp", None),
    # [B, S] token/segment arrays
    "tokens": P(("dp", "fsdp"), "sp"),
}


def spec_for_path(path: str, stacked: bool = False) -> P:
    """PartitionSpec for a dotted param path. ``stacked`` prepends the
    layer axis (sharded on pp)."""
    for pattern, spec in PARAM_RULES:
        if re.search(pattern, path):
            if stacked and path.startswith("blocks."):
                return P("pp", *spec)
            return spec
    raise AssertionError("unreachable: catch-all rule")


def _shrink_to_fit(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop axis assignments that don't divide the dim (e.g. tp=4 on a
    3-dim) so tiny test models still shard cleanly."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        keep = []
        for a in axes:
            asize = mesh.shape[a]
            if shape[i] % (size * asize) == 0:
                keep.append(a)
                size *= asize
        out.append(tuple(keep) if len(keep) > 1 else (keep[0] if keep else None))
    # trailing Nones are implicit
    return P(*out)


def param_specs(params: Any, mesh: Mesh) -> Any:
    """PartitionSpec pytree matching *params* (stacked-layer layout).

    Quantized serving leaves shard like the plain kernels they replace
    (the round-2 engine refused quantized+tp entirely):

    - int8 ``QuantTensor``: values [L, in, out] get the kernel's spec;
      the per-(L, in) scale keeps the leading axes and replicates its
      size-1 tail.
    - int4 ``Quant4Tensor`` stores KERNEL-oriented packed nibbles
      [L, in/2, out] with group scales [L, in/group, out] and channel
      scales [L, in]: packed+scales take the kernel spec
      (layer, in_ax, out_ax) directly and chan takes (layer, in_ax) —
      the same tp/fsdp placement as the dequantized kernel.
    """
    from ..ops.quantization import Quant4Tensor, QuantTensor
    from ..utils.tree import path_str

    def is_q(x):
        return isinstance(x, (QuantTensor, Quant4Tensor))

    flat, treedef = jax.tree_util.tree_flatten_with_path(params,
                                                         is_leaf=is_q)
    leaves = []
    for path, leaf in flat:
        spec = spec_for_path(path_str(path), stacked=True)
        if isinstance(leaf, Quant4Tensor):
            layer_ax, in_ax, out_ax = (spec + (None, None, None))[:3]
            packed = _shrink_to_fit(P(layer_ax, in_ax, out_ax),
                                    leaf.packed.shape, mesh)
            scale = _shrink_to_fit(P(layer_ax, in_ax, out_ax),
                                   leaf.scale.shape, mesh)
            chan = _shrink_to_fit(P(layer_ax, in_ax), leaf.chan.shape,
                                  mesh)
            leaves.append(Quant4Tensor(packed, scale, chan,
                                       group=leaf.group))
        elif isinstance(leaf, QuantTensor):
            v = _shrink_to_fit(spec, leaf.values.shape, mesh)
            s = _shrink_to_fit(P(*v[:-1], None), leaf.scale.shape, mesh)
            leaves.append(QuantTensor(v, s))
        else:
            leaves.append(_shrink_to_fit(spec, leaf.shape, mesh))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def param_shardings(params: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(params, mesh))


def shard_params(params: Any, mesh: Mesh) -> Any:
    """Place a param pytree onto the mesh per the rules."""
    return jax.device_put(params, param_shardings(params, mesh))


def batch_specs(batch: Any, mesh: Mesh) -> Any:
    """Shard batch arrays: [B, S, ...] over (dp,fsdp) x sp; rank-1 [B]
    arrays (e.g. cache offsets) over (dp,fsdp) only; scalars replicated."""
    def spec(x):
        if x.ndim == 0:
            return P()
        if x.ndim == 1:
            return _shrink_to_fit(P(("dp", "fsdp")), x.shape, mesh)
        s = ACTIVATION_RULES["tokens"]
        return _shrink_to_fit(P(*s, *(None,) * (x.ndim - 2)), x.shape, mesh)
    return jax.tree_util.tree_map(spec, batch)


def shard_batch(batch: Any, mesh: Mesh) -> Any:
    return jax.device_put(
        batch,
        jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                               batch_specs(batch, mesh)))


def _activation_spec(name: str, shape: tuple[int, ...],
                     mesh: Optional[Mesh]) -> Optional[P]:
    """ACTIVATION_RULES[name] cut to this shape on *mesh* or the ambient
    one; None where there is no mesh to shard over."""
    mesh = mesh or current_mesh()
    if mesh is None or mesh.empty or mesh.size == 1:
        return None
    return _shrink_to_fit(P(*ACTIVATION_RULES[name][: len(shape)]), shape,
                          mesh)


def constrain(x: jax.Array, name: str, mesh: Optional[Mesh] = None) -> jax.Array:
    """Apply a named activation sharding constraint (no-op outside a mesh).

    Used inside model forward to anchor GSPMD propagation at block
    boundaries — the TPU replacement for hand-placed NCCL calls.
    """
    spec = _activation_spec(name, x.shape, mesh)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh or current_mesh(), spec))


def shard_counts(name: str, shape: tuple[int, ...],
                 mesh: Optional[Mesh] = None) -> tuple[int, ...]:
    """Over how many devices ``constrain(x, name)`` spreads each axis of an
    *x* of this shape (1 everywhere outside a mesh)."""
    spec = _activation_spec(name, shape, mesh)
    if spec is None:
        return (1,) * len(shape)
    sizes = (mesh or current_mesh()).shape
    entries = tuple(spec) + (None,) * (len(shape) - len(spec))
    return tuple(
        math.prod(sizes[a] for a in (e if isinstance(e, tuple) else (e,)))
        if e else 1 for e in entries)


# -- ambient mesh (context) --------------------------------------------------

import contextlib
import threading

_ctx = threading.local()


def current_mesh() -> Optional[Mesh]:
    """The mesh made ambient by ``use_mesh`` on this thread, or None."""
    return getattr(_ctx, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Make *mesh* ambient so models/ops can place sharding constraints
    without threading a mesh argument through every call."""
    prev = current_mesh()
    _ctx.mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _ctx.mesh = prev
