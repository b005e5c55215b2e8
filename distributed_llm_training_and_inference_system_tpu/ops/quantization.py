"""Quantization ops: absmax int8 and blockwise int4 (pack/unpack).

Parity: the reference's export command advertises int8-awq / int4-gptq
quantization but is a "coming soon" stub (reference cli/commands/export.py:29,
SURVEY §2 row 18). These are real, XLA-compilable quantizers used by
``llmctl export`` and the serving KV cache.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.annotations import np_host_only, np_twin_of


def quantize_int8_rows(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric absmax int8 over the LAST axis, scale WITHOUT keepdims:
    (values int8 [..., D], scale fp32 [...]).

    Pure jnp elementwise/reduce — safe to call both from traced XLA code
    and from inside Pallas kernel bodies (the KV quantize-on-write and
    the in-kernel dequant must share one definition of the absmax math,
    or the fused write path and the reference path drift)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8_rows(q: jax.Array, scale: jax.Array,
                         dtype=jnp.float32) -> jax.Array:
    """Inverse of quantize_int8_rows: values [..., D] * scale [...]."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def quantize_int8(x: jax.Array, axis: int = -1) -> tuple[jax.Array, jax.Array]:
    """Symmetric absmax int8 quantization along *axis*.

    Returns (values int8, scales float32) with x ≈ values * scales.
    """
    if axis in (-1, x.ndim - 1):
        q, scale = quantize_int8_rows(x)
        return q, scale[..., None]
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def quantize_int4_blockwise(x: jax.Array, block: int = 32) -> tuple[jax.Array, jax.Array]:
    """Blockwise symmetric int4, packed two nibbles per uint8.

    The trailing axis must be divisible by *block*. Returns
    (packed uint8 of shape [..., n/2], scales float32 of shape [..., n/block]).
    """
    n = x.shape[-1]
    if n % block != 0:
        raise ValueError(f"last dim {n} not divisible by block {block}")
    xb = x.astype(jnp.float32).reshape(*x.shape[:-1], n // block, block)
    absmax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax / 7.0, 1e-12)
    q = jnp.clip(jnp.round(xb / scale), -7, 7).astype(jnp.int8)
    q = q.reshape(*x.shape[:-1], n)
    # pack pairs: low nibble = even index, high nibble = odd index
    lo = (q[..., 0::2] & 0xF).astype(jnp.uint8)
    hi = (q[..., 1::2] & 0xF).astype(jnp.uint8)
    packed = (lo | (hi << 4)).astype(jnp.uint8)
    return packed, scale[..., 0].astype(jnp.float32)


def _unnibble(v: jax.Array) -> jax.Array:
    """Sign-extend a 4-bit two's-complement nibble (shared by both int4
    dequant paths — the encoding must never diverge between them)."""
    v = v.astype(jnp.int8)
    return jnp.where(v >= 8, v - 16, v)


# -- int4 KV rows (Int4Pages — ops/paged_attention.py) ------------------------
#
# The KV-cache flavor of int4: per-(token, kv-head) absmax over head_dim
# (same row granularity as the int8 quantize_int8_rows path, so the
# per-page scale tile keeps the kernel-friendly [.., Nkv, PS] layout from
# round 6), with nibbles packed pairwise along the PAGE-SLOT axis — two
# consecutive tokens share one byte. Packing along PS (not D) keeps
# head_dim on the minor axis, so the Pallas page tile stays a full
# 128-lane vector and unpack is a sublane relabel, exactly the lesson the
# weight-side [.., in/2, out] layout already paid for (the round-3
# transpose-in-the-scan disaster documented on quantize_int4_groupwise).


def quantize_int4_rows(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric absmax int4 over the LAST axis: (values int8 in [-7, 7]
    [..., D], scale fp32 [...]). The int4 sibling of quantize_int8_rows —
    pure jnp, safe both traced and inside Pallas kernel bodies. Values
    stay UNPACKED int8 here; pack_int4_rows pairs them along a chosen
    axis (the write path packs along the page-slot axis)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(absmax / 7.0, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -7, 7).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def pack_int4_rows(q: jax.Array, axis: int = -2) -> jax.Array:
    """Pack int4-valued int8 rows pairwise along ``axis``: element 2i ->
    low nibble, 2i+1 -> high nibble of byte i. An ODD count along the
    axis pads one zero row (the unpacked tail reads back as 0; callers
    slicing with ``unpack_int4_rows(..., n=odd)`` never see it)."""
    axis = axis % q.ndim
    n = q.shape[axis]
    if n % 2:
        pad = [(0, 0)] * q.ndim
        pad[axis] = (0, 1)
        q = jnp.pad(q, pad)
    lo = (jax.lax.slice_in_dim(q, 0, None, 2, axis) & 0xF).astype(jnp.uint8)
    hi = (jax.lax.slice_in_dim(q, 1, None, 2, axis) & 0xF).astype(jnp.uint8)
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_int4_rows(packed: jax.Array, axis: int = -2,
                     n: int | None = None) -> jax.Array:
    """Inverse of pack_int4_rows: uint8 bytes -> sign-extended int8 rows
    interleaved along ``axis`` (count doubles; ``n`` trims a padded odd
    tail). stack+reshape is a free row-major relabel along the packed
    axis — no transpose, fusable into the consuming dequant."""
    axis = axis % packed.ndim
    lo = _unnibble(packed & 0xF)
    hi = _unnibble(packed >> 4)
    q = jnp.stack([lo, hi], axis=axis + 1)
    shape = (*packed.shape[:axis], packed.shape[axis] * 2,
             *packed.shape[axis + 1:])
    q = q.reshape(shape)
    if n is not None and n < shape[axis]:
        q = jax.lax.slice_in_dim(q, 0, n, 1, axis)
    return q


def dequantize_int4_rows(packed: jax.Array, scale: jax.Array,
                         dtype=jnp.float32) -> jax.Array:
    """Inverse of quantize_int4_rows+pack_int4_rows for the KV layout:
    packed [..., PS/2, D] uint8 * row scales [..., PS] -> [..., PS, D].
    Shared by the write-path round-trip checks and the Pallas kernel
    body (one definition of the nibble math, like the int8 pair)."""
    q = unpack_int4_rows(packed, axis=-2, n=scale.shape[-1])
    return (q.astype(jnp.float32) * scale[..., :, None]).astype(dtype)


def dequantize_int4_blockwise(packed: jax.Array, scale: jax.Array,
                              block: int = 32, dtype=jnp.bfloat16) -> jax.Array:
    lo = _unnibble(packed & 0xF)
    hi = _unnibble(packed >> 4)
    n = packed.shape[-1] * 2
    q = jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1], n)
    qb = q.reshape(*q.shape[:-1], n // block, block).astype(jnp.float32)
    out = qb * scale[..., None]
    return out.reshape(*q.shape[:-1], n).astype(dtype)


# -- courier codec helpers (numpy, host-side) ---------------------------------
#
# The fleet courier's ``delta-zlib`` wire codec (serve/fleet/transport.py)
# delta-encodes quantized KV page planes before per-chunk zlib: adjacent
# page slots hold KV for adjacent tokens, whose quantized values are
# strongly correlated (CacheGen, PAPERS.md), so per-plane deltas along
# the page-slot axis concentrate near zero and the byte stream becomes
# highly compressible. These are the NUMPY twins of the jnp nibble
# helpers above — ONE definition of the nibble/byte layout (element 2i =
# low nibble, 2i+1 = high nibble, packed along the page-slot axis, D
# minor) shared by the write path, the gather fallback, and the wire
# codec; tests pin the np pack/unpack against the jnp pair so the codec
# can never disagree with the cache about where a token's bytes live.
# All four transforms are size-preserving bijections in modular
# arithmetic (mod-256 bytes for int8 values, mod-16 nibbles for packed
# int4), so the codec applies them blindly and the courier's end-to-end
# CRC over the RAW bytes still proves correctness after the inverse.


@np_host_only("token-axis delta filter exists only in the courier wire "
              "codec (host-side); the device never sees delta-coded "
              "planes")
def delta_encode_planes_np(a: np.ndarray, axis: int = -2) -> np.ndarray:
    """Mod-256 first-difference along ``axis`` (the page-slot axis of an
    int8 KV plane [..., PS, D]): row i becomes row_i - row_{i-1}, row 0
    is kept. Byte-wraparound arithmetic makes this a bijection for any
    1-byte dtype; the inverse is :func:`delta_decode_planes_np`."""
    u = np.ascontiguousarray(a).view(np.uint8)
    out = u.copy()
    axis = axis % u.ndim
    hi = [slice(None)] * u.ndim
    lo = [slice(None)] * u.ndim
    hi[axis] = slice(1, None)
    lo[axis] = slice(None, -1)
    out[tuple(hi)] = u[tuple(hi)] - u[tuple(lo)]     # wraps mod 256
    return out.view(a.dtype)


@np_host_only("inverse of the host-side courier delta filter")
def delta_decode_planes_np(a: np.ndarray, axis: int = -2) -> np.ndarray:
    """Inverse of :func:`delta_encode_planes_np`: mod-256 prefix sum."""
    u = np.ascontiguousarray(a).view(np.uint8)
    out = np.add.accumulate(u, axis=axis % u.ndim, dtype=np.uint8)
    return out.view(a.dtype)


@np_twin_of("unpack_int4_rows")
def unpack_nibbles_np(packed: np.ndarray, axis: int = -2) -> np.ndarray:
    """uint8 bytes -> RAW nibbles (0..15, NO sign extension) interleaved
    along ``axis`` (count doubles) — the same 2i=low/2i+1=high layout as
    :func:`unpack_int4_rows`, kept unsigned so modular nibble arithmetic
    stays trivially bijective."""
    axis = axis % packed.ndim
    lo = (packed & 0xF).astype(np.uint8)
    hi = (packed >> 4).astype(np.uint8)
    q = np.stack([lo, hi], axis=axis + 1)
    shape = (*packed.shape[:axis], packed.shape[axis] * 2,
             *packed.shape[axis + 1:])
    return q.reshape(shape)


@np_twin_of("pack_int4_rows")
def pack_nibbles_np(q: np.ndarray, axis: int = -2) -> np.ndarray:
    """Inverse of :func:`unpack_nibbles_np` (element 2i -> low nibble,
    2i+1 -> high nibble of byte i; the :func:`pack_int4_rows` layout)."""
    axis = axis % q.ndim
    even = [slice(None)] * q.ndim
    odd = [slice(None)] * q.ndim
    even[axis] = slice(0, None, 2)
    odd[axis] = slice(1, None, 2)
    lo = (q[tuple(even)] & 0xF).astype(np.uint8)
    hi = (q[tuple(odd)] & 0xF).astype(np.uint8)
    return (lo | (hi << 4)).astype(np.uint8)


@np_host_only("mod-16 nibble delta filter exists only in the courier "
              "wire codec (host-side)")
def nibble_delta_encode_np(packed: np.ndarray,
                           axis: int = -2) -> np.ndarray:
    """Mod-16 first-difference over the UNPACKED nibble stream of a
    packed-int4 plane ([..., PS/2, D] -> nibbles along the page-slot
    axis -> deltas -> repacked). Size-preserving and bijective; adjacent
    tokens' int4 values differ by small amounts, so the delta nibbles
    cluster around 0/15 and zlib bites."""
    axis = axis % packed.ndim
    q = unpack_nibbles_np(packed, axis)
    out = q.copy()
    hi = [slice(None)] * q.ndim
    lo = [slice(None)] * q.ndim
    hi[axis] = slice(1, None)
    lo[axis] = slice(None, -1)
    out[tuple(hi)] = (q[tuple(hi)] - q[tuple(lo)]) & 0xF
    return pack_nibbles_np(out, axis)


@np_host_only("inverse of the host-side mod-16 nibble delta filter")
def nibble_delta_decode_np(packed: np.ndarray,
                           axis: int = -2) -> np.ndarray:
    """Inverse of :func:`nibble_delta_encode_np`: mod-16 prefix sum over
    the nibble stream (mod-256 accumulate & 0xF — 16 divides 256, so the
    residues agree), then repack."""
    axis = axis % packed.ndim
    q = unpack_nibbles_np(packed, axis)
    out = np.add.accumulate(q, axis=axis, dtype=np.uint8) & 0xF
    return pack_nibbles_np(out, axis)


def quantize_int4_groupwise(
    w: jax.Array,            # [..., in, out] kernel(s)
    group: int = 128,
    act_scale: jax.Array | None = None,   # [..., in] AWQ channel statistic
    alpha: float = 0.5,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Group-wise symmetric int4 along the INPUT axis (the matmul reduction
    dim — the W4A16 convention: each [group]-sized slice of input channels
    shares one scale, so dequant error stays local to a partial sum).

    With ``act_scale`` the AWQ channel trick is applied first (salient
    input channels scaled up before quantization, inverse folded into
    dequant) — the int4 counterpart of quantize_int8_awq and the real
    version of the reference's stubbed ``--quant int4-gptq`` choice
    (reference llmctl/cli/commands/export.py:23-29).

    Storage is KERNEL-oriented: packed uint8 [..., in/2, out] (nibble pair
    (2i, 2i+1) of input channels at row i), scales fp32 [..., in/group,
    out], chan fp32 [..., in]. The first round-3 chip measurement of the
    original [..., out, in/2] layout showed why this matters: its dequant
    needed a per-layer fp32 ``swapaxes`` of every kernel INSIDE the decode
    scan, turning W4A16 into 19.6 tok/s vs bf16's 91 — the transpose
    materialised ~8x the traffic int4 was supposed to save. The quant-time
    transpose below is one-time; dequant is a pure elementwise chain in
    the matmul's own orientation.

    Returns (packed, scale, chan); W ≈ unpack(packed)*scales / chan[:,None].
    """
    if act_scale is not None:
        chan = act_scale.astype(jnp.float32) ** alpha
        chan = chan / jnp.exp(jnp.mean(jnp.log(chan), axis=-1, keepdims=True))
    else:
        chan = jnp.ones(w.shape[:-2] + (w.shape[-2],), jnp.float32)
    w_scaled = w.astype(jnp.float32) * chan[..., :, None]
    wt = jnp.swapaxes(w_scaled, -1, -2)            # [..., out, in]
    packed, scale = quantize_int4_blockwise(wt, block=group)
    packed = jnp.swapaxes(packed, -1, -2)          # [..., in/2, out]
    scale = jnp.swapaxes(scale, -1, -2)            # [..., in/group, out]
    return packed, scale, chan


def dequantize_int4_groupwise(packed: jax.Array, scale: jax.Array,
                              chan: jax.Array, group: int = 128,
                              dtype=jnp.bfloat16) -> jax.Array:
    """Inverse of quantize_int4_groupwise -> [..., in, out].

    Transpose-free: nibble pairs interleave along the second-minor axis,
    so ``stack(axis=-2) + reshape`` is a free row-major relabel and the
    whole unpack * scale * (1/chan) chain stays elementwise in *dtype* —
    fusable into the consuming matmul's operand read."""
    lo = _unnibble(packed & 0xF)                   # input channels 2i
    hi = _unnibble(packed >> 4)                    # input channels 2i+1
    n = packed.shape[-2] * 2
    out = packed.shape[-1]
    q = jnp.stack([lo, hi], axis=-2).reshape(*packed.shape[:-2], n, out)
    qg = q.reshape(*q.shape[:-2], n // group, group, out).astype(dtype)
    w = (qg * scale[..., :, None, :].astype(dtype)).reshape(q.shape)
    inv_chan = (1.0 / chan).astype(dtype)
    return w * inv_chan[..., :, None]


@jax.tree_util.register_pytree_node_class
class Quant4Tensor:
    """Runtime form of a W4A16 weight: packed int4 nibbles + group scales
    (+ AWQ channel scales), registered as a pytree so it rides the stacked-
    layer ``lax.scan`` like QuantTensor. Storage is kernel-oriented
    ([..., in/2, out] — see quantize_int4_groupwise). Logical shape/ndim
    are the ORIGINAL kernel's ([..., in, out]) so shape-inspecting code
    (sharding rules, planners) sees the matmul geometry, not the packed
    layout."""

    def __init__(self, packed, scale, chan, group: int = 128):
        self.packed = packed
        self.scale = scale
        self.chan = chan
        self.group = group

    @property
    def shape(self):
        s = self.packed.shape            # [..., in/2, out]
        return (*s[:-2], s[-2] * 2, s[-1])

    @property
    def ndim(self):
        return self.packed.ndim

    def dequant(self, dtype=jnp.bfloat16):
        return dequantize_int4_groupwise(self.packed, self.scale, self.chan,
                                         self.group, dtype)

    def tree_flatten(self):
        return (self.packed, self.scale, self.chan), self.group

    @classmethod
    def tree_unflatten(cls, group, children):
        return cls(*children, group=group)


@jax.tree_util.register_pytree_node_class
class QuantTensor:
    """Runtime form of an int8 weight: (values int8, scale fp32), leaves of
    a registered pytree so it can ride through ``jax.lax.scan`` over the
    stacked-layer axis (the dict-marked export form carries a string tag,
    which scan xs cannot). ``W ~= values * scale``."""

    def __init__(self, values, scale):
        self.values = values
        self.scale = scale

    @property
    def shape(self):
        return self.values.shape

    @property
    def ndim(self):
        return self.values.ndim

    def dequant(self, dtype=jnp.bfloat16):
        return dequantize_int8(self.values, self.scale, dtype)

    def tree_flatten(self):
        return (self.values, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _is_quant_marker(x: Any) -> bool:
    return isinstance(x, dict) and "__quant__" in x


def to_runtime_quant(tree: Any) -> Any:
    """Convert export-form ``{"__quant__": ..., values, scale}`` leaves
    into scan-compatible QuantTensor / Quant4Tensor leaves.

    ``int8-awq`` markers are REFUSED, not silently narrowed: dropping the
    ``chan`` channel scaling the exporter divided out would serve garbage
    weights with no error (awq is an interchange format — the serve
    runtime consumes int8 / int4 / int4-awq, whose awq scaling is already
    folded into the stored values)."""
    def conv(x):
        if not _is_quant_marker(x):
            return x
        kind = x["__quant__"]
        if kind == "int4":
            return Quant4Tensor(x["values"], x["scale"], x["chan"],
                                group=int(x.get("group", 128)))
        if kind == "int8":
            return QuantTensor(x["values"], x["scale"])
        raise ValueError(
            f"quant marker {kind!r} has no runtime form (int8-awq "
            "artifacts are interchange-only; re-export as int8 or int4)")
    return jax.tree_util.tree_map(conv, tree, is_leaf=_is_quant_marker)


def _is_runtime_quant(x: Any) -> bool:
    return isinstance(x, (QuantTensor, Quant4Tensor))


def cast_params(tree: Any, dtype, keep_w4: bool = False,
                keep_w8: bool = False) -> Any:
    """Cast a (possibly mixed plain/Quant[4]Tensor) param tree for compute:
    plain leaves are cast; quantized leaves are DEQUANTIZED. Call this
    per layer inside the scan body so only one layer's bf16 weights are
    ever materialised (the whole-tree int8/int4 storage saving survives).

    ``keep_w4=True`` passes Quant4Tensor leaves through UN-dequantized —
    for consumers routing them into the in-kernel-dequant Pallas matmul
    (ops.int4_matmul_pallas), where the XLA dequant chain's 2.5x-bf16 HBM
    round trip (the round-3/4 measured int4 slowdown) never happens.
    ``keep_w8=True`` is the int8 counterpart (ops.int8_matmul_pallas,
    the same ~5x-int8-bytes dequant round trip measured as gpt-7b's
    40.8 ms decode step, battery 8)."""
    def one(x):
        if isinstance(x, Quant4Tensor) and keep_w4:
            return x
        if isinstance(x, QuantTensor) and keep_w8:
            return x
        if _is_runtime_quant(x):
            return x.dequant(dtype)
        return x.astype(dtype)
    return jax.tree_util.tree_map(one, tree, is_leaf=_is_runtime_quant)


def precast_params(tree: Any, dtype) -> Any:
    """Cast PLAIN leaves to the compute dtype, leaving quantized leaves
    quantized. Run this once OUTSIDE the layer scan: casting inside the
    scan body would stream the fp32 master weights from HBM every layer
    (measured -0.05 MFU on the training step, BASELINE.md round 2); the
    int8/int4 leaves still dequantize per-layer inside the body via
    ``cast_params``."""
    def one(x):
        if _is_runtime_quant(x):
            return x
        return x.astype(dtype)
    return jax.tree_util.tree_map(one, tree, is_leaf=_is_runtime_quant)


def tree_weight_bytes(tree: Any) -> int:
    """HBM bytes of a param tree (QuantTensor counts its int8 + scale)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += leaf.size * leaf.dtype.itemsize
    return int(total)


def quantize_tree_int8(params: Any, min_size: int = 4096,
                       min_ndim: int = 2) -> Any:
    """Quantize every large float leaf of a param pytree to (int8, scale).

    Small leaves (norm scales, biases) stay in their original dtype. For
    STACKED-layer trees (kernels [L, in, out]) pass ``min_ndim=3``: norm
    scales and attention biases are [L, H]-shaped and big enough to pass
    the size filter, but quantizing them buys ~0.002% of the memory for a
    per-layer precision hit on every normalization.
    """
    def q(x):
        if (hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
                and x.size >= min_size and x.ndim >= min_ndim):
            values, scale = quantize_int8(x)
            return {"__quant__": "int8", "values": values, "scale": scale}
        return x
    return jax.tree_util.tree_map(q, params)


def quantize_tree_int4(params: Any, model_cfg=None,
                       calib_tokens: jax.Array | None = None,
                       group: int = 128, alpha: float = 0.5,
                       min_size: int = 4096) -> Any:
    """Group-wise int4 (W4A16) over a FULL param pytree; only the stacked
    [L, in, out] block kernels quantize (embedding/lm_head/norms keep full
    precision — same policy as the int8 path). Odd input dims fall back
    to int8.

    With ``model_cfg`` + ``calib_tokens`` the AWQ channel statistic is
    calibrated (activation_channel_scales, needs the full tree) and
    applied to the kernels it covers. Group size is clamped to the input
    dim when needed."""
    act = {}
    if model_cfg is not None and calib_tokens is not None:
        act = activation_channel_scales(params, model_cfg, calib_tokens)

    def q(path_entries, x):
        path = ".".join(str(getattr(k, "key", k)) for k in path_entries)
        if (hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
                and x.size >= min_size and x.ndim == 3):   # [L, in, out]
            g = group
            while x.shape[-2] % g and g > 2:
                g //= 2
            if x.shape[-2] % g or g < 2:
                return quantize_tree_int8(x, min_size=min_size, min_ndim=3)
            packed, scale, chan = quantize_int4_groupwise(
                x, group=g, act_scale=act.get(path), alpha=alpha)
            return {"__quant__": "int4", "values": packed, "scale": scale,
                    "chan": chan, "group": g}
        # norm scales / biases ([L, H]) stay full precision, mirroring
        # the engine's int8 path (min_ndim=3)
        return (quantize_tree_int8(x, min_size=min_size, min_ndim=3)
                if hasattr(x, "dtype") else x)

    return jax.tree_util.tree_map_with_path(q, params)


def dequantize_tree(params: Any, dtype=jnp.bfloat16) -> Any:
    def is_qleaf(x):
        return isinstance(x, dict) and str(
            x.get("__quant__", "")).startswith(("int8", "int4"))

    def dq(x):
        if is_qleaf(x):
            if x["__quant__"] == "int4":
                return dequantize_int4_groupwise(
                    x["values"], x["scale"], x["chan"],
                    group=int(x.get("group", 128)), dtype=dtype)
            if x["__quant__"] == "int8-awq":
                return dequantize_int8_awq(x["values"], x["scale"],
                                           x["chan"], dtype)
            return dequantize_int8(x["values"], x["scale"], dtype)
        return x
    return jax.tree_util.tree_map(dq, params, is_leaf=is_qleaf)


def quantization_error(x: np.ndarray, block: int | None = None) -> float:
    """Relative L2 error of int8 round-trip (used by `llmctl export --verify`)."""
    xj = jnp.asarray(x)
    q, s = quantize_int8(xj)
    back = dequantize_int8(q, s, jnp.float32)
    num = float(jnp.linalg.norm((back - xj.astype(jnp.float32))))
    den = float(jnp.linalg.norm(xj.astype(jnp.float32))) + 1e-12
    return num / den


def activation_channel_scales(
    params: Any, model_cfg, calib_tokens: jax.Array,
) -> dict[str, jax.Array]:
    """Per-input-channel activation RMS for the projection kernels, from one
    calibration forward pass — the "activation-aware" statistic AWQ scales
    by (channels carrying large activations keep more precision). Params use
    the stacked-layer layout (kernels [L, in, out]), so this returns
    {stacked param path: [L, in_features] fp32} for the q/k/v and mlp
    gate/up/down kernels (o and MoE expert kernels keep plain absmax: o's
    input is attention's output, and experts are token-routed).

    The pass drives ``models.layers.decoder_block`` with a matmul that
    records what it is given: the kernels to calibrate go in as
    (path, kernel) pairs.
    """
    from ..models.layers import attend_fresh, decoder_block, rope_frequencies

    compute_dtype = jnp.dtype(model_cfg.dtype)
    x = params["embed"]["embedding"][calib_tokens].astype(compute_dtype)
    inv_freq = rope_frequencies(model_cfg.head_dim, model_cfg.rope.base,
                                model_cfg.rope.scaling,
                                model_cfg.rope.scaling_factor)
    B, S = calib_tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, axis=0)
    calibrated = {f"blocks.{n}.kernel" for n in (
        "q", "k", "v", "mlp.gate", "mlp.up", "mlp.down")}
    per_layer: dict[str, list[jax.Array]] = {}

    def recording_matmul(a, w):
        if isinstance(w, tuple):
            path, w = w
            per_layer.setdefault(path, []).append(jnp.sqrt(jnp.mean(
                a.astype(jnp.float32) ** 2,
                axis=tuple(range(a.ndim - 1)))) + 1e-6)
        return a @ w

    def layer_of(i):
        def one(path_entries, p):
            path = "blocks." + ".".join(str(k.key) for k in path_entries)
            p = p[i].astype(compute_dtype)
            return (path, p) if path in calibrated else p
        return jax.tree_util.tree_map_with_path(one, params["blocks"])

    for i in range(model_cfg.num_layers):
        x, _, _ = decoder_block(
            x, layer_of(i), model_cfg, positions, inv_freq,
            attend_fresh(positions, None, block=model_cfg.attention_block),
            matmul=recording_matmul)
    return {k: jnp.stack(v) for k, v in per_layer.items()}   # [L, in]


def quantize_int8_awq(
    w: jax.Array,            # [..., in, out] kernel(s)
    act_scale: jax.Array,    # [..., in] per-input-channel activation RMS
    alpha: float = 0.5,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Activation-aware int8: scale salient input channels UP before absmax
    quantization (AWQ's s = act^alpha, normalised), so channels that carry
    large activations keep more mantissa; the inverse scale folds into
    dequant. Returns (q int8, scales fp32 per-out-channel, chan fp32
    [..., in]). W ≈ (q * scales) / chan[..., None]."""
    s = act_scale.astype(jnp.float32) ** alpha
    s = s / jnp.exp(jnp.mean(jnp.log(s), axis=-1, keepdims=True))  # geomean=1
    w_scaled = w.astype(jnp.float32) * s[..., :, None]
    q, scales = quantize_int8(w_scaled, axis=-2)   # per-out-channel absmax
    return q, scales, s


def dequantize_int8_awq(q: jax.Array, scales: jax.Array, chan: jax.Array,
                        dtype=jnp.bfloat16) -> jax.Array:
    """Inverse of quantize_int8_awq."""
    return ((q.astype(jnp.float32) * scales)
            / chan[..., :, None]).astype(dtype)


def quantize_tree_int8_awq(params: Any, model_cfg, calib_tokens: jax.Array,
                           alpha: float = 0.5, min_size: int = 4096) -> Any:
    """AWQ-style activation-aware int8 over a param pytree.

    Kernels with a calibrated activation statistic get channel-scaled
    quantization (quantize_int8_awq); everything else falls back to plain
    absmax. Reference parity: the `int8-awq` flag of the reference's
    stubbed `export convert` (reference cli/commands/export.py:29)."""
    act = activation_channel_scales(params, model_cfg, calib_tokens)

    def q(path_entries, x):
        path = ".".join(str(getattr(k, "key", k)) for k in path_entries)
        if (path in act and hasattr(x, "dtype")
                and jnp.issubdtype(x.dtype, jnp.floating)
                and x.size >= min_size and x.ndim == 3):   # [L, in, out]
            qv, scales, chan = quantize_int8_awq(x, act[path], alpha=alpha)
            return {"__quant__": "int8-awq", "values": qv,
                    "scale": scales, "chan": chan}
        return quantize_tree_int8(x, min_size=min_size)

    return jax.tree_util.tree_map_with_path(q, params)
