"""Transformer building blocks, pure-functional JAX.

The reference delegates all modeling to HuggingFace AutoModelForCausalLM
(reference runtime/engine.py:119-140, serve/server.py:146-170); this module
implements the architecture described by its model configs
(reference configs/models/llama-7b.json: RMSNorm, RoPE, multi-head attention,
SwiGLU) natively: functions over explicit param pytrees, bf16-compute/
fp32-master friendly, XLA-fusable, with hooks for Pallas kernels in ops/.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..config.schema import ModelConfig
from ..utils.platform import kernel_impl, report_impl

Params = Any  # nested dict pytree of jnp arrays


# ---------------------------------------------------------------------------
# Normalisation
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5,
             impl: str = "xla") -> jax.Array:
    """RMSNorm. Reduction in fp32 regardless of activation dtype."""
    if impl == "pallas":
        from ..ops.rmsnorm import rms_norm_pallas
        report_impl("rms_norm", kernel_impl(), f"x{tuple(x.shape)}")
        return rms_norm_pallas(x, scale, eps)
    report_impl("rms_norm", "xla", f"x{tuple(x.shape)}")
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, base: float = 10000.0,
                     scaling: str = "none", factor: float = 1.0,
                     yarn=None) -> jax.Array:
    """Inverse frequencies for RoPE [head_dim//2], fp32. ``yarn`` (the
    model's ``RopeConfig``, read under ``scaling="yarn"``): frequency i is
    f_i below the correction dim of ``beta_fast`` rotations over
    ``original_max_position``, f_i / factor above that of ``beta_slow``,
    and the linear blend between."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (base ** exponent)
    if scaling == "yarn" and factor != 1.0:
        import math

        def correction_dim(rotations):
            return (head_dim * math.log(yarn.original_max_position
                                        / (rotations * 2 * math.pi))
                    / (2 * math.log(base)))
        low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
        high = min(math.ceil(correction_dim(yarn.beta_slow)), head_dim - 1)
        ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        inv_freq = inv_freq / factor * ramp + inv_freq * (1.0 - ramp)
    elif scaling == "linear" and factor != 1.0:
        inv_freq = inv_freq / factor
    elif scaling == "ntk" and factor != 1.0:
        # NTK-aware: stretch the base instead of the positions
        adjusted = base * (factor ** (head_dim / max(head_dim - 2, 1)))
        inv_freq = 1.0 / (adjusted ** exponent)
    return inv_freq


def model_rope_frequencies(cfg: ModelConfig, kind: str = "full"
                           ) -> jax.Array:
    """``rope_frequencies`` of the values ``cfg``'s attention rotates: the
    whole head, or a latent-attention head's ``qk_rope_head_dim``; of a
    layer of ``kind`` (a window layer has a rope of its own)."""
    rope = cfg.layer_rope(kind)
    return rope_frequencies(cfg.rope_dim, rope.base, rope.scaling,
                            rope.scaling_factor, yarn=rope)


class LayerKind(NamedTuple):
    """What tells a WINDOW layer of the uniform stack from a full one
    (``ModelConfig.layer_types``), as ``decoder_block`` and the ``attend``
    makers take it: static numbers where the kind is (serve/decode.py scans
    over periods), traced ones where it rides a scan over layers
    (models/gpt.py)."""
    window: Any        # keys a query sees, itself included; 0: every one
    inv_freq: Any      # [D/2]: the kind's rope
    rope_scale: Any    # cos and sin are multiplied by it (None: not at all)


def rope_scale(rope) -> Optional[float]:
    """What ``apply_rope`` multiplies cos and sin by under ``rope`` (a
    ``RopeConfig``): its ``attention_factor``, None where that is 1 (no
    operation in the program)."""
    return None if rope.attention_factor == 1.0 else rope.attention_factor


def layer_kind(cfg: ModelConfig, kind: str) -> LayerKind:
    """The ``LayerKind`` of a layer of ``kind`` ("sliding" | "full")."""
    return LayerKind(cfg.sliding_window if kind == "sliding" else 0,
                     model_rope_frequencies(cfg, kind),
                     rope_scale(cfg.layer_rope(kind)))


def layer_kinds(cfg: ModelConfig) -> Optional[LayerKind]:
    """Every layer's ``LayerKind`` stacked [L, ...] (window int32, a full
    layer's 0), the per-layer operands of a scan over the layers; None for
    a model without window layers (an empty pytree: its scan and its
    program are what they were)."""
    if not cfg.has_window:
        return None
    kinds = [layer_kind(cfg, t) for t in cfg.layer_types]
    return LayerKind(
        jnp.asarray([k.window for k in kinds], jnp.int32),
        jnp.stack([k.inv_freq for k in kinds]),
        jnp.asarray([k.rope_scale or 1.0 for k in kinds], jnp.float32))


def apply_rope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array,
               scale: Any = None) -> jax.Array:
    """Rotate [..., S, N, D] by position. positions: [..., S] int32.
    ``scale``: cos and sin times it (YaRN's ``attention_factor``)."""
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # [...,S,D/2]
    cos = jnp.cos(angles)[..., :, None, :]   # [...,S,1,D/2]
    sin = jnp.sin(angles)[..., :, None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def block_visible(q_positions: jax.Array, kv_positions: jax.Array,
                  block: int = 0, window: Any = None) -> jax.Array:
    """Which keys a query may see by POSITION, [..., Sq, Skv]: those at or
    before it, or, with ``block`` > 0 (generation by diffusion over blocks,
    ``ModelConfig.attention_block``), those of its own block of ``block``
    positions and of every earlier one: the rows of a block see each other
    whole, blocks are causal among themselves. ``window`` (a WINDOW layer;
    an int or a traced int32, 0 = none): of those at or before it the last
    ``window`` alone, itself included: key j iff i - window < j <= i."""
    q, kv = q_positions[..., :, None], kv_positions[..., None, :]
    if block > 0:
        return q // block >= kv // block
    if window is None or (isinstance(window, int) and window == 0):
        return q >= kv
    return (q >= kv) & ((kv > q - window) | (window == 0))


def attention_mask(q_positions: jax.Array, kv_positions: jax.Array,
                   q_segments: Optional[jax.Array] = None,
                   kv_segments: Optional[jax.Array] = None,
                   causal: bool = True, block: int = 0,
                   window: Any = None) -> jax.Array:
    """Boolean [B, Sq, Skv] mask: True = attend.

    Packed-sequence aware: tokens attend only within their own segment
    (segment id 0 = padding, never attended). ``block``, ``window``: see
    ``block_visible``.
    """
    mask = jnp.ones(q_positions.shape[:-1] + (q_positions.shape[-1],
                    kv_positions.shape[-1]), dtype=bool)
    if causal:
        mask = block_visible(q_positions, kv_positions, block, window)
    if q_segments is not None and kv_segments is not None:
        same = q_segments[..., :, None] == kv_segments[..., None, :]
        valid = kv_segments[..., None, :] != 0
        mask = mask & same & valid
    return mask


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          mask: Optional[jax.Array] = None) -> jax.Array:
    """Reference XLA attention. q:[B,Sq,Nq,D] k,v:[B,Skv,Nkv,D] -> [B,Sq,Nq,D].

    GQA: Nq must be a multiple of Nkv; kv heads are broadcast per group.
    v's head size may differ from q's and k's (latent attention: 192 for
    the scores, 128 for the values); the output has v's.
    Softmax in fp32 (the flash/pallas path in ops/attention.py matches these
    numerics and is validated against this function in tests).
    """
    B, Sq, Nq, D = q.shape
    Nkv = k.shape[2]
    groups = Nq // Nkv
    qg = q.reshape(B, Sq, Nkv, groups, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(D))
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, Nq, v.shape[-1]).astype(q.dtype)


def _flash_on_mesh(q, k, v, segment_ids):
    """The flash kernel under the ambient mesh. A Pallas kernel is a custom
    call GSPMD cannot partition — the TPU lowering of a tp/fsdp-sharded
    step raises "Mosaic kernels cannot be automatically partitioned",
    which interpret mode never shows — so on a multi-device mesh it runs
    per shard: batch over (dp, fsdp), heads over tp. Attention needs no
    communication across either. (Inside the pipeline schedule's own
    shard_map the call stays as it is.)"""
    from jax.sharding import PartitionSpec as P

    from ..ops.attention import flash_attention
    from ..parallel.sharding import current_mesh
    mesh = current_mesh()
    on_mesh = (mesh is not None and mesh.size > 1
               and mesh.shape.get("pp", 1) == 1)
    report_impl("attention", kernel_impl("flash"), f"q{tuple(q.shape)}"
                + (f", shard_map over {dict(mesh.shape)}" if on_mesh else ""))
    if not on_mesh:
        return flash_attention(q, k, v, segment_ids=segment_ids, causal=True)
    qspec = P(("dp", "fsdp"), None, "tp", None)
    operands, specs = (q, k, v), (qspec, qspec, qspec)
    if segment_ids is not None:
        operands += (segment_ids,)
        specs += (P(("dp", "fsdp"), None),)
    fn = jax.shard_map(
        lambda q_, k_, v_, s_=None: flash_attention(
            q_, k_, v_, segment_ids=s_, causal=True),
        mesh=mesh, in_specs=specs, out_specs=qspec, check_vma=False)
    return fn(*operands)


def qk_project_norm(x: jax.Array, layer: Params, which: str,
                    cfg: ModelConfig) -> jax.Array:
    """The norm ``cfg.qk_norm`` puts on the query (``which="q"``) or key
    (``"k"``) PROJECTION [..., N*D], before the split into heads and
    before rope. "projection" (OLMoE): one RMSNorm over the whole
    projection width, scaled by ``layer["q_norm"]`` / ``layer["k_norm"]``.
    "head" (``sdar_moe``): one RMSNorm over EACH head's ``head_dim`` values,
    every head under the same learned [head_dim] scale.
    Called by ``decoder_block``."""
    if cfg.qk_norm == "none":
        return x
    scale = layer[f"{which}_norm"]["scale"]
    if cfg.qk_norm == "head":
        heads = x.reshape(*x.shape[:-1], -1, cfg.head_dim)
        return rms_norm(heads, scale, cfg.norm_eps).reshape(x.shape)
    return rms_norm(x, scale, cfg.norm_eps)


def attend_fresh(positions: jax.Array, segment_ids: Optional[jax.Array],
                 attn_impl: str = "xla", block: int = 0, window: Any = None):
    """``attend`` for a block that keeps no cache (training, evaluation,
    the pipeline stages, calibration): causal attention of the window's own
    q over its own k and v, packed sequences apart by ``segment_ids``,
    through ``attn_impl`` (xla | flash | ring | ulysses). With ``block``
    or ``window`` (``block_visible``) the mask has a term which only the xla
    route has."""
    if block > 0 and attn_impl != "xla":
        raise ValueError(f"attn_impl={attn_impl!r} has no block rule: a "
                         "model that generates by diffusion over blocks "
                         "attends through xla outside the page kernels")
    if window is not None and attn_impl != "xla":
        raise ValueError(
            f"attn_impl={attn_impl!r} has no window term: a model with "
            "window layers (sliding_window) attends through xla outside the "
            "page kernels (the flash kernel, ring and ulysses attention "
            "mask causally alone; ROADMAP B3)")

    def attend(q, k, v):
        if attn_impl == "flash":
            out = _flash_on_mesh(q, k, v, segment_ids)
        elif attn_impl == "ring":
            from ..ops.ring_attention import ring_attention
            out = ring_attention(q, k, v, positions=positions,
                                 segment_ids=segment_ids, axis_name="sp")
        elif attn_impl == "ulysses":
            from ..ops.ulysses import ulysses_attention
            out = ulysses_attention(q, k, v, positions=positions,
                                    segment_ids=segment_ids, axis_name="sp")
        else:
            report_impl("attention", "xla", f"q{tuple(q.shape)}")
            mask = attention_mask(positions, positions, segment_ids,
                                  segment_ids, block=block, window=window)
            out = dot_product_attention(q, k, v, mask)
        return out, None
    return attend


def attend_dense_cache(kv_cache: tuple[jax.Array, jax.Array],
                       cache_offset: jax.Array, positions: jax.Array,
                       block: int = 0, window: Any = None):
    """``attend`` over one layer's dense cache ``(k_cache, v_cache)`` of
    shape [B, S_max, Nkv, D] (cold prefill, ``evals/``): the new K/V are
    written at each row's ``cache_offset`` [B] (its current length) and
    attention runs over the cache; the state returned is the updated
    cache. A window layer's cache is full-length too, masked by
    ``window`` (``block_visible``)."""
    k_cache, v_cache = kv_cache

    def attend(q, k, v):
        B, S = q.shape[:2]
        S_max = k_cache.shape[1]
        # scatter new tokens at each row's offset
        write_idx = cache_offset[:, None] + jnp.arange(S)[None, :]      # [B,S]
        b_idx = jnp.arange(B)[:, None].repeat(S, axis=1)
        kc = k_cache.at[b_idx, write_idx].set(k.astype(k_cache.dtype))
        vc = v_cache.at[b_idx, write_idx].set(v.astype(v_cache.dtype))
        report_impl("prefill_attention", "xla",
                    f"q{tuple(q.shape)} over a [{B}, {S_max}] cache")
        kv_positions = jnp.arange(S_max)[None, :].repeat(B, axis=0)
        valid = kv_positions < (cache_offset[:, None] + S)
        mask = block_visible(positions, kv_positions, block, window) \
            & valid[:, None, :]
        out = dot_product_attention(q, kc.astype(q.dtype),
                                    vc.astype(q.dtype), mask)
        return out, (kc, vc)
    return attend


# ---------------------------------------------------------------------------
# Dense / MoE feed-forward
# ---------------------------------------------------------------------------

def _activate(x: jax.Array, activation: str) -> jax.Array:
    if activation == "silu":
        return jax.nn.silu(x)
    if activation == "gelu":
        return jax.nn.gelu(x)
    if activation == "relu2":
        return jnp.square(jax.nn.relu(x))
    return jax.nn.relu(x)


def scaled(x: jax.Array, multiplier: float) -> jax.Array:
    """``x`` times one of a model's muP multipliers (``ModelConfig.mup``),
    in float32, back in x's dtype. A multiplier of exactly 1 (every model
    but ``falcon_h1``; its ``attention_in_multiplier``) is no operation in
    the program."""
    if multiplier == 1.0:
        return x
    return (x.astype(jnp.float32) * multiplier).astype(x.dtype)


def dense_matmul(a: jax.Array, w: jax.Array) -> jax.Array:
    """[B, S, in] x [in, out]: how a block multiplies a plain weight."""
    return jnp.einsum("bsh,hf->bsf", a, w)


def mlp_block(x: jax.Array, layer: Params, cfg: ModelConfig,
              matmul=dense_matmul) -> jax.Array:
    """Gated FFN (SwiGLU for silu — reference llama-7b.json activation),
    or with ``cfg.mlp_gated`` False the plain two-kernel
    ``down(act(up(x)))``. ``matmul(a, w)``: see ``decoder_block``."""
    gate_by, down_by = cfg.mup.mlp
    if cfg.mlp_gated:
        gate = scaled(matmul(x, layer["gate"]["kernel"]), gate_by)
        up = matmul(x, layer["up"]["kernel"])
        h = _activate(gate, cfg.activation) * up
    else:
        h = _activate(matmul(x, layer["up"]["kernel"]), cfg.activation)
    return scaled(matmul(h, layer["down"]["kernel"]), down_by).astype(x.dtype)


def moe_route(xt: jax.Array, router_kernel: jax.Array, cfg: ModelConfig,
              selection_bias: Optional[jax.Array] = None
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Token-choice routing of xt [N, H] over ALL the router's experts, in
    float32. ``router_score`` "softmax": softmax, then the top-k;
    ``top_w`` sums to 1 per token iff ``cfg.moe.norm_topk_prob``.
    "sigmoid": a score per expert, the top-k taken of score +
    ``selection_bias`` [E] (the bias picks, it does not weigh), the weights
    the chosen SCORES, divided by their sum iff ``norm_topk_prob``, times
    ``routed_scaling_factor``. Returns (scores [N, E], top_w [N, K],
    top_e [N, K]). Shared by the dropless serving block and training's
    capacity block."""
    with jax.named_scope("moe_router"):
        # full float32 passes: at the TPU's default precision a float32
        # matmul multiplies in bfloat16, and a router logit off by 1e-3
        # flips a token's k-th choice to another expert wherever two are
        # close (1 row in ~1,000 at OLMoE's widths: 7 % of that row's
        # output). [N, H] x [H, E] is too small to cost anything.
        logits = jnp.einsum("nh,he->ne", xt.astype(jnp.float32),
                            router_kernel.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        if cfg.moe.router_score == "sigmoid":
            scores = jax.nn.sigmoid(logits)                      # [N,E]
            pick = scores if selection_bias is None else (
                scores + selection_bias.astype(jnp.float32))
            _, top_e = jax.lax.top_k(pick, cfg.moe.experts_per_token)
            top_w = jnp.take_along_axis(scores, top_e, axis=-1)
            if cfg.moe.norm_topk_prob:
                top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True)
                                 + 1e-20)
            return scores, top_w * cfg.moe.routed_scaling_factor, top_e
        probs = jax.nn.softmax(logits, axis=-1)                  # [N,E]
        top_w, top_e = jax.lax.top_k(probs, cfg.moe.experts_per_token)
        if cfg.moe.norm_topk_prob:
            top_w = top_w / jnp.maximum(
                jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)
    return probs, top_w, top_e


def moe_row_tile(n_choices: int, num_experts: int, dtype) -> int:
    """Rows of one expert tile: the power of two nearest the mean rows an
    expert gets, between the dtype's sublane packing (16 rows of bf16, 8 of
    float32) and 128 (the MXU's edge)."""
    floor = 16 if jnp.dtype(dtype).itemsize < 4 else 8
    mean = max(n_choices // num_experts, 1)
    return int(min(128, max(floor, 1 << (mean - 1).bit_length())))


def moe_block(x: jax.Array, layer: Params, cfg: ModelConfig,
              live: Optional[jax.Array] = None, layer_index=None
              ) -> tuple[jax.Array, jax.Array]:
    """Dropless token-choice top-k MoE: every live token is served by ALL
    of its k experts whatever else is in the batch (the serving path;
    OLMoE, Mixtral and their kin are dropless).

    Static shapes without a capacity: the N*K choices are ranked within
    their expert in token order (the stable sort by expert, computed as a
    running count over a one-hot), each expert's rows are laid out in a
    buffer padded to whole ``tm``-row tiles (at most E*(tm-1) rows of
    padding, a static bound), one grouped matmul over the ragged groups
    runs gate/up and another down (ops/moe_gmm.py), and each token gathers
    its K rows back weighted by its router probabilities. No row can
    displace another: a token's output does not depend on its batch
    companions. ``live`` [B, S] marks real tokens, as a bool mask or as
    packed-sequence segment ids (0 = padding); idle decode slots and
    prefill padding get no rows, hit no expert (their experts' weights are
    not read) and return zeros.

    ``layer`` holds ``router`` and the experts' ``gate`` / ``up`` / ``down``
    kernels (no ``gate`` with ``cfg.mlp_gated`` False: two matmuls an
    expert), either one layer's [E, in, out] (``layer_index=None``) or the
    whole stack [L, E, in, out] with ``layer_index`` (the router [H, E] is
    always one layer's): the stack is handed to the kernel as it lies, so
    no layer's 3 x [E, H, F] slab is copied out per layer.

    ``E`` is the experts HELD here (``cfg.moe.num_experts``). Where the
    router is wider (``cfg.moe.router_width``: this chip's share of a
    layer several chips divide), the router runs over all of its experts
    and only the choices that fall on held experts are ranked, padded,
    multiplied and gathered back: what the absent experts would add is
    left out, and nothing stands in for the chips that hold them.

    Returns (output [B, S, H], counts [E] int32: live tokens' choices per
    held expert).
    """
    from ..ops.moe_gmm import grouped_matmul
    B, S, H = x.shape
    E, K = cfg.moe.num_experts, cfg.moe.experts_per_token
    N = B * S
    if live is not None and live.dtype != jnp.bool_:
        live = live != 0
    xt = x.reshape(N, H)
    _, top_w, top_e = moe_route(xt, layer["router"]["kernel"], cfg,
                                layer["router"].get("bias"))

    with jax.named_scope("moe_dispatch"):
        tm = moe_row_tile(N * K, E, x.dtype)
        # every expert holds whole tiles: sum_e ceil(c_e / tm) is at most
        # (N*K + E*(tm-1)) / tm, and an expert has at most N rows
        n_tiles = min((N * K + E * (tm - 1)) // tm, E * (-(-N // tm)))
        M = n_tiles * tm
        flat_e = top_e.reshape(N * K)
        flat_live = (jnp.ones((N * K,), bool) if live is None
                     else jnp.repeat(live.reshape(N), K))
        if not cfg.moe.holds_all:
            flat_e = flat_e - cfg.moe.first_expert
            flat_live = flat_live & (flat_e >= 0) & (flat_e < E)
            flat_e = jnp.clip(flat_e, 0, E - 1)
        onehot = (flat_e[:, None] == jnp.arange(E)[None, :]) \
            & flat_live[:, None]                                 # [NK,E]
        running = running_count(onehot)
        counts = running[-1]                                     # [E]
        rank = jnp.take_along_axis(running, flat_e[:, None], 1)[:, 0] - 1
        tiles_of = (counts + tm - 1) // tm                       # [E]
        tile_end = jnp.cumsum(tiles_of)                          # [E]
        tiles_used = tile_end[-1]
        row_start = (tile_end - tiles_of) * tm                   # [E]
        # a dead choice goes out of range: its scatter is dropped and its
        # gather reads row 0 under a zero weight
        dest = jnp.where(flat_live, row_start[flat_e] + rank, M)  # [NK]
        # tile t belongs to the first expert whose tiles end past it; the
        # unused tail is clamped to the last used tile's expert (same
        # weight block index: no DMA)
        t = jnp.minimum(jnp.arange(n_tiles), tiles_used - 1)
        tile_group = jnp.minimum(
            jnp.sum(tile_end[None, :] <= t[:, None], axis=1), E - 1
        ).astype(jnp.int32)
        row_token = jnp.zeros((M,), jnp.int32).at[dest].set(
            jnp.repeat(jnp.arange(N, dtype=jnp.int32), K), mode="drop")
        xs = xt[row_token]                                       # [M,H]

    with jax.named_scope("moe_experts"):
        # one token a sequence is the decode step; a window is a prefill
        # (the names a device trace tells the two by, as paged_attention's)
        mm = functools.partial(
            grouped_matmul, tile_group=tile_group, tiles_used=tiles_used,
            layer=layer_index, tm=tm,
            name="moe_gmm" if S == 1 else "moe_gmm_prefill")
        # gate / up stacks lie [.., H, F], or [.., F, H] (out, in) where F
        # is no multiple of the chip's 128 lanes (gpt.py lays them so;
        # ops/moe_gmm.py says why): read off the stack itself
        up_shape = layer["up"]["kernel"].shape
        into = (functools.partial(mm, rhs_transposed=True)
                if up_shape[-1] == H != up_shape[-2] else mm)
        if cfg.mlp_gated:
            hidden = _activate(into(xs, layer["gate"]["kernel"]),
                               cfg.activation) * into(xs, layer["up"]["kernel"])
        else:
            hidden = _activate(into(xs, layer["up"]["kernel"]),
                               cfg.activation)
        ys = mm(hidden, layer["down"]["kernel"])                 # [M,H]

    with jax.named_scope("moe_combine"):
        rows = ys[jnp.minimum(dest, M - 1)].astype(jnp.float32)  # [NK,H]
        # (masked, not weighed by zero: a dead choice reads a row no
        # expert wrote)
        rows = jnp.where(flat_live[:, None], rows, 0.0)
        out = jnp.sum((rows * top_w.reshape(N * K, 1)).reshape(N, K, H),
                      axis=1)
    return out.reshape(B, S, H).astype(x.dtype), counts


def running_count(onehot: jax.Array, block: int = 256) -> jax.Array:
    """The running count down the rows of a [rows, E] 0/1 mask (int32):
    ``moe_block``'s rank of a choice within its expert. Up to 1,024 rows
    (every decode step the benchmark has) it is ``jnp.cumsum``. Above that
    XLA's cumsum becomes a ``reduce-window`` that takes ~110 us a layer on
    the v5e whatever the length (a decode step that carries 128 rows of a
    prompt ranks 1,280 choices: 1.0 ms a step in ten layers), so blocks of
    ``block`` rows are summed on the MXU (0 / 1 operands in bfloat16,
    float32 sums of at most ``block``: exact) and a cumsum over the few
    blocks' totals is added: ~5 us a layer (PERF.md 6, PR 36). Rows that
    do not fill whole blocks keep the plain cumsum."""
    rows, width = onehot.shape
    if rows <= 1024 or rows % block:
        return jnp.cumsum(onehot.astype(jnp.int32), axis=0)
    lower = jnp.tril(jnp.ones((block, block), jnp.bfloat16))
    within = jnp.einsum(
        "ij,bje->bie", lower,
        onehot.reshape(rows // block, block, width).astype(jnp.bfloat16),
        preferred_element_type=jnp.float32).astype(jnp.int32)
    totals = within[:, -1]
    before = jnp.cumsum(totals, axis=0) - totals
    return (within + before[:, None]).reshape(rows, width)


def moe_stats(counts: jax.Array, cfg: Optional[ModelConfig] = None,
              live: Optional[jax.Array] = None, tokens: int = 0) -> jax.Array:
    """One dropless block's routing as the int32 vector
    (``cfg.moe.stats_size``) the serve programs sum over layers and steps
    and hand the engine: the live tokens' choices per held expert, how
    many held experts got any and, where not every expert is held
    (``cfg``), the choices of the block's live tokens (``live``, else all
    ``tokens``) over ALL the router's experts."""
    stats = jnp.append(counts, jnp.sum(counts > 0, dtype=counts.dtype))
    if cfg is None or cfg.moe.holds_all:
        return stats
    n_live = (jnp.int32(tokens) if live is None
              else jnp.sum(live != 0, dtype=jnp.int32))
    return jnp.append(stats, n_live * cfg.moe.experts_per_token)


def moe_block_capacity(x: jax.Array, layer: Params, cfg: ModelConfig
                       ) -> tuple[jax.Array, jax.Array]:
    """Token-choice top-k MoE with GShard-style capacity dispatch: the
    TRAINING route (differentiable through plain einsums, experts sharded
    on 'ep'). Serving never takes it: see ``moe_block``.

    Static shapes throughout (XLA requirement): tokens are dispatched into
    a fixed per-expert capacity C; overflow tokens fall back to the
    residual stream. Experts carry a leading E axis that the mesh shards
    on 'ep' (SURVEY §2.2: EP absent from the reference).

    Dispatch is SORT-based, not one-hot: the classic GShard one-hot
    einsum builds [N, E, C] dispatch/combine tensors whose memory grows
    ~quadratically in tokens (C itself is O(N/E)); at b8 x S4096 on
    gpt-moe-test scales that tensor alone is ~5 GB *per layer*.
    Here choices are stably sorted by expert id, each expert gathers its
    first C tokens from the sorted order, and outputs scatter-add back —
    peak extra memory is the [E, C, H] expert buffers plus O(N*K) index
    vectors, linear in tokens. The stable sort preserves the flattened
    (token-major) choice order, so the set of dropped overflow tokens is
    IDENTICAL to the one-hot formulation (asserted in tests).

    Returns (output, aux_loss).
    """
    B, S, H = x.shape
    E = cfg.moe.num_experts
    K = cfg.moe.experts_per_token
    N = B * S
    C = max(int(cfg.moe.capacity_factor * K * N / E), 1)

    xt = x.reshape(N, H)
    probs, top_p, top_e = moe_route(xt, layer["router"]["kernel"], cfg)

    flat_e = top_e.reshape(N * K)
    flat_w = top_p.reshape(N * K)
    flat_tok = jnp.repeat(jnp.arange(N, dtype=jnp.int32), K)     # [NK]

    order = jnp.argsort(flat_e, stable=True)                     # [NK]
    counts = jnp.bincount(flat_e, length=E)                      # [E]
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])          # [E]
    # expert e's buffer slot c holds sorted choice starts[e] + c,
    # valid while c < counts[e] (the rest of the buffer is padding)
    c_idx = jnp.arange(C, dtype=counts.dtype)
    gather_pos = jnp.minimum(starts[:, None] + c_idx[None, :],
                             N * K - 1)                          # [E,C]
    valid = c_idx[None, :] < counts[:, None]                     # [E,C]
    choice = order[gather_pos]                                   # [E,C]
    tok = flat_tok[choice]                                       # [E,C]
    w = jnp.where(valid, flat_w[choice], 0.0).astype(x.dtype)    # [E,C]

    # gather each expert's tokens; padding rows are zeroed so invalid
    # slots contribute nothing even before the w=0 combine
    xe = xt[tok] * valid[..., None].astype(x.dtype)              # [E,C,H]

    def expert_ffn(we, xe_):
        g = jnp.einsum("ch,hf->cf", xe_, we["gate"])
        u = jnp.einsum("ch,hf->cf", xe_, we["up"])
        return jnp.einsum("cf,fh->ch", _activate(g, cfg.activation) * u,
                          we["down"])

    he = jax.vmap(expert_ffn)(
        {"gate": layer["gate"]["kernel"], "up": layer["up"]["kernel"],
         "down": layer["down"]["kernel"]}, xe)                    # [E,C,H]

    # combine: scatter-add the weighted expert outputs back per token
    # (a token's K choices land in different experts and accumulate)
    out = jnp.zeros((N, H), x.dtype).at[tok.reshape(-1)].add(
        (he * w[..., None]).reshape(E * C, H),
        mode="drop", indices_are_sorted=False, unique_indices=False)
    out = out.reshape(B, S, H)

    # load-balancing aux loss (Switch-style): E * mean(f_e * p_e).
    # f_e = fraction of choices routed to e — exactly counts/N, already
    # computed for the dispatch (no [N, K, E] one-hot needed)
    f = counts.astype(jnp.float32) / N
    p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * p) * cfg.moe.router_aux_loss_weight
    return out.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# The decoder block
# ---------------------------------------------------------------------------

def decoder_block(
    x: jax.Array,               # [B, S, H] the residual stream
    layer: Params,              # one layer's parameters
    cfg: ModelConfig,
    positions: jax.Array,       # [B, S] int32
    inv_freq: jax.Array,
    attend,
    *,
    matmul=dense_matmul,
    norm_impl: str = "xla",
    live: Optional[jax.Array] = None,
    moe_impl: str = "dropless",
    layer_index=None,
    kind: Optional[str] = None,
    recur=None,
    rope_scale: Any = None,
) -> tuple[jax.Array, Any, Any]:
    """One pre-norm transformer block: THE layer equations. Training,
    evaluation and the pipeline stages (models/gpt.py ``_block_fn``), cold
    prefill over a dense cache (the same), paged decode, suffix and chunked
    prefill and speculative verification (serve/decode.py) and the AWQ
    calibration pass (ops/quantization.py) all run this function; a new
    architecture changes it, and nothing else.

    What differs between those callers is not the equations, and comes in
    as two functions:

    - ``attend(q, k, v) -> (out, state)``: where K and V live. q
      [B, S, Nq, D] and the window's new k, v [B, S, Nkv, D] arrive
      normed, biased and rotated; ``out`` is [B, S, Nq, D] and ``state``
      whatever the caller keeps (``attend_fresh``: None;
      ``attend_dense_cache``: the updated cache; serve/decode.py: the page
      pools). A new cache state is a new ``attend``.
    - ``matmul(a, w)``: how a weight is multiplied. ``w`` is
      ``layer[...]["kernel"]`` as the caller's tree holds it (an array, a
      packed int4 / int8 tensor, a tagged kernel), so a caller's matmul
      dispatches on its type.

    A layer of a stack with WINDOW layers differs from its neighbours by
    its ``LayerKind`` alone: the window is in the caller's ``attend``, the
    kind's frequencies come as ``inv_freq`` and ``rope_scale`` multiplies
    cos and sin (None: a model without window layers).

    The feed-forward is chosen from ``cfg`` and ``moe_impl``: the dense
    ``mlp_block``; the dropless ``moe_block`` (``live``, ``layer["moe"]``
    and ``layer_index`` as ``moe_block`` takes them); training's
    ``moe_block_capacity``.

    ``kind`` None is a layer of the uniform stack: attention THEN
    feed-forward under two norms (``attn_norm``, ``mlp_norm``), and with
    ``cfg.sandwich_norm`` a second norm on each sub-layer's OUTPUT before
    the residual takes it (``attn_out_norm``, ``mlp_out_norm``:
    ``x + N2(Attn(N1(x)))``). A layer of
    a layer table (``cfg.layer_pattern``) is ONE norm (``layer["norm"]``)
    and ONE mixer, chosen by ``kind``: ``*`` the same attention (or, for a
    model with latent attention, ``latent_attention_mixer``), ``E`` the
    same experts (plus the shared expert), ``D`` the dense ``mlp_block``,
    ``M`` the Mamba-2 mixer, whose state lives where ``recur`` says, as K
    and V live where ``attend`` says (``ssm_mixer``), ``K`` the Kimi Delta
    Attention mixer, likewise (``kda_mixer``), ``C`` (``lfm2_moe``) the
    gated short convolution, whose window of rows is its whole state
    (``shortconv_mixer``), ``P`` (``falcon_h1``) the
    attention AND the Mamba-2 mixer on the SAME normed stream, their
    outputs summed, both states returned (``attend``'s, ``recur``'s). With
    ``cfg.hc_mult`` > 1 the residual is ``hc_mult`` streams ([B, S, n, H])
    and the table's residual rule is the hyper-connection (``hc_maps``).

    Returns (x, the mixer's state (``attend``'s or ``recur``'s, a ``P``
    layer's pair of them; None for an expert layer), what the caller sums
    over the layers: None for a dense layer, the ``moe_stats`` of a
    dropless one, the router's aux loss for the capacity route).
    """
    if kind is not None:
        # a layer of a layer table: ONE norm, ONE mixer. With residual
        # STREAMS (``cfg.hc_mult`` > 1: x is [B, S, n, H]) the sub-layer
        # reads a mix of them and its output goes back through the
        # hyper-connection's maps; otherwise the plain ``x + out``.
        maps = hc_maps(x, layer["hc"], cfg) if cfg.hc_mult > 1 else None
        u = x if maps is None else hc_read(x, maps)
        h = rms_norm(u, layer["norm"]["scale"], cfg.norm_eps, impl=norm_impl)
        state = aux = None
        if kind == "M":
            out, state = ssm_mixer(h, layer, cfg, recur, matmul)
        elif kind == "K":
            out, state = kda_mixer(h, layer, cfg, recur, matmul)
        elif kind == "C":
            out, state = shortconv_mixer(h, layer, cfg, recur, matmul)
        elif kind == "*":
            mixer = (latent_attention_mixer if cfg.is_latent
                     else attention_mixer)
            out, state = mixer(h, layer, cfg, positions, inv_freq, attend,
                               matmul)
        elif kind == "E":
            out, aux = experts_mixer(h, layer, cfg, live, moe_impl,
                                     layer_index, matmul)
        elif kind == "D":
            with jax.named_scope("dense_mlp"):
                out = mlp_block(h, layer, cfg, matmul=matmul)
        elif kind == "P":
            # two mixers under ONE norm: each reads ``h``, the residual
            # takes their sum, and the layer keeps both kinds of state
            with jax.named_scope("parallel_attention"):
                out, kv_state = attention_mixer(
                    h, layer, cfg, positions, inv_freq, attend, matmul)
            with jax.named_scope("parallel_ssm"):
                ssm_out, ssm_state = ssm_mixer(h, layer, cfg, recur, matmul)
            out, state = out + ssm_out.astype(out.dtype), (kv_state,
                                                           ssm_state)
        else:
            raise ValueError(f"no layer kind {kind!r}")
        if maps is not None:
            return hc_write(x, out, maps), state, aux
        return x + out.astype(x.dtype), state, aux

    def out_norm(out, name):
        # the sandwich's second norm: on the sub-layer's OUTPUT, inside the
        # residual
        if not cfg.sandwich_norm:
            return out
        return rms_norm(out.astype(x.dtype), layer[name]["scale"],
                        cfg.norm_eps, impl=norm_impl)

    h = rms_norm(x, layer["attn_norm"]["scale"], cfg.norm_eps, impl=norm_impl)
    out, state = attention_mixer(h, layer, cfg, positions, inv_freq, attend,
                                 matmul, rope_scale)
    x = x + out_norm(out, "attn_out_norm").astype(x.dtype)

    h = rms_norm(x, layer["mlp_norm"]["scale"], cfg.norm_eps, impl=norm_impl)
    if cfg.is_moe:
        ffn, aux = experts_mixer(h, layer["moe"], cfg, live, moe_impl,
                                 layer_index, matmul)
    else:
        ffn, aux = mlp_block(h, layer["mlp"], cfg, matmul=matmul), None
    return x + out_norm(ffn, "mlp_out_norm").astype(x.dtype), state, aux


def attention_mixer(h: jax.Array, layer: Params, cfg: ModelConfig,
                    positions: jax.Array, inv_freq: jax.Array, attend,
                    matmul=dense_matmul, rope_scale: Any = None
                    ) -> tuple[jax.Array, Any]:
    """Grouped-query attention over the normed stream ``h`` [B, S, H]:
    projections, optional q/k norms and biases, rope unless
    ``cfg.position_embedding`` is "none" (``rope_scale``: ``apply_rope``'s),
    ``attend``, with
    ``cfg.attention_gate`` the output times ``sigmoid(h W_g)`` elementwise
    (``solar_open2``), the output projection. Returns (out [B, S, H],
    ``attend``'s state)."""
    B, S, _ = h.shape
    D, Nq, Nkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    mup = cfg.mup
    h = scaled(h, mup.attention_in)
    q = qk_project_norm(matmul(h, layer["q"]["kernel"]), layer, "q",
                        cfg).reshape(B, S, Nq, D)
    k = qk_project_norm(scaled(matmul(h, layer["k"]["kernel"]), mup.key),
                        layer, "k", cfg).reshape(B, S, Nkv, D)
    v = matmul(h, layer["v"]["kernel"]).reshape(B, S, Nkv, D)
    if cfg.attention_bias:
        q = q + layer["q"]["bias"].reshape(Nq, D)
        k = k + layer["k"]["bias"].reshape(Nkv, D)
        v = v + layer["v"]["bias"].reshape(Nkv, D)
    if cfg.position_embedding == "rope":
        q = apply_rope(q, positions, inv_freq, rope_scale)
        k = apply_rope(k, positions, inv_freq, rope_scale)

    out, state = attend(q, k, v)
    if cfg.attention_gate:
        with jax.named_scope("attn_gate"):
            gate = matmul(h, layer["gate"]["kernel"]).reshape(B, S, Nq, D)
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(h.dtype)
    out = scaled(matmul(out.reshape(B, S, Nq * D), layer["o"]["kernel"]),
                 mup.attention_out)
    # named so remat policies can pin it resident: the flash kernel's output
    # is a custom call, not a dot, so dots_* policies rematerialise it —
    # which re-runs the whole O(S^2) flash forward inside the backward pass
    # (the name lowers to nothing in a program that takes no gradient)
    return checkpoint_name(out.astype(h.dtype), "attn_out"), state


def latent_attention_mixer(h: jax.Array, layer: Params, cfg: ModelConfig,
                           positions: jax.Array, inv_freq: jax.Array, attend,
                           matmul=dense_matmul) -> tuple[jax.Array, Any]:
    """Multi-head latent attention over the normed stream ``h`` [B, S, H]:

        c_q           = RMSNorm(h W_qa)
        [q_nope|q_pe] = c_q W_qb   a head;   q_pe = rope(q_pe)
        [c_kv|k_pe]   = h W_kva;   c_kv = RMSNorm(c_kv);  k_pe = rope(k_pe)
        [k_nope|v]    = c_kv W_kvb  a head

    scores (q_nope . k_nope + q_pe . k_pe) * ``cfg.softmax_scale``, rope
    over the ``pe`` values alone (halves paired, as ``apply_rope``). What a
    cache keeps of a token is the LATENT row [c_kv | k_pe], for all heads.
    Without a query bottleneck (``cfg.mla.q_lora_rank`` 0) the query is ONE
    direct projection ``h W_q`` and has no norm; with
    ``cfg.position_embedding`` "none" the ``pe`` values are carried and
    scored and never rotated (``kimi_linear``).

    ``attend`` comes in two kinds, told by ``attend.latent``. Unset
    (``attend_fresh``: training-style callers and cold prefill): the
    EXPANDED form, q / k [B, S, N, nope + rope] and v [B, S, N, v] as
    ``attention_mixer`` hands them; the state returned is then the latent
    rows [B, S, latent], which cold prefill writes to the pages. Set
    (serve/decode.py, over the latent page pool): the ABSORBED form, the
    same mathematics with W_kvb folded into the query and the output,
    ``attend(q~ [B, S, N, kv_rank + rope], rows [B, S, latent], scale)``
    -> (o~ [B, S, N, kv_rank], state), so a page is read once and serves as
    keys and as values.
    """
    B, S, _ = h.shape
    a, N = cfg.mla, cfg.num_heads
    dn, dr, dv, r = (a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim,
                     a.kv_lora_rank)
    def rotate(pe):
        if cfg.position_embedding == "none":
            return pe
        return apply_rope(pe, positions, inv_freq)
    with jax.named_scope("mla_q_proj"):
        if a.q_lora_rank:
            c_q = rms_norm(matmul(h, layer["q_a"]["kernel"]),
                           layer["q_a_norm"]["scale"], cfg.norm_eps)
            q = matmul(c_q, layer["q_b"]["kernel"])
        else:
            q = matmul(h, layer["q"]["kernel"])
        q = q.reshape(B, S, N, dn + dr)
        q_nope, q_pe = q[..., :dn], rotate(q[..., dn:])
    with jax.named_scope("mla_kv_compress"):
        ckv = matmul(h, layer["kv_a"]["kernel"])
        c_kv = rms_norm(ckv[..., :r], layer["kv_norm"]["scale"], cfg.norm_eps)
        k_pe = rotate(ckv[..., None, r:])[..., 0, :]
        rows = jnp.concatenate([c_kv, k_pe], axis=-1)            # [B,S,r+dr]
    w_kvb = layer["kv_b"]["kernel"]                  # [r, N * (dn + dv)]
    if getattr(attend, "latent", False):
        w = w_kvb.reshape(r, N, dn + dv)
        with jax.named_scope("mla_absorb"):
            q_lat = jnp.concatenate(
                [jnp.einsum("bsnd,rnd->bsnr", q_nope, w[..., :dn]), q_pe],
                axis=-1)
        o_lat, state = attend(q_lat, rows, cfg.softmax_scale)
        with jax.named_scope("mla_absorb"):
            out = jnp.einsum("bsnr,rnd->bsnd", o_lat.astype(h.dtype),
                             w[..., dn:])
    else:
        kv = matmul(c_kv, w_kvb).reshape(B, S, N, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe[:, :, None], (B, S, N, dr))],
            axis=-1)
        # ``dot_product_attention`` divides by sqrt(head_dim) itself: what
        # is left of the scale (YaRN's m^2) goes onto the query
        m2 = cfg.rope.softmax_mscale ** 2
        qf = jnp.concatenate([q_nope, q_pe], axis=-1)
        out, state = attend(qf if m2 == 1.0 else (qf * m2).astype(qf.dtype),
                            k, kv[..., dn:])
        state = rows if state is None else state
    out = matmul(out.reshape(B, S, N * dv), layer["o"]["kernel"])
    return checkpoint_name(out.astype(h.dtype), "attn_out"), state


def hc_maps(x: jax.Array, hc: Params, cfg: ModelConfig
            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The three maps of a manifold-constrained hyper-connection from the
    residual streams ``x`` [B, S, n, C], in float32 (a 4 x 4 Sinkhorn in
    bfloat16 is not the mechanism):

        x_hat   = RMSNorm(vec(X))                    over all n * C values
        [p|q|R] = x_hat phi                          phi [n*C, n + n + n*n]
        H_pre   = sigmoid(a_pre p + b_pre)           [n]
        H_post  = 2 sigmoid(a_post q + b_post)       [n]
        H_res   = Sinkhorn(exp(clip(a_res mat(R) + b_res, lo, hi)))  [n, n]

    ``cfg.hc_sinkhorn_iters`` times rows then columns divided by their sums
    (+ ``cfg.hc_eps``, also the norm's epsilon): doubly stochastic,
    ``H_res[i, j]`` weighs stream j into stream i. Returns (H_pre [B,S,n],
    H_post [B,S,n], H_res [B,S,n,n])."""
    B, S, n, C = x.shape
    eps = cfg.hc_eps
    with jax.named_scope("hc_maps"):
        xf = x.reshape(B, S, n * C).astype(jnp.float32)
        x_hat = (xf * jax.lax.rsqrt(
            jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
            * (1.0 + hc["norm"]["scale"].astype(jnp.float32)))
        pqr = jnp.einsum("bsk,km->bsm", x_hat,
                         hc["phi"]["kernel"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        a = hc["a"].astype(jnp.float32)
        h_pre = jax.nn.sigmoid(a[0] * pqr[..., :n] + hc["b_pre"])
        h_post = 2.0 * jax.nn.sigmoid(a[1] * pqr[..., n:2 * n] + hc["b_post"])
        m = jnp.exp(jnp.clip(
            a[2] * pqr[..., 2 * n:].reshape(B, S, n, n) + hc["b_res"],
            cfg.hc_clamp_min, cfg.hc_clamp_max))
        for _ in range(cfg.hc_sinkhorn_iters):
            m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
            m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return h_pre, h_post, m


def hc_read(x: jax.Array, maps) -> jax.Array:
    """What a sub-layer reads of the streams: ``H_pre @ X`` [B, S, C]."""
    # (n is 4: a multiply and a sum over the streams on the vector unit in
    # float32, not a matmul whose operands the chip would round)
    with jax.named_scope("hc_mix"):
        return jnp.sum(maps[0][..., None] * x.astype(jnp.float32),
                       axis=2).astype(x.dtype)


def hc_write(x: jax.Array, out: jax.Array, maps) -> jax.Array:
    """The streams after a sub-layer whose output is ``out`` [B, S, C]:
    ``H_res @ X + outer(H_post, out)`` [B, S, n, C]."""
    with jax.named_scope("hc_mix"):
        mixed = jnp.sum(maps[2][..., None]
                        * x.astype(jnp.float32)[:, :, None], axis=3)
        return (mixed + maps[1][..., None]
                * out.astype(jnp.float32)[:, :, None, :]).astype(x.dtype)


def experts_mixer(h: jax.Array, moe: Params, cfg: ModelConfig, live,
                  moe_impl: str, layer_index, matmul=dense_matmul
                  ) -> tuple[jax.Array, Any]:
    """The sparse feed-forward over the normed stream ``h``: the dropless
    ``moe_block`` (with its ``moe_stats``) or training's capacity route
    (with the router's aux loss), plus the shared expert every token takes
    where the model has one (``moe["shared"]``: a plain dense branch)."""
    if moe_impl == "dropless":
        ffn, counts = moe_block(h, moe, cfg, live=live,
                                layer_index=layer_index)
        aux = moe_stats(counts, cfg, live, h.shape[0] * h.shape[1])
    else:
        ffn, aux = moe_block_capacity(h, moe, cfg)
    if cfg.moe.shared_expert_size:
        with jax.named_scope("moe_shared_expert"):
            ffn = ffn + mlp_block(h, moe["shared"], cfg, matmul=matmul)
    return ffn, aux


def ssm_mixer(h: jax.Array, layer: Params, cfg: ModelConfig, recur,
              matmul=dense_matmul) -> tuple[jax.Array, Any]:
    """The Mamba-2 mixer over the normed stream ``h`` [B, S, H]:
    ``[z | xBC | dt] = h W_in``; ``recur(xBC, dt, layer)`` runs the conv
    and the recurrence wherever its state lives (ops/ssm.py
    ``recur_window`` / ``recur_step``) and returns (y [B, S, d_in], state);
    then the gated norm and the output projection. With muP multipliers
    (``cfg.mup``): ``ssm_in`` on h, ``ssm`` on the five parts z, x, B, C,
    dt of the in-projection's output (one vector over its columns),
    ``ssm_out`` on the output."""
    from ..ops.ssm import ssm_gated_norm
    s, mup = cfg.ssm, cfg.mup
    d_in, C = s.inner_size, s.conv_channels
    with jax.named_scope("ssm_in_proj"):
        zxbcdt = matmul(scaled(h, mup.ssm_in), layer["in_proj"]["kernel"])
        if any(m != 1.0 for m in mup.ssm):
            gn = s.n_groups * s.state_size
            by_column = np.repeat(np.asarray(mup.ssm, np.float32),
                                  [d_in, d_in, gn, gn, s.num_heads])
            zxbcdt = (zxbcdt.astype(jnp.float32) * by_column).astype(
                zxbcdt.dtype)
    z, xbc, dt = (zxbcdt[..., :d_in], zxbcdt[..., d_in:d_in + C],
                  zxbcdt[..., d_in + C:])
    y, state = recur(xbc, dt, layer)
    y = ssm_gated_norm(y, z, layer["gate_norm"]["scale"], s.n_groups,
                       cfg.norm_eps)
    with jax.named_scope("ssm_out_proj"):
        return scaled(matmul(y, layer["out_proj"]["kernel"]),
                      mup.ssm_out), state


def kda_mixer(h: jax.Array, layer: Params, cfg: ModelConfig, recur,
              matmul=dense_matmul) -> tuple[jax.Array, Any]:
    """The Kimi Delta Attention mixer over the normed stream ``h``
    [B, S, H]: ``[q | k | v | f_lo | g_lo | b] = h W_in`` (the three
    projections, the first halves of the decay's and the output gate's
    low-rank pairs and the beta logits: one matmul); ``recur(qkv, f, b,
    layer)`` runs the conv and the gated delta rule wherever its state
    lives (ops/kda.py ``recur_window`` / ``recur_step`` / ``recur_chunk``)
    and returns (o [B, S, d_in], state); then the sigmoid-gated head norm
    and the output projection."""
    from ..ops.kda import kda_gated_norm
    kd = cfg.kda
    C, r = kd.conv_channels, kd.head_dim
    proj = matmul(h, layer["in_proj"]["kernel"])
    qkv, f_lo, g_lo, b = (proj[..., :C], proj[..., C:C + r],
                          proj[..., C + r:C + 2 * r], proj[..., C + 2 * r:])
    o, state = recur(qkv, matmul(f_lo, layer["f_b"]["kernel"]), b, layer)
    o = kda_gated_norm(o, matmul(g_lo, layer["g_b"]["kernel"]),
                       layer["gate_norm"]["scale"], kd.num_heads,
                       cfg.norm_eps)
    return matmul(o, layer["out_proj"]["kernel"]), state


def shortconv_mixer(h: jax.Array, layer: Params, cfg: ModelConfig, recur,
                    matmul=dense_matmul) -> tuple[jax.Array, Any]:
    """The gated short-convolution mixer (``lfm2_moe``'s ``conv`` layers)
    over the normed stream ``h`` [B, S, H]: ``[B | C | u] = h W_in`` (that
    order, no bias); ``z = B * u``; ``recur(z, layer)`` runs the depthwise
    causal conv wherever its window lives (ops/shortconv.py
    ``recur_window`` / ``recur_step`` / ``recur_chunk``) and returns
    (y [B, S, H], state); then ``(C * y) W_out``. No activation, no
    position embedding; the gates multiply in float32."""
    H = cfg.hidden_size
    with jax.named_scope("shortconv_mixer"):
        bcu = matmul(h, layer["in_proj"]["kernel"]).astype(jnp.float32)
        z = (bcu[..., :H] * bcu[..., 2 * H:]).astype(h.dtype)
        y, state = recur(z, layer)
        gated = (bcu[..., H:2 * H] * y.astype(jnp.float32)).astype(h.dtype)
        return matmul(gated, layer["out_proj"]["kernel"]), state
