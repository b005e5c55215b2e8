"""Transformer building blocks, pure-functional JAX.

The reference delegates all modeling to HuggingFace AutoModelForCausalLM
(reference runtime/engine.py:119-140, serve/server.py:146-170); this module
implements the architecture described by its model configs
(reference configs/models/llama-7b.json: RMSNorm, RoPE, multi-head attention,
SwiGLU) natively: functions over explicit param pytrees, bf16-compute/
fp32-master friendly, XLA-fusable, with hooks for Pallas kernels in ops/.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

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
                     scaling: str = "none", factor: float = 1.0) -> jax.Array:
    """Inverse frequencies for RoPE [head_dim//2], fp32."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (base ** exponent)
    if scaling == "linear" and factor != 1.0:
        inv_freq = inv_freq / factor
    elif scaling == "ntk" and factor != 1.0:
        # NTK-aware: stretch the base instead of the positions
        adjusted = base * (factor ** (head_dim / max(head_dim - 2, 1)))
        inv_freq = 1.0 / (adjusted ** exponent)
    return inv_freq


def apply_rope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array) -> jax.Array:
    """Rotate [..., S, N, D] by position. positions: [..., S] int32."""
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # [...,S,D/2]
    cos = jnp.cos(angles)[..., :, None, :]   # [...,S,1,D/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def attention_mask(q_positions: jax.Array, kv_positions: jax.Array,
                   q_segments: Optional[jax.Array] = None,
                   kv_segments: Optional[jax.Array] = None,
                   causal: bool = True) -> jax.Array:
    """Boolean [B, Sq, Skv] mask: True = attend.

    Packed-sequence aware: tokens attend only within their own segment
    (segment id 0 = padding, never attended).
    """
    mask = jnp.ones(q_positions.shape[:-1] + (q_positions.shape[-1],
                    kv_positions.shape[-1]), dtype=bool)
    if causal:
        mask = q_positions[..., :, None] >= kv_positions[..., None, :]
    if q_segments is not None and kv_segments is not None:
        same = q_segments[..., :, None] == kv_segments[..., None, :]
        valid = kv_segments[..., None, :] != 0
        mask = mask & same & valid
    return mask


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          mask: Optional[jax.Array] = None) -> jax.Array:
    """Reference XLA attention. q:[B,Sq,Nq,D] k,v:[B,Skv,Nkv,D] -> [B,Sq,Nq,D].

    GQA: Nq must be a multiple of Nkv; kv heads are broadcast per group.
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
    return out.reshape(B, Sq, Nq, D).astype(q.dtype)


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


def attention_block(
    x: jax.Array,
    layer: Params,
    cfg: ModelConfig,
    positions: jax.Array,
    segment_ids: Optional[jax.Array],
    inv_freq: jax.Array,
    kv_cache: Optional[tuple[jax.Array, jax.Array]] = None,
    cache_offset: Optional[jax.Array] = None,
    attn_impl: str = "xla",
) -> tuple[jax.Array, Optional[tuple[jax.Array, jax.Array]]]:
    """Self-attention sublayer (pre-norm residual outside).

    With ``kv_cache=(k_cache, v_cache)`` of shape [B, S_max, Nkv, D] and
    ``cache_offset`` [B] (current lengths), the new K/V are written at the
    offset and attention runs over the cache — the decode path the
    reference's KVCacheManager never actually implements
    (defect SURVEY §2.4.2, reference server.py:199-204).
    """
    B, S, H = x.shape
    D, Nq, Nkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads

    q = jnp.einsum("bsh,hd->bsd", x, layer["q"]["kernel"]).reshape(B, S, Nq, D)
    k = jnp.einsum("bsh,hd->bsd", x, layer["k"]["kernel"]).reshape(B, S, Nkv, D)
    v = jnp.einsum("bsh,hd->bsd", x, layer["v"]["kernel"]).reshape(B, S, Nkv, D)
    if cfg.attention_bias:
        q = q + layer["q"]["bias"].reshape(Nq, D)
        k = k + layer["k"]["bias"].reshape(Nkv, D)
        v = v + layer["v"]["bias"].reshape(Nkv, D)

    q = apply_rope(q, positions, inv_freq)
    k = apply_rope(k, positions, inv_freq)

    new_cache = None
    if kv_cache is not None:
        k_cache, v_cache = kv_cache
        S_max = k_cache.shape[1]
        assert cache_offset is not None
        # scatter new tokens at each row's offset
        write_idx = cache_offset[:, None] + jnp.arange(S)[None, :]      # [B,S]
        b_idx = jnp.arange(B)[:, None].repeat(S, axis=1)
        k_cache = k_cache.at[b_idx, write_idx].set(k.astype(k_cache.dtype))
        v_cache = v_cache.at[b_idx, write_idx].set(v.astype(v_cache.dtype))
        new_cache = (k_cache, v_cache)
        report_impl("prefill_attention", "xla",
                    f"q{tuple(q.shape)} over a [{B}, {S_max}] cache")
        kv_positions = jnp.arange(S_max)[None, :].repeat(B, axis=0)
        valid = kv_positions < (cache_offset[:, None] + S)
        mask = (positions[..., :, None] >= kv_positions[..., None, :]) & valid[:, None, :]
        out = dot_product_attention(q, k_cache.astype(q.dtype),
                                    v_cache.astype(q.dtype), mask)
    elif attn_impl == "flash":
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
        mask = attention_mask(positions, positions, segment_ids, segment_ids)
        out = dot_product_attention(q, k, v, mask)

    out = out.reshape(B, S, Nq * D)
    out = jnp.einsum("bsd,dh->bsh", out, layer["o"]["kernel"])
    return out.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# Dense / MoE feed-forward
# ---------------------------------------------------------------------------

def _activate(x: jax.Array, activation: str) -> jax.Array:
    if activation == "silu":
        return jax.nn.silu(x)
    if activation == "gelu":
        return jax.nn.gelu(x)
    return jax.nn.relu(x)


def mlp_block(x: jax.Array, layer: Params, cfg: ModelConfig,
              matmul=None) -> jax.Array:
    """Gated FFN (SwiGLU for silu — reference llama-7b.json activation).

    ``matmul(a, w)`` overrides the kernel contraction — the serving decode
    path injects the in-kernel-dequant W4A16 Pallas matmul for
    Quant4Tensor weights (serve/decode.py) without forking the FFN
    semantics."""
    if matmul is None:
        matmul = lambda a, w: jnp.einsum("bsh,hf->bsf", a, w)
    gate = matmul(x, layer["gate"]["kernel"])
    up = matmul(x, layer["up"]["kernel"])
    h = _activate(gate, cfg.activation) * up
    return matmul(h, layer["down"]["kernel"]).astype(x.dtype)


def moe_block(x: jax.Array, layer: Params, cfg: ModelConfig,
              router_key: Optional[jax.Array] = None) -> tuple[jax.Array, jax.Array]:
    """Token-choice top-k MoE with GShard-style capacity dispatch.

    Static shapes throughout (XLA requirement): tokens are dispatched into
    a fixed per-expert capacity C; overflow tokens fall back to the
    residual stream. Experts carry a leading E axis that the mesh shards
    on 'ep' (SURVEY §2.2: EP absent from the reference).

    Dispatch is SORT-based, not one-hot: the classic GShard one-hot
    einsum builds [N, E, C] dispatch/combine tensors whose memory grows
    ~quadratically in tokens (C itself is O(N/E)); at b8 x S4096 on
    gpt-moe-test scales that tensor alone was ~5 GB *per layer* — the
    measured 20.8 GB OOM of round 4 (battery 11, VERDICT r4 item 7).
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
    logits = jnp.einsum("nh,he->ne", xt.astype(jnp.float32),
                        layer["router"]["kernel"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # [N,E]

    # top-k expert choice per token
    top_p, top_e = jax.lax.top_k(probs, K)                       # [N,K]
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

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
