"""The plain reference of a sparse-expert decoder, as OLMoE's published
``OlmoeDecoderLayer`` describes it (Muennighoff et al. 2024, "OLMoE: Open
Mixture-of-Experts Language Models"; ``model_type: olmoe``).

Pre-norm block, one sequence x [S, H] at a time:

    h = x + O( attn( rope(RMSNorm_q(Wq n1(x))), rope(RMSNorm_k(Wk n1(x))),
                     Wv n1(x) ) )
    p = softmax_fp32(Wr n2(h)) over all E experts;  S = top-k(p)
    y = h + sum_{e in S} p_e * down_e( silu(gate_e n2(h)) * up_e n2(h) )

RMSNorm_q / RMSNorm_k span the WHOLE query / key projection (Nq*D, Nkv*D
wide), before the split into heads and before rope (configuration key
``qk_norm: "projection"``; ``"none"`` leaves them out). The top-k weights
stay as the softmax over all experts gave them unless the configuration's
``norm_topk_prob`` is true (then they are renormalised to sum to 1).
Dropless: every position is served by all k of its experts.

Float32 ``jax.numpy`` with full-precision matrix multiplications, no
cache, no kernels, no sorting, no batching: EVERY expert is applied to
every position and masked by the top-k weights (E / k times the work,
nothing at the few hundred positions of a check), ONE expert's weights
cast to float32 at a time so that it fits beside a loaded engine (a
layer's 64 experts in float32 are 1.6 GB). Independent of ``models/``; it
reads only that program's parameter tree:

    embed.embedding [V,H]; blocks.{q,k,v,o}.kernel [L,in,out];
    blocks.{q_norm,k_norm}.scale [L,Nq*D] / [L,Nkv*D] (with qk_norm);
    blocks.moe.router.kernel [L,H,E];
    blocks.moe.{gate,up}.kernel [L,E,H,F]; blocks.moe.down.kernel [L,E,F,H];
    blocks.{attn_norm,mlp_norm}.scale [L,H]; final_norm.scale [H];
    lm_head.kernel [H,V]

One departure from the published form, the program's own: a norm's weight
is stored as ``scale`` with the weight being ``1 + scale`` (the q/k norms
too).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
_mm = functools.partial(jnp.matmul, precision=_HIGHEST)


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + scale)


def _rope(x, theta):
    """x [S, N, D]: rotate the pair (i, i + D/2) of every head by
    position * theta**(-2i/D)."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


@functools.partial(jax.jit, static_argnames=("n_q", "n_kv", "eps", "theta",
                                             "qk_norm"))
def _attention(x, w, *, n_q, n_kv, eps, theta, qk_norm):
    """x + O(attn(...)) on one sequence x [S, H]."""
    w = jax.tree_util.tree_map(_f32, w)
    s = x.shape[0]
    h = _rms_norm(x, w["attn_norm"], eps)
    q, k = _mm(h, w["q"]), _mm(h, w["k"])
    if qk_norm == "projection":
        q = _rms_norm(q, w["q_norm"], eps)
        k = _rms_norm(k, w["k_norm"], eps)
    q = _rope(q.reshape(s, n_q, -1), theta)
    k = _rope(k.reshape(s, n_kv, -1), theta)
    v = _mm(h, w["v"]).reshape(s, n_kv, -1)
    d = q.shape[-1]
    k, v = (jnp.repeat(a, n_q // n_kv, axis=1) for a in (k, v))
    scores = jnp.einsum("qnd,knd->nqk", q, k, precision=_HIGHEST) / d ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, -1), v,
                     precision=_HIGHEST)
    return x + _mm(att.reshape(s, n_q * d), w["o"])


@functools.partial(jax.jit, static_argnames=("top_k", "eps", "renormalise"))
def _route(x, mlp_norm, router, *, top_k, eps, renormalise):
    """(n2(h) [S, H], weights [S, E]: a position's top-k router
    probabilities at its experts' columns, zero elsewhere)."""
    h = _rms_norm(x, _f32(mlp_norm), eps)
    p = jax.nn.softmax(_mm(h, _f32(router)), -1)
    top_p, top_e = jax.lax.top_k(p, top_k)
    if renormalise:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return h, jnp.zeros_like(p).at[rows, top_e].set(top_p)


@jax.jit
def _expert(h, weights, moe, i, e):
    """Expert e of layer i on EVERY position, weighted by its column of
    the routing weights (zero where the position did not choose it). The
    stacks come in whole and only this expert's slices are cast."""
    gate, up, down = (_f32(moe[n]["kernel"][i, e])
                      for n in ("gate", "up", "down"))
    y = _mm(jax.nn.silu(_mm(h, gate)) * _mm(h, up), down)
    return weights[:, e, None] * y


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_scale, head, *, eps):
    return _mm(_rms_norm(x, _f32(final_scale), eps), _f32(head))


def hidden(params, tokens, config: dict):
    """Final hidden states [S, H] (before the last norm) of ONE sequence of
    token ids, float32."""
    b, moe = params["blocks"], params["blocks"]["moe"]
    experts = {n: moe[n] for n in ("gate", "up", "down")}
    qk_norm = config.get("qk_norm", "none")
    eps = float(config["rms_norm_eps"])
    x = _f32(params["embed"]["embedding"][jnp.asarray(tokens, jnp.int32)])
    for i in range(config["num_hidden_layers"]):
        w = {"attn_norm": b["attn_norm"]["scale"][i],
             "q": b["q"]["kernel"][i], "k": b["k"]["kernel"][i],
             "v": b["v"]["kernel"][i], "o": b["o"]["kernel"][i]}
        if qk_norm == "projection":
            w["q_norm"] = b["q_norm"]["scale"][i]
            w["k_norm"] = b["k_norm"]["scale"][i]
        x = _attention(x, w, n_q=config["num_attention_heads"],
                       n_kv=config["num_key_value_heads"], eps=eps,
                       theta=float(config["rope_theta"]), qk_norm=qk_norm)
        h, weights = _route(
            x, b["mlp_norm"]["scale"][i], moe["router"]["kernel"][i],
            top_k=config["num_experts_per_tok"], eps=eps,
            renormalise=bool(config.get("norm_topk_prob", False)))
        for e in range(config["num_experts"]):
            x = x + _expert(h, weights, experts, i, e)
    return x


def logits(params, tokens, config: dict, positions=None):
    """Logits [len(positions) or S, V] of one sequence."""
    x = hidden(params, tokens, config)
    if positions is not None:
        x = x[jnp.asarray(positions, jnp.int32)]
    return _head(x, params["final_norm"]["scale"],
                 params["lm_head"]["kernel"],
                 eps=float(config["rms_norm_eps"]))
