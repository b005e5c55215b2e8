"""The plain reference: a dense pre-norm decoder as its papers describe it.

RMSNorm (Zhang & Sennrich 2019), rotary position embedding in the
rotate-half layout of the published checkpoints (Su et al. 2021; the
``config.json`` families of Mistral-7B and InternLM2 use it), grouped-query
causal attention (Ainslie et al. 2023), a SwiGLU feed-forward (Shazeer 2020)
and an untied output head. Straightforward ``jax.numpy`` in float32 with
full-precision matrix multiplications, no cache, no kernels, no batching:
one sequence at a time, one layer's weights cast at a time, so it fits
beside a loaded engine or trainer. Independent of ``models/gpt.py``; it only
reads that program's parameter tree:

    embed.embedding [V,H]; blocks.{q,k,v,o}.kernel [L,in,out];
    blocks.mlp.{gate,up,down}.kernel [L,in,out];
    blocks.{attn_norm,mlp_norm}.scale [L,H]; final_norm.scale [H];
    lm_head.kernel [H,V]

One departure from the published form, the program's own: a norm's weight
is stored as ``scale`` with the weight being ``1 + scale``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


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


@functools.partial(jax.jit, static_argnames=("n_q", "n_kv", "eps", "theta"))
def _layer(x, w, *, n_q, n_kv, eps, theta):
    """One block on one sequence x [S, H]; w holds that layer's weights."""
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    w = jax.tree_util.tree_map(_f32, w)
    s = x.shape[0]
    h = _rms_norm(x, w["attn_norm"], eps)
    q = _rope(mm(h, w["q"]).reshape(s, n_q, -1), theta)
    k = _rope(mm(h, w["k"]).reshape(s, n_kv, -1), theta)
    v = mm(h, w["v"]).reshape(s, n_kv, -1)
    d = q.shape[-1]
    group = n_q // n_kv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qnd,knd->nqk", q, k, precision=_HIGHEST) / d ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, -1), v,
                     precision=_HIGHEST)
    x = x + mm(att.reshape(s, n_q * d), w["o"])
    h = _rms_norm(x, w["mlp_norm"], eps)
    return x + mm(jax.nn.silu(mm(h, w["gate"])) * mm(h, w["up"]), w["down"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_scale, head, *, eps):
    return jnp.matmul(_rms_norm(x, _f32(final_scale), eps), _f32(head),
                      precision=_HIGHEST)


def _layer_weights(blocks, i: int) -> dict:
    b = blocks
    return {"attn_norm": b["attn_norm"]["scale"][i],
            "mlp_norm": b["mlp_norm"]["scale"][i],
            "q": b["q"]["kernel"][i], "k": b["k"]["kernel"][i],
            "v": b["v"]["kernel"][i], "o": b["o"]["kernel"][i],
            "gate": b["mlp"]["gate"]["kernel"][i],
            "up": b["mlp"]["up"]["kernel"][i],
            "down": b["mlp"]["down"]["kernel"][i]}


def hidden(params, tokens, config: dict):
    """Final hidden states [S, H] (before the last norm) of ONE sequence of
    token ids, float32."""
    n_q = config["num_attention_heads"]
    x = _f32(params["embed"]["embedding"][jnp.asarray(tokens, jnp.int32)])
    for i in range(config["num_hidden_layers"]):
        x = _layer(x, _layer_weights(params["blocks"], i), n_q=n_q,
                   n_kv=config["num_key_value_heads"],
                   eps=float(config["rms_norm_eps"]),
                   theta=float(config["rope_theta"]))
    return x


def logits(params, tokens, config: dict, positions=None):
    """Logits [len(positions) or S, V] of one sequence."""
    x = hidden(params, tokens, config)
    if positions is not None:
        x = x[jnp.asarray(positions, jnp.int32)]
    return _head(x, params["final_norm"]["scale"],
                 params["lm_head"]["kernel"],
                 eps=float(config["rms_norm_eps"]))


def next_token_loss(params, tokens, config: dict, block: int = 1024):
    """Sum of the next-token cross-entropies of ONE sequence and the number
    of predicted positions (S - 1); logits are made ``block`` positions at a
    time so that [S, V] never exists."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = hidden(params, tokens, config)
    total = 0.0
    n = tokens.shape[0] - 1
    for a in range(0, n, block):
        b = min(a + block, n)
        lg = _head(x[a:b], params["final_norm"]["scale"],
                   params["lm_head"]["kernel"],
                   eps=float(config["rms_norm_eps"]))
        logz = jax.nn.logsumexp(lg, -1)
        tgt = jnp.take_along_axis(lg, tokens[a + 1:b + 1, None], -1)[:, 0]
        total += float(jnp.sum(logz - tgt))
    return total, n
