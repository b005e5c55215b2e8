"""The plain reference of a decoder whose mixers are gated short convolutions
and, one layer in four, grouped-query attention, over dense and
sparse-expert feed-forwards, as ``model_type: lfm2_moe`` describes it
(LiquidAI/LFM2-8B-A1B, config.json; written from ISSUE 55's equations and
the catalog row, not from the program).

One sequence x [S, H] at a time; RMSNorm with a learned scale and
``norm_eps`` throughout; decoder layer i is a mixer then a feed-forward,
each under its own norm on the residual stream:

    conv  (layer_types[i] == "conv"):
        [B | C | u] = norm(x) W_in            H -> 3 H, no bias, that order
        z    = B * u
        y_t  = sum_{j=0..K-1} w[j] * z_{t-(K-1)+j}   depthwise, causal, zeros
                                              before the sequence, NO bias,
                                              NO activation (K = conv_L_cache)
        x    = x + (C * y) W_out
    full_attention:
        q, k, v = norm(x) W_q, W_k, W_v       n_q / n_kv heads of D = H / n_q
        q, k = RMSNorm_D(q; w_qn), RMSNorm_D(k; w_kn)   over each head's D
        q, k = rope(q), rope(k)               rotate-half, all D, theta
        x    = x + softmax(q k^T / sqrt(D), causal) v W_o
    dense  (i < num_dense_layers):   x = x + W_2(silu(W_1 m) * W_3 m)
    experts (the rest):
        s    = sigmoid(m W_r)                 float32, every expert
        pick = top-k of s + expert_bias       the bias picks ...
        w    = s[pick] / sum(s[pick])         ... and does not weigh
        x    = x + sum_e w_e W_2e(silu(W_1e m) * W_3e m) * routed_scaling_factor
    logits = RMSNorm(x_L; w_final) E^T        the head IS the embedding

Float32 ``jax.numpy`` with full-precision matrix multiplications, a full
causal convolution and a full attention over the whole sequence, no cache,
no kernels, no batching. Independent of the program's ``models/`` and
``ops/``; it reads only that program's parameter tree (one stack a layer
KIND, indexed by the layer's rank among its kind):

    blocks.conv.{norm.scale [Lc,H], in_proj.kernel [Lc,H,3H],
                 conv.kernel [Lc,K,H], out_proj.kernel [Lc,H,H]}
    blocks.attn.{norm.scale, q, k, v, o .kernel, q_norm.scale [La,D],
                 k_norm.scale [La,D]}
    blocks.mlp.{norm.scale, gate, up, down .kernel}
    blocks.moe.{norm.scale, router.kernel [Le,H,E], router.bias [Le,E],
                gate.kernel, up.kernel [Le,E,H,F], down.kernel [Le,E,F,H]}
    embed.embedding [V,H]; final_norm.scale [H]

It computes in BLOCKS, so that at the published widths it fits on a chip
beside the server's 10.8 GB of weights: one expert's three kernels are cast
to float32 at a time (132 MB), the dense MLP by ``MLP_BLOCKS`` column blocks
of its width, the attention a key-value head's query group at a time, the
head by ``HEAD_BLOCKS`` row blocks of the vocabulary (at 65,536 x 2,048:
34 MB a block).

Departures from the published form, each the program's own: a norm's weight
is stored as ``scale`` with the weight being ``1 + scale``; the conv kernel
lies [K, H] (``w[:, j]`` is ``kernel[j]``); the renormalisation adds 1e-20
where the published code adds 1e-6 (under 1e-6 relative: the chosen scores
sum to ~2).

``logits(..., with_margin=True)`` also gives, for every position, the least
over the expert layers of the distance between the k-th and the k+1-th
largest biased score: how far the position's chosen SET is from being
another (``benchmark/runners/hybrid.py`` says what a check does with it).

``wrong`` computes a WRONG model on purpose, to show that a comparison
against this reference fails when it should: ``float8`` (every matmul
operand rounded to float8_e4m3: the nearest precision under the
configuration's bfloat16), ``swap_bc`` (B and C of the in-projection
swapped), ``taps_reversed``, ``stale_window`` (the conv starts from the
window a former occupant of the slot left: here the sequence's own LAST
K-1 rows, not zeros), ``no_expert_bias``, ``bias_as_weight``,
``no_qk_norm``, ``rope_on_conv`` (rotary positions on a conv layer's
``u``, in heads of D), ``no_rope``, ``drop_conv`` (the conv's taps set to
the identity: ``y = z``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
MLP_BLOCKS = 4
HEAD_BLOCKS = 16

WRONG = ("float8", "swap_bc", "taps_reversed", "stale_window",
         "no_expert_bias", "bias_as_weight", "no_qk_norm", "rope_on_conv",
         "no_rope", "drop_conv")


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _mm(a, b, float8=False):
    if float8:
        a, b = (_f32(t.astype(jnp.float8_e4m3fn)) for t in (a, b))
    return jnp.matmul(a, b, precision=_HIGHEST)


def _rms_norm(x, scale, eps):
    # (the program stores a norm's weight as ``scale`` = weight - 1)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + scale)


def _rope(x, theta):
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]         # value i with i + D/2
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, scale, *, eps):
    return _rms_norm(x, _f32(scale), eps)


@functools.partial(jax.jit, static_argnames=(
    "eps", "float8", "swap_bc", "taps_reversed", "stale_window",
    "rope_heads", "theta", "drop_conv"))
def _shortconv(x, w, *, eps, float8, swap_bc, taps_reversed, stale_window,
               rope_heads, theta, drop_conv):
    """x + the gated short convolution of norm(x): [S, H]."""
    w = jax.tree_util.tree_map(_f32, w)
    s, h = x.shape
    bcu = _mm(_rms_norm(x, w["norm"], eps), w["in_proj"], float8)
    b, c, u = bcu[:, :h], bcu[:, h:2 * h], bcu[:, 2 * h:]
    if swap_bc:
        b, c = c, b
    if rope_heads:
        u = _rope(u.reshape(s, rope_heads, -1), theta).reshape(s, h)
    z = b * u
    taps = w["conv"][::-1] if taps_reversed else w["conv"]     # [K, H]
    k = taps.shape[0]
    before = z[s - (k - 1):] if stale_window else jnp.zeros((k - 1, h))
    padded = jnp.concatenate([before, z], 0)
    y = z if drop_conv else sum(taps[j] * padded[j:j + s] for j in range(k))
    return x + _mm(c * y, w["out_proj"], float8)


@functools.partial(jax.jit, static_argnames=(
    "n_q", "n_kv", "eps", "theta", "float8", "qk_norm"))
def _attention(x, w, *, n_q, n_kv, eps, theta, float8, qk_norm):
    """x + the grouped-query attention of norm(x): [S, H]."""
    w = jax.tree_util.tree_map(_f32, w)
    s = x.shape[0]
    m = _rms_norm(x, w["norm"], eps)
    q = _mm(m, w["q"], float8).reshape(s, n_q, -1)
    k = _mm(m, w["k"], float8).reshape(s, n_kv, -1)
    v = _mm(m, w["v"], float8).reshape(s, n_kv, -1)
    if qk_norm:     # over each head's D values, one [D] scale for all heads
        q, k = _rms_norm(q, w["q_norm"], eps), _rms_norm(k, w["k_norm"], eps)
    if theta:
        q, k = _rope(q, theta), _rope(k, theta)
    d, r = q.shape[-1], n_q // n_kv
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    outs = []
    for j in range(n_kv):       # a key-value head's query group at a time
        scores = jnp.einsum("qnd,kd->nqk", q[:, j * r:(j + 1) * r], k[:, j],
                            precision=_HIGHEST) / d ** 0.5
        scores = jnp.where(causal[None], scores, -jnp.inf)
        outs.append(jnp.einsum("nqk,kd->qnd", jax.nn.softmax(scores, -1),
                               v[:, j], precision=_HIGHEST))
    att = jnp.concatenate(outs, 1).reshape(s, n_q * d)
    return x + _mm(att, w["o"], float8)


@functools.partial(jax.jit, static_argnames=("width", "float8"))
def _mlp_block(m, gate, up, down, i, j, *, width, float8):
    """Column block j (``width`` columns) of layer i's gated MLP: [S, H]
    from the normed stream. The stacks come in whole; only this block's
    slices are cast."""
    def cols(stack):
        return _f32(jax.lax.dynamic_slice_in_dim(stack[i], j * width, width,
                                                 axis=1))
    hidden = jax.nn.silu(_mm(m, cols(gate), float8)) * _mm(m, cols(up),
                                                           float8)
    rows = _f32(jax.lax.dynamic_slice_in_dim(down[i], j * width, width,
                                             axis=0))
    return _mm(hidden, rows, float8)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "eps", "renormalise", "scale", "float8", "no_bias",
    "bias_as_weight"))
def _route(x, norm, router, bias, *, top_k, eps, renormalise, scale, float8,
           no_bias, bias_as_weight):
    """(norm(x) [S, H], weights [S, E]: a position's routing weights at its
    chosen experts' columns, zero elsewhere, margin [S]: the k-th largest
    biased score less the k+1-th)."""
    u = _rms_norm(x, _f32(norm), eps)
    s = jax.nn.sigmoid(_mm(u, _f32(router), float8))
    pick = s if no_bias else s + _f32(bias)
    top_p, top_e = jax.lax.top_k(pick, top_k + 1)
    margin, top_e = top_p[:, top_k - 1] - top_p[:, top_k], top_e[:, :top_k]
    top_w = jnp.take_along_axis(pick if bias_as_weight else s, top_e, -1)
    if renormalise:
        top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
    rows = jnp.arange(x.shape[0])[:, None]
    return u, jnp.zeros_like(s).at[rows, top_e].set(top_w * scale), margin


@functools.partial(jax.jit, static_argnames=("float8",))
def _expert(u, weight, gate, up, down, i, e, *, float8):
    """W_2(silu(W_1 u) * W_3 u) of layer i's expert e on EVERY position,
    times its column of the routing weights. The stacks come in whole; only
    this expert's slices are cast."""
    hidden = jax.nn.silu(_mm(u, _f32(gate[i, e]), float8)) * _mm(
        u, _f32(up[i, e]), float8)
    return weight[:, None] * _mm(hidden, _f32(down[i, e]), float8)


@functools.partial(jax.jit, static_argnames=("width", "float8"))
def _head_block(x, embedding, j, *, width, float8):
    """The logits of ``width`` rows of the vocabulary from row j * width:
    the head is the embedding's transpose."""
    rows = _f32(jax.lax.dynamic_slice_in_dim(embedding, j * width, width,
                                             axis=0))
    return _mm(x, rows.T, float8)


def layer_table(config: dict) -> list[tuple[str, str]]:
    """[(mixer, feed-forward)] of the decoder layers: ("conv" |
    "full_attention", "dense" | "experts"), from ``layer_types`` and
    ``num_dense_layers``."""
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"]:
        raise ValueError("layer_types names each decoder layer once")
    return [(t, "dense" if i < config["num_dense_layers"] else "experts")
            for i, t in enumerate(types)]


def hidden(params, tokens, config: dict, wrong: str | None = None):
    """(final hidden states [S, H] (before the last norm) of ONE sequence
    of token ids, float32; the routing margin [S])."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"no wrong model {wrong!r}: {WRONG}")
    b = params["blocks"]
    eps = float(config["norm_eps"])
    float8 = wrong == "float8"
    theta = float(config["rope_theta"])
    n_q = config["num_attention_heads"]
    x = _f32(params["embed"]["embedding"][jnp.asarray(tokens, jnp.int32)])
    margin = jnp.full((x.shape[0],), jnp.inf)
    rank = {"conv": 0, "full_attention": 0, "dense": 0, "experts": 0}
    for mixer, ffn in layer_table(config):
        i = rank[mixer]
        rank[mixer] += 1
        if mixer == "conv":
            c = b["conv"]
            x = _shortconv(
                x, {"norm": c["norm"]["scale"][i],
                    "in_proj": c["in_proj"]["kernel"][i],
                    "conv": c["conv"]["kernel"][i],
                    "out_proj": c["out_proj"]["kernel"][i]},
                eps=eps, float8=float8, swap_bc=wrong == "swap_bc",
                taps_reversed=wrong == "taps_reversed",
                stale_window=wrong == "stale_window",
                rope_heads=n_q if wrong == "rope_on_conv" else 0,
                theta=theta, drop_conv=wrong == "drop_conv")
        else:
            a = b["attn"]
            x = _attention(
                x, {"norm": a["norm"]["scale"][i],
                    **{n: a[n]["kernel"][i] for n in ("q", "k", "v", "o")},
                    "q_norm": a["q_norm"]["scale"][i],
                    "k_norm": a["k_norm"]["scale"][i]},
                n_q=n_q, n_kv=config["num_key_value_heads"], eps=eps,
                theta=0.0 if wrong == "no_rope" else theta, float8=float8,
                qk_norm=wrong != "no_qk_norm")
        i = rank[ffn]
        rank[ffn] += 1
        if ffn == "dense":
            mlp = b["mlp"]
            u = _norm(x, mlp["norm"]["scale"][i], eps=eps)
            width = mlp["up"]["kernel"].shape[-1]
            blocks = MLP_BLOCKS if width % MLP_BLOCKS == 0 else 1
            for j in range(blocks):     # by column blocks of its width
                x = x + _mlp_block(
                    u, mlp["gate"]["kernel"], mlp["up"]["kernel"],
                    mlp["down"]["kernel"], i, j, width=width // blocks,
                    float8=float8)
        else:
            moe = b["moe"]
            u, weights, m = _route(
                x, moe["norm"]["scale"][i], moe["router"]["kernel"][i],
                moe["router"]["bias"][i],
                top_k=config["num_experts_per_tok"], eps=eps,
                renormalise=bool(config["norm_topk_prob"]),
                scale=float(config["routed_scaling_factor"]), float8=float8,
                no_bias=wrong == "no_expert_bias",
                bias_as_weight=wrong == "bias_as_weight")
            margin = jnp.minimum(margin, m)
            for e in range(config["num_experts"]):
                x = x + _expert(u, weights[:, e], moe["gate"]["kernel"],
                                moe["up"]["kernel"], moe["down"]["kernel"],
                                i, e, float8=float8)
    return x, margin


def logits(params, tokens, config: dict, positions=None,
           wrong: str | None = None, with_margin: bool = False,
           round_to: int = 0):
    """Logits [len(positions) or S, V] of one sequence, or with
    ``with_margin`` (logits, routing margin [len(positions) or S]).
    ``round_to``: zeros follow the sequence up to a multiple of it (one
    compiled shape for many lengths; no earlier position of a causal model
    sees them; ``stale_window`` reads the padded end)."""
    tokens = list(tokens)
    if round_to and len(tokens) % round_to:
        if positions is None:
            positions = range(len(tokens))
        tokens = tokens + [0] * (round_to - len(tokens) % round_to)
    x, margin = hidden(params, tokens, config, wrong)
    if positions is not None:
        at = jnp.asarray(list(positions), jnp.int32)
        x, margin = x[at], margin[at]
    x = _norm(x, params["final_norm"]["scale"], eps=float(config["norm_eps"]))
    embedding = params["embed"]["embedding"]
    vocab = embedding.shape[0]
    blocks = HEAD_BLOCKS if vocab % HEAD_BLOCKS == 0 else 1
    lg = jnp.concatenate(
        [_head_block(x, embedding, j, width=vocab // blocks,
                     float8=wrong == "float8") for j in range(blocks)], -1)
    return (lg, margin) if with_margin else lg
