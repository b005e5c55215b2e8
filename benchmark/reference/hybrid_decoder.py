"""The plain reference of a hybrid state-space / attention / sparse-expert
decoder, as ``model_type: nemotron_h`` describes it
(NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, config.json; Mamba-2: Dao & Gu 2024,
"Transformers are SSMs"; the router: DeepSeek-V3's sigmoid scores with a
selection bias).

A layer table (``hybrid_override_pattern``, one letter a layer). Layer ``l``
of kind ``c``, one sequence x [S, H] at a time:

    x <- x + mixer_c(RMSNorm(x; w_l, eps))

- ``M`` Mamba-2: ``[z | xBC | dt] = u W_in``; a depthwise causal conv of
  width K over xBC (zeros before the first token), then silu; xBC splits
  into x [nh, P], B [G, N], C [G, N]; ``D_t = softplus(dt_t + dt_bias)``,
  ``A = -exp(A_log)``;
  ``h_t[i] = exp(D_t[i] A[i]) h_{t-1}[i] + D_t[i] outer(x_t[i], B_t[g(i)])``,
  ``y_t[i] = h_t[i] C_t[g(i)] + Dskip[i] x_t[i]``, as the plain recurrence
  over t (no chunks); ``y <- y * silu(z)``, THEN an RMS norm over each of
  the G channel groups; ``out = y W_out``.
- ``*`` attention: grouped-query, causal, softmax(q k^T / sqrt(D)) v, no
  bias and NO position embedding.
- ``E`` experts: ``s = sigmoid(u W_r)``; the chosen set is the top-k of
  ``s + b`` (the selection bias picks, it does not weigh); weights
  ``scale * s_e / (sum of the chosen s + 1e-20)``;
  ``expert(u) = W_down relu(W_up u)^2`` (no gate); plus the shared expert.
  Only the experts HELD here (``n_routed_experts`` of the router's
  ``router_experts``, from ``first_expert``) are applied: what the absent
  ones would add is left out, as in the program.

After the last layer a final RMSNorm and the untied head, over the rows of
the vocabulary held here.

Float32 ``jax.numpy`` with full-precision matrix multiplications, no cache,
no kernels, no chunks, no sorting, no batching: every held expert is
applied to every position and masked by the routing weights, one expert's
weights cast to float32 at a time. Independent of ``models/``; it reads
only that program's parameter tree (one stack a layer KIND, indexed by the
layer's rank among its kind):

    blocks.ssm.{norm.scale [Lm,H], in_proj.kernel [Lm,H,d_in+C+nh],
                conv.kernel [Lm,K,C], conv.bias [Lm,C], dt_bias, A_log, D
                [Lm,nh], gate_norm.scale [Lm,d_in], out_proj.kernel}
    blocks.attn.{norm.scale, q, k, v, o .kernel}
    blocks.moe.{norm.scale, router.kernel [Le,H,Er], router.bias [Le,Er],
                up.kernel [Le,E,F,H] (out, in) or [Le,E,H,F],
                down.kernel [Le,E,F,H],
                shared.up.kernel [Le,H,Fs], shared.down.kernel [Le,Fs,H]}
    embed.embedding [V,H]; final_norm.scale [H]; lm_head.kernel [H,V]

Departures from the published form, each the program's own: a norm's
weight is stored as ``scale`` with the weight being ``1 + scale`` (the
gated norm's too); the conv kernel lies [K, C] (``w_c[:, j]`` is
``kernel[j]``); a routed expert's up kernel lies [F, H] (out, in) where
F is no multiple of 128, else [H, F]: read off its shape.

``logits(..., with_margin=True)`` also gives, for every position, the
least over the expert layers of the distance between the 6th and the 7th
largest biased score (top-k's k-th and k+1-th): how far the position's
chosen SET is from being another. A server that rounds its stream to
bfloat16 picks another set where that distance is under its rounding, and
either set is right there (``benchmark/runners/hybrid.py`` leaves such
tokens out of its sample).

``wrong`` computes a WRONG model on purpose, to show that a check against
this reference fails when it should (``benchmark/runners/hybrid.py``):
``float8`` (every matmul operand rounded to float8_e4m3), ``float8_experts``
(only the operands of the routed experts' two matmuls), ``norm_before_gate``,
``softmax_scores``, ``bias_as_weight``, ``rope`` (rotary positions in
attention), ``padding_in_state`` (``pad_to``: the prompt's first
``prompt_len`` tokens are followed by padding up to ``pad_to`` rows that a
state-space layer lets into its state, as a prefill bucket's would be).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _mm(a, b, float8=False):
    if float8:
        a, b = (_f32(t.astype(jnp.float8_e4m3fn)) for t in (a, b))
    return jnp.matmul(a, b, precision=_HIGHEST)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + scale)


def _rope(x, theta):
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


@functools.partial(jax.jit, static_argnames=(
    "nh", "p", "n", "g", "eps", "float8", "norm_before_gate"))
def _mamba(x, w, keep, *, nh, p, n, g, eps, float8, norm_before_gate):
    """x + mixer_M(norm(x)) on one sequence x [S, H]. ``keep`` [S] is 1
    where a position enters the state (0: it decays by 1 and adds
    nothing, and the conv reads it as it is)."""
    w = jax.tree_util.tree_map(_f32, w)
    s = x.shape[0]
    d_in, gn = nh * p, g * n
    u = _rms_norm(x, w["norm"], eps)
    zxbcdt = _mm(u, w["in_proj"], float8)
    z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:2 * d_in + 2 * gn],
                  zxbcdt[:, 2 * d_in + 2 * gn:])
    k = w["conv_kernel"].shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc], 0)
    conv = w["conv_bias"] + sum(w["conv_kernel"][j] * padded[j:j + s]
                                for j in range(k))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_in].reshape(s, nh, p)
    b = jnp.repeat(xbc[:, d_in:d_in + gn].reshape(s, g, n), nh // g, axis=1)
    c = jnp.repeat(xbc[:, d_in + gn:].reshape(s, g, n), nh // g, axis=1)
    step = jax.nn.softplus(dt + w["dt_bias"]) * keep[:, None]    # [S, nh]
    a = -jnp.exp(w["A_log"])

    def one(h, t):
        x_t, b_t, c_t, d_t = t
        h = (jnp.exp(d_t * a)[:, None, None] * h
             + d_t[:, None, None] * x_t[:, :, None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], -1) + w["D"][:, None] * x_t

    _, y = jax.lax.scan(one, jnp.zeros((nh, p, n)), (xs, b, c, step))
    y = y.reshape(s, d_in)

    def group_norm(v):
        vg = v.reshape(s, g, d_in // g)
        vg = vg * jax.lax.rsqrt(jnp.mean(vg * vg, -1, keepdims=True) + eps)
        return vg.reshape(s, d_in) * (1.0 + w["gate_norm"])

    y = (group_norm(y) * jax.nn.silu(z) if norm_before_gate
         else group_norm(y * jax.nn.silu(z)))
    return x + _mm(y, w["out_proj"], float8)


@functools.partial(jax.jit, static_argnames=("n_q", "n_kv", "eps", "float8",
                                             "theta"))
def _attention(x, w, seen, *, n_q, n_kv, eps, float8, theta):
    """x + O(attn(...)) on one sequence x [S, H]; ``seen`` [S] marks the
    keys later positions may attend to (all, but for padding)."""
    w = jax.tree_util.tree_map(_f32, w)
    s = x.shape[0]
    h = _rms_norm(x, w["norm"], eps)
    q = _mm(h, w["q"], float8).reshape(s, n_q, -1)
    k = _mm(h, w["k"], float8).reshape(s, n_kv, -1)
    v = _mm(h, w["v"], float8).reshape(s, n_kv, -1)
    if theta:
        q, k = _rope(q, theta), _rope(k, theta)
    d = q.shape[-1]
    k, v = (jnp.repeat(t, n_q // n_kv, axis=1) for t in (k, v))
    scores = jnp.einsum("qnd,knd->nqk", q, k, precision=_HIGHEST) / d ** 0.5
    idx = jnp.arange(s)
    mask = (idx[:, None] >= idx[None, :]) & (
        (seen[None, :] > 0) | (idx[:, None] == idx[None, :]))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    att = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, -1), v,
                     precision=_HIGHEST)
    return x + _mm(att.reshape(s, n_q * d), w["o"], float8)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "eps", "renormalise", "scale", "float8", "softmax_scores",
    "bias_as_weight"))
def _route(x, norm, router, bias, *, top_k, eps, renormalise, scale, float8,
           softmax_scores, bias_as_weight):
    """(norm(x) [S, H], weights [S, Er]: a position's routing weights at
    its chosen experts' columns, zero elsewhere, margin [S]: the k-th
    largest biased score less the k+1-th)."""
    u = _rms_norm(x, _f32(norm), eps)
    logits = _mm(u, _f32(router), float8)
    s = jax.nn.softmax(logits, -1) if softmax_scores else jax.nn.sigmoid(
        logits)
    pick = s + _f32(bias)
    top_p, top_e = jax.lax.top_k(pick, top_k + 1)
    margin, top_e = top_p[:, top_k - 1] - top_p[:, top_k], top_e[:, :top_k]
    top_w = jnp.take_along_axis(pick if bias_as_weight else s, top_e, -1)
    if renormalise:
        top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
    rows = jnp.arange(x.shape[0])[:, None]
    return u, jnp.zeros_like(s).at[rows, top_e].set(top_w * scale), margin


@functools.partial(jax.jit, static_argnames=("float8",))
def _expert(u, weight, up, down, i, e, *, float8):
    """down_e(relu(up_e u)^2) of layer i's held expert e on EVERY
    position, times its column of the routing weights. The stacks come in
    whole; only this expert's slices are cast."""
    w = _f32(up[i, e])              # [F, H] (out, in), or [H, F]
    w = w.T if w.shape[1] == u.shape[1] != w.shape[0] else w
    hidden = jnp.square(jax.nn.relu(_mm(u, w, float8)))
    return weight[:, None] * _mm(hidden, _f32(down[i, e]), float8)


@functools.partial(jax.jit, static_argnames=("float8",))
def _shared(u, up, down, *, float8):
    return _mm(jnp.square(jax.nn.relu(_mm(u, _f32(up), float8))), _f32(down),
               float8)


@functools.partial(jax.jit, static_argnames=("eps", "float8"))
def _head(x, final_scale, head, *, eps, float8):
    return _mm(_rms_norm(x, _f32(final_scale), eps), _f32(head), float8)


def hidden(params, tokens, config: dict, wrong: str | None = None,
           keep=None):
    """(final hidden states [S, H] (before the last norm) of ONE sequence
    of token ids, float32; the routing margin [S], see the module's
    docstring). ``keep`` [S] (``padding_in_state``): 0 at padding rows,
    which the attention layers' later queries do not see."""
    b = params["blocks"]
    eps = float(config["layer_norm_epsilon"])
    float8 = wrong == "float8"
    float8_experts = float8 or wrong == "float8_experts"
    x = _f32(params["embed"]["embedding"][jnp.asarray(tokens, jnp.int32)])
    s = x.shape[0]
    margin = jnp.full((s,), jnp.inf)
    seen = jnp.ones((s,)) if keep is None else _f32(keep)
    # padding_in_state is the fault itself: every row enters the state
    in_state = jnp.ones((s,))
    rank = {"M": 0, "*": 0, "E": 0}
    for kind in config["hybrid_override_pattern"]:
        i = rank[kind]
        rank[kind] += 1
        if kind == "M":
            m = b["ssm"]
            w = {"norm": m["norm"]["scale"][i],
                 "in_proj": m["in_proj"]["kernel"][i],
                 "conv_kernel": m["conv"]["kernel"][i],
                 "conv_bias": m["conv"]["bias"][i],
                 "dt_bias": m["dt_bias"][i], "A_log": m["A_log"][i],
                 "D": m["D"][i], "gate_norm": m["gate_norm"]["scale"][i],
                 "out_proj": m["out_proj"]["kernel"][i]}
            x = _mamba(x, w, in_state, nh=config["mamba_num_heads"],
                       p=config["mamba_head_dim"],
                       n=config["ssm_state_size"], g=config["n_groups"],
                       eps=eps, float8=float8,
                       norm_before_gate=wrong == "norm_before_gate")
        elif kind == "*":
            a = b["attn"]
            w = {"norm": a["norm"]["scale"][i], **{
                n: a[n]["kernel"][i] for n in ("q", "k", "v", "o")}}
            x = _attention(x, w, seen, n_q=config["num_attention_heads"],
                           n_kv=config["num_key_value_heads"], eps=eps,
                           float8=float8, theta=float(
                               config["rope_theta"]) if wrong == "rope"
                           else 0.0)
        elif kind == "E":
            moe = b["moe"]
            u, weights, m = _route(
                x, moe["norm"]["scale"][i], moe["router"]["kernel"][i],
                moe["router"]["bias"][i],
                top_k=config["num_experts_per_tok"], eps=eps,
                renormalise=bool(config.get("norm_topk_prob", True)),
                scale=float(config.get("routed_scaling_factor", 1.0)),
                float8=float8, softmax_scores=wrong == "softmax_scores",
                bias_as_weight=wrong == "bias_as_weight")
            margin = jnp.minimum(margin, m)
            first = int(config.get("first_expert", 0))
            for e in range(config["n_routed_experts"]):     # the held ones
                x = x + _expert(u, weights[:, first + e],
                                moe["up"]["kernel"], moe["down"]["kernel"],
                                i, e, float8=float8_experts)
            if "shared" in moe:
                x = x + _shared(u, moe["shared"]["up"]["kernel"][i],
                                moe["shared"]["down"]["kernel"][i],
                                float8=float8)
        else:
            raise ValueError(f"no layer kind {kind!r}")
    return x, margin


def logits(params, tokens, config: dict, positions=None,
           wrong: str | None = None, prompt_len: int = 0, pad_to: int = 0,
           with_margin: bool = False, round_to: int = 0):
    """Logits [len(positions) or S, V] of one sequence; with
    ``with_margin`` (logits, routing margin [len(positions) or S]: see the
    module's docstring). ``round_to``: zeros follow the sequence up to a
    multiple of it (one compiled shape for many lengths; no earlier
    position of a causal model sees them). With ``wrong``
    ``padding_in_state``, ``pad_to - prompt_len`` padding rows (token 0)
    follow the first ``prompt_len`` tokens, the state-space layers let
    them into their state, and ``positions`` still count the real
    tokens."""
    tokens = list(tokens)
    keep = None
    if wrong == "padding_in_state" and pad_to > prompt_len:
        pads = pad_to - prompt_len
        keep = [1] * prompt_len + [0] * pads + [1] * (
            len(tokens) - prompt_len)
        tokens = tokens[:prompt_len] + [0] * pads + tokens[prompt_len:]
        if positions is not None:
            positions = [p + pads if p >= prompt_len else p
                         for p in positions]
    if round_to and len(tokens) % round_to:
        more = round_to - len(tokens) % round_to
        if positions is None:
            positions = range(len(tokens))
        tokens = tokens + [0] * more
        keep = keep and keep + [1] * more
    x, margin = hidden(params, tokens, config, wrong, keep)
    if positions is not None:
        at = jnp.asarray(list(positions), jnp.int32)
        x, margin = x[at], margin[at]
    lg = _head(x, params["final_norm"]["scale"], params["lm_head"]["kernel"],
               eps=float(config["layer_norm_epsilon"]),
               float8=wrong == "float8")
    return (lg, margin) if with_margin else lg
