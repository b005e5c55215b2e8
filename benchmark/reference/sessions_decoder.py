"""The plain reference of a decoder that mixes by a gated delta-rule linear
attention (Kimi Delta Attention whose beta reaches 2) in three layers of
four and by GATED softmax attention without any position embedding in the
fourth, every layer's feed-forward sparse experts with a shared one, as
``model_type: solar_open2`` names them key for key (Solar-Open2-250B,
config.json; KDA: the Kimi Linear report, arXiv:2510.26692; negative
eigenvalues: arXiv:2411.12537; the sigmoid / bias router: DeepSeek-V3).

One sequence at a time; ``x`` is the normed stream [S, C]. Decoder layer l
(0-indexed in ``gqa_layers``) is a mixer then a feed-forward, each under its
own RMSNorm on the plain residual.

Softmax layer (``gqa_layers``), heads i of ``head_dim`` d, 8 query heads a
key/value head, DENSE over the whole sequence, NO rotation (``use_rope``
false), no q/k norm, no bias:

    q_i, k_j, v_j = x W_q, x W_k, x W_v
    a_i = softmax_causal(q_i k_{i // 8}^T d^-1/2) v_{i // 8}
    y = (a * sigmoid(x W_g)) W_o                       ``use_gqa_gate``

Delta-rule layer, heads h of ``linear_attn_config.head_dim`` d, TOKEN BY
TOKEN from a zero state:

    q~, k~, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
                depthwise, causal, width ``short_conv_kernel_size``, no bias
    q_h = q~_h / sqrt(|q~_h|^2 + 1e-6) * d^-1/2;   k_h likewise, unscaled
    g_h = -exp(A_log_h) * softplus((x W_fa W_fb)_h + dt_bias_h)     in R^d
    beta_h = 2 sigmoid(x W_b)_h                  ``kda_allow_neg_eigval``
    S' = Diag(exp(g_h)) S_{t-1};   S_t = S' + beta_h k_h (v_h - S'^T k_h)^T
    o_h = S_t^T q_h
    y = (RMSNorm_d(o_h; w) * sigmoid((x W_ga W_gb)_h)) W_o

Experts: ``s = sigmoid(x W_r)`` over ALL ``router_experts`` outputs, the
chosen set the top-k of ``s + b``, weights ``routed_scaling_factor * s_e /
(sum of the chosen s + 1e-20)`` (``norm_topk_prob``); an expert is ``W_down
(silu(W_gate u) * W_up u)``; plus the shared expert on every token. Only the
HELD experts (``first_expert ..< first_expert + n_routed_experts``: this
chip's share of the layer) are applied: what the absent ones would add is
left out, as in the program.

Departures from the published description, all of LAYOUT (the function is
the same): the program stores W_q | W_k | W_v | W_fa | W_ga | W_b of a
delta-rule layer side by side as one ``in_proj`` and the three convs as one
kernel over the same columns; a norm's weight is stored as ``scale`` with
the weight ``1 + scale``; A_log is one value a head and dt_bias one a
channel. The gate's form and the router's score are the configuration's
``assumed`` (the config.json has no key for either).

Float32 ``jax.numpy`` at ``highest`` matmul precision, no cache, no chunks,
no kernels, no batching, no snapshot: every position's state comes from
position 0. Blocked so that a 16k-token session fits beside a server's
weights (attention a key/value head and a block of queries at a time, as
many blocks as hold LIVE tokens, the delta rule a block of heads at a time
and as many steps as the sequence has live tokens, a feed-forward a block of
rows at a time, an expert over the positions that chose it), and with
``compiled`` each kind of sub-layer is
jitted once a padded length (``pad_to``: zeros follow the sequence; no
earlier position of a causal model sees them, and they choose no expert).
Independent of ``models/`` and ``ops/``; it reads only the program's
parameter tree (one stack a layer KIND, indexed by the rank among its kind):

    blocks.kda.{norm.scale [Lk,C], in_proj.kernel [Lk,C,3nd+2d+n],
                conv.kernel [Lk,K,3nd], f_b.kernel / g_b.kernel [Lk,d,nd],
                A_log [Lk,n], dt_bias [Lk,nd], gate_norm.scale [Lk,d],
                out_proj.kernel [Lk,nd,C]}
    blocks.attn.{norm.scale, q.kernel [La,C,N d], k.kernel / v.kernel
                 [La,C,Nkv d], gate.kernel [La,C,N d], o.kernel [La,N d,C]}
    blocks.moe.{norm.scale, router.kernel [Le,C,E_all], router.bias,
                gate / up .kernel [Le,E,C,Fe], down.kernel [Le,E,Fe,C],
                shared.{gate,up,down}.kernel}
    embed.embedding [V,C]; final_norm.scale [C]; lm_head.kernel [C,V]

``wrong`` computes a WRONG model on purpose, to show that a check against
this reference fails when it should: ``float8`` (every operand of every
matrix product rounded to float8_e4m3, the nearest precision under the
configuration's bfloat16), ``bf16_state`` (the delta-rule state rounded to
bfloat16 after every token), ``beta_unscaled`` (beta = sigmoid, without the
factor 2), ``no_gate`` (the softmax layer's output not gated), ``rope``
(rope on q and k of the softmax layer), ``no_renorm`` (the chosen scores
not renormalised), and two servers that follow a prefix hit at position
``hit`` WRONGLY: ``zero_at_hit`` (the state set to zero there: a slot armed
from nothing) and ``stale_at_hit`` (the state of one page earlier put
there: a slot armed from the snapshot of the page before). Several at once
are joined by ``+``.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmark.reference import latent_decoder
from benchmark.reference.latent_decoder import (
    EXPERT_ROWS, QUERY_BLOCK, VOCAB_BLOCK, _f32, _mlp, _r, _rms_norm, _rope,
    route)

L2_EPS = 1e-6
# heads of a delta-rule layer run at once: a block's q | k | v columns over
# a 16k-token session are 0.8 GB in float32
KDA_HEAD_BLOCK = 32

# the faults of ``wrong`` that change each of SUB_LAYERS (a sub-layer is
# compiled once for the faults that concern it, whatever the others are)
CONCERNS = (("bf16_state", "beta_unscaled", "zero_at_hit", "stale_at_hit"),
            ("no_gate", "rope"), ("no_renorm",))


def _has(wrong, fault: str) -> bool:
    return wrong is not None and fault in wrong.split("+")


def layer_kinds(config: dict) -> list:
    """The mixer, "*" or "K", of each decoder layer (every feed-forward is
    an expert layer: ``first_k_dense_replace`` 0)."""
    if int(config.get("first_k_dense_replace", 0)):
        raise ValueError("this reference has no dense feed-forward layer")
    softmax_at = set(config["gqa_layers"])
    return ["*" if i in softmax_at else "K"
            for i in range(int(config["num_hidden_layers"]))]


def _kda(h, w, config: dict, wrong, live, hit, page):
    """(The delta-rule mixer over h [S, C], the state [n, d, d] BEFORE token
    ``hit``), token by token from a zero state over the first ``live``
    tokens (the rest is padding: its rows are zeros). ``hit``, ``page``:
    where a served turn was armed from a snapshot: what a snapshot taken
    there must hold, and where ``zero_at_hit`` / ``stale_at_hit`` follow
    the hit wrongly."""
    la = config["linear_attn_config"]
    n, d, K = (int(la["num_heads"]), int(la["head_dim"]),
               int(la["short_conv_kernel_size"]))
    nd, s = n * d, h.shape[0]
    eps = float(config["rms_norm_eps"])
    hb = math.gcd(KDA_HEAD_BLOCK, n)
    lo_rank = _r(h) @ _r(w["in_proj"][:, 3 * nd:])       # [S, 2d + n]
    f_lo, g_lo, b = lo_rank[:, :d], lo_rank[:, d:2 * d], lo_rank[:, 2 * d:]
    beta_all = jax.nn.sigmoid(b)                         # [S, n]
    if config.get("kda_allow_neg_eigval") and not _has(wrong,
                                                       "beta_unscaled"):
        beta_all = 2.0 * beta_all

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)

    def heads(j):
        """o [S, hb, d], normed and gated, of heads j * hb ..< (j + 1) * hb."""
        def cols(mat, part):        # a block's columns of q | k | v
            return jax.lax.dynamic_slice_in_dim(
                mat, part * nd + j * hb * d, hb * d, axis=-1)

        def conv(part):
            x = _r(h) @ _r(cols(w["in_proj"], part))
            # y_t = sum_i w[i] x_{t - (K - 1) + i}: zeros before the sequence
            padded = jnp.pad(x, ((K - 1, 0), (0, 0)))
            kernel = _f32(cols(w["conv"], part))
            return jax.nn.silu(sum(padded[i:i + s] * kernel[i]
                                   for i in range(K))).reshape(s, hb, d)
        q, k, v = unit(conv(0)) * d ** -0.5, unit(conv(1)), conv(2)
        at = j * hb

        def mine(x, width=d):       # a block's heads of [.., n * width]
            return jax.lax.dynamic_slice_in_dim(x, at * width, hb * width,
                                                axis=-1)
        g = (-jnp.exp(_f32(mine(w["A_log"], 1)))[:, None] * jax.nn.softplus(
            _r(f_lo) @ _r(mine(w["f_b"])) + _f32(mine(w["dt_bias"]))
        ).reshape(s, hb, d))
        beta = mine(beta_all, 1)

        def token(t, carry):
            S, saved, kept, o = carry
            kept = jnp.where(t == hit, S, kept)
            if _has(wrong, "stale_at_hit"):
                saved = jnp.where(t == hit - page, S, saved)
                S = jnp.where(t == hit, saved, S)
            if _has(wrong, "zero_at_hit"):
                S = jnp.where(t == hit, 0.0, S)
            q_t, k_t, v_t, g_t, b_t = (a[t] for a in (q, k, v, g, beta))
            Sd = S * jnp.exp(g_t)[:, :, None]                    # [hb,dk,dv]
            u = b_t[:, None] * (v_t - jnp.sum(Sd * k_t[:, :, None], axis=1))
            S = Sd + k_t[:, :, None] * u[:, None, :]
            if _has(wrong, "bf16_state"):
                # (``reduce_precision`` and not a pair of conversions:
                # compiled, the chip's compiler keeps the excess precision)
                S = jax.lax.reduce_precision(S, exponent_bits=8,
                                             mantissa_bits=7)
            o_t = jnp.sum(S * q_t[:, :, None], axis=1)
            return (S, saved, kept,
                    jax.lax.dynamic_update_index_in_dim(o, o_t, t, 0))
        zero = jnp.zeros((hb, d, d), jnp.float32)
        _, _, kept, o = jax.lax.fori_loop(0, live, token, (
            zero, zero, zero, jnp.zeros((s, hb, d), jnp.float32)))
        return _rms_norm(o, w["gate_norm"], eps) * jax.nn.sigmoid(
            (_r(g_lo) @ _r(mine(w["g_b"]))).reshape(s, hb, d)), kept
    o, kept = jax.lax.map(heads, jnp.arange(n // hb))    # [n/hb, S, hb, d]
    return (_r(jnp.moveaxis(o, 0, 1).reshape(s, nd)) @ _r(w["out_proj"]),
            kept.reshape(n, d, d))


def _attention(h, w, config: dict, wrong, live=None):
    """The gated softmax mixer over h [S, C], dense and causal (blocks of
    queries from ``live`` on, the padding, are left at zero)."""
    s = h.shape[0]
    n, nkv, d = (int(config[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim"))
    q = (_r(h) @ _r(w["q"])).reshape(s, nkv, n // nkv, d)
    k = (_r(h) @ _r(w["k"])).reshape(s, nkv, d)
    v = (_r(h) @ _r(w["v"])).reshape(s, nkv, d)
    if config.get("use_rope", True) or _has(wrong, "rope"):
        inv_freq = 1.0 / float(config["rope_theta"]) ** (
            jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        q, k = _rope(q, inv_freq), _rope(k, inv_freq)
    pos = jnp.arange(s)
    q, k, v = _r(q), _r(k), _r(v)

    block = min(QUERY_BLOCK, s)
    blocks = -(-s // block) if live is None else -(-live // block)

    def kv_head(of):                    # its group of query heads
        q_g, k_g, v_g = of              # [S, n/nkv, d], [S, d], [S, d]

        def attend(j, out):             # a block of queries over all keys
            at = jnp.minimum(j * block, s - block)
            qb = jax.lax.dynamic_slice_in_dim(q_g, at, block, 0)
            sc = jnp.einsum("qnd,kd->nqk", qb, k_g) * d ** -0.5
            sc = jnp.where(pos[None, None, :] <= (at + pos[:block])[
                None, :, None], sc, -jnp.inf)
            return jax.lax.dynamic_update_slice_in_dim(out, jnp.einsum(
                "nqk,kd->qnd", jax.nn.softmax(sc, -1), v_g), at, 0)
        return jax.lax.fori_loop(0, blocks, attend, jnp.zeros_like(q_g))
    out = jax.lax.map(kv_head, tuple(jnp.moveaxis(a, 1, 0)
                                     for a in (q, k, v)))   # [nkv,S,n/nkv,d]
    out = jnp.moveaxis(out, 0, 1).reshape(s, n * d)
    if config.get("use_gqa_gate") and not _has(wrong, "no_gate"):
        out = out * jax.nn.sigmoid(_r(h) @ _r(w["gate"]))
    return _r(out) @ _r(w["o"])


def _experts(h, moe, i, live, config: dict, wrong):
    """(the expert layer's output [S, C] from the HELD experts and the
    shared one, routing margin [S]); rows from ``live`` on choose none."""
    s = h.shape[0]
    if _has(wrong, "no_renorm"):
        config = dict(config, norm_topk_prob=False)
    weights, margin = route(h, moe["router"]["kernel"][i],
                            moe["router"]["bias"][i], config, None)
    weights = jnp.where(jnp.arange(s)[:, None] < live, weights, 0.0)
    out = _mlp(h, moe["shared"]["gate"]["kernel"][i],
               moe["shared"]["up"]["kernel"][i],
               moe["shared"]["down"]["kernel"][i])
    rows_at_once = min(EXPERT_ROWS, s)
    first = int(config.get("first_expert", 0))

    def expert(out, e):
        # the positions that chose held expert e (router output first + e)
        w_e = jnp.take(weights, first + e, axis=1)
        at = jnp.concatenate([
            jnp.nonzero(w_e > 0, size=s, fill_value=s)[0],
            jnp.full((rows_at_once,), s)])

        def some_rows(j, out):
            idx = jax.lax.dynamic_slice(at, (j * rows_at_once,),
                                        (rows_at_once,))
            y = _mlp(h[jnp.minimum(idx, s - 1)], moe["gate"]["kernel"][i, e],
                     moe["up"]["kernel"][i, e], moe["down"]["kernel"][i, e])
            w = jnp.where(idx < s, w_e[jnp.minimum(idx, s - 1)], 0.0)
            return out.at[idx].add(y * w[:, None], mode="drop")
        blocks = (jnp.sum(w_e > 0) + rows_at_once - 1) // rows_at_once
        return jax.lax.fori_loop(0, blocks, some_rows, out), None
    out, _ = jax.lax.scan(expert, out,
                          jnp.arange(int(config["n_routed_experts"])))
    return out, margin


def _normed(x, stack, i, config):
    return _rms_norm(x, stack["norm"]["scale"][i],
                     float(config["rms_norm_eps"]))


def _kda_sub_layer(x, a, i, live, hit, page, config: dict, wrong):
    w = {k: a[k]["kernel"][i] for k in (
        "in_proj", "conv", "f_b", "g_b", "out_proj")}
    w.update(A_log=a["A_log"][i], dt_bias=a["dt_bias"][i],
             gate_norm=a["gate_norm"]["scale"][i])
    out, state = _kda(_normed(x, a, i, config), w, config, wrong, live, hit,
                      page)
    return x + out, state


def _attention_sub_layer(x, a, i, live, config: dict, wrong):
    w = {k: a[k]["kernel"][i] for k in ("q", "k", "v", "gate", "o")
         if k in a}
    return x + _attention(_normed(x, a, i, config), w, config, wrong, live)


def _experts_sub_layer(x, moe, i, live, config: dict, wrong):
    """(x, the layer's routing margin [S])."""
    out, margin = _experts(_normed(x, moe, i, config), moe, i, live, config,
                           wrong)
    return x + out, margin


SUB_LAYERS = (_kda_sub_layer, _attention_sub_layer, _experts_sub_layer)


@functools.lru_cache(maxsize=None)
def _compiled_sub_layer(kind: int, config_json: str, wrong):
    return jax.jit(functools.partial(SUB_LAYERS[kind],
                                     config=json.loads(config_json),
                                     wrong=wrong), donate_argnums=0)


def _compiled_sub_layers(config_json: str, wrong):
    """``SUB_LAYERS`` jitted for one configuration: a program a KIND of
    sub-layer, a padded length and the faults that concern the kind (the
    layer's index is an argument, the stacks go in whole)."""
    faults = wrong.split("+") if wrong else ()
    return tuple(_compiled_sub_layer(
        kind, config_json, "+".join(f for f in faults if f in mine) or None)
        for kind, mine in enumerate(CONCERNS))


def _model_keys(config: dict) -> dict:
    """The keys of ``config`` the sub-layers read (a compiled sub-layer is
    kept by them: the file's prose and serve table are not the model's)."""
    keys = ("linear_attn_config", "rms_norm_eps", "kda_allow_neg_eigval",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "use_rope", "rope_theta", "use_gqa_gate", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor", "first_expert",
            "n_routed_experts")
    return {k: config[k] for k in keys if k in config}


def hidden(params, tokens, config: dict, wrong: str | None = None,
           live: int | None = None, compiled: bool = False, hit: int = 0,
           page: int = 0):
    """(the stream [S, C] after the last layer, routing margin [S], the
    delta-rule layers' states [Lk, n, d, d] before token ``hit``)."""
    b = params["blocks"]
    x = _f32(params["embed"]["embedding"][jnp.asarray(tokens, jnp.int32)])
    s = x.shape[0]
    margin = jnp.full((s,), jnp.inf)
    kinds = layer_kinds(config)
    model = _model_keys(config)
    fs = (_compiled_sub_layers(json.dumps(model, sort_keys=True), wrong)
          if compiled else tuple(
              functools.partial(f, config=model, wrong=wrong)
              for f in SUB_LAYERS))
    kda, attention, experts = fs
    live, hit, page = (jnp.int32(a) for a in (
        s if live is None else live, hit, page))
    seen = {"K": 0, "*": 0}
    states = []
    for e, mixer in enumerate(kinds):
        rank = jnp.int32(seen[mixer])
        seen[mixer] += 1
        if mixer == "K":
            x, state = kda(x, b["kda"], rank, live, hit, page)
            states.append(state)
        else:
            x = attention(x, b["attn"], rank, live)
        x, layer_margin = experts(x, b["moe"], jnp.int32(e), live)
        margin = jnp.minimum(margin, layer_margin)
    return x, margin, jnp.stack(states)


def logits(params, tokens, config: dict, positions=None,
           wrong: str | None = None, with_margin: bool = False,
           pad_to: int = 0, compiled: bool = False, hit: int = 0,
           page: int = 0, with_state: bool = False):
    """Logits [len(positions) or S, V] of one sequence; with
    ``with_margin`` (logits, routing margin [len(positions) or S]); with
    ``with_state`` also, last, the delta-rule layers' states [Lk, n, d, d]
    BEFORE token ``hit``: what a snapshot taken there must hold.
    ``pad_to``: zeros follow the sequence up to that length (one compiled
    shape for many lengths). ``compiled``: each kind of sub-layer runs as
    one jitted program. ``hit``, ``page``: the position a served turn was
    armed at and the page's tokens, for ``zero_at_hit`` / ``stale_at_hit``."""
    tokens = list(tokens)
    live = len(tokens)
    if pad_to > live:
        if positions is None:
            positions = range(live)
        tokens = tokens + [0] * (pad_to - live)
    latent_decoder._FLOAT8[0] = _has(wrong, "float8")
    # (a compiler may keep more precision than a fused pair of conversions
    # asks for: the roundings to float8 run operation by operation)
    compiled = compiled and not _has(wrong, "float8")
    try:
        with jax.default_matmul_precision("highest"):
            x, margin, states = hidden(params, tokens, config, wrong, live,
                                       compiled, hit, page)
            if positions is not None:
                at = jnp.asarray(list(positions), jnp.int32)
                x, margin = x[at], margin[at]
            x = _rms_norm(x, params["final_norm"]["scale"],
                          float(config["rms_norm_eps"]))
            head = params["lm_head"]["kernel"]
            lg = jnp.concatenate([                 # a block of the vocabulary
                _r(x) @ _r(head[:, lo:lo + VOCAB_BLOCK])
                for lo in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1)
    finally:
        latent_decoder._FLOAT8[0] = False
    out = (lg, margin) if with_margin else (lg,)
    out = (*out, states) if with_state else out
    return out if len(out) > 1 else out[0]
