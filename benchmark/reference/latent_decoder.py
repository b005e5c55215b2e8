"""The plain reference of a decoder with latent attention (MLA), a
multi-stream manifold-constrained residual path (mHC) and sparse experts
behind a leading dense layer, as ``model_type: xing4_0`` names them key for
key (Xing4.0-29B-A4B, config.json; MLA and the sigmoid / bias / scaling
router: DeepSeek-V2 / -V3; mHC: arXiv:2512.24880 on hyper-connections,
arXiv:2409.19606; YaRN: arXiv:2309.00071).

One sequence at a time. With C the hidden size and n = ``hc_mult``, a
token's residual state is X in R^{n x C}: n copies of its embedding at the
start, the sum of the streams before the final norm. Decoder layer l is an
attention sub-layer then a feed-forward one (a gated SiLU MLP in the first
``first_k_dense_replace`` layers, experts after), each sub-layer F wrapped
in a hyper-connection:

    x_hat   = RMSNorm(vec(X); w_hc, hc_eps)              over all n*C values
    [p|q|R] = x_hat phi                                   phi [n*C, 2n + n*n]
    H_pre   = sigmoid(a_pre p + b_pre)                    [n]
    H_post  = 2 sigmoid(a_post q + b_post)                [n]
    M       = exp(clip(a_res mat(R) + b_res, lo, hi))     [n, n]
    H_res   = hc_sinkhorn_iters x { M <- M / (rowsum + hc_eps);
                                    M <- M / (colsum + hc_eps) }
    u       = H_pre @ X                                   [C]
    X      <- H_res @ X + outer(H_post, F(RMSNorm(u; w, eps)))

Latent attention on h = RMSNorm(u), heads i:

    c_q           = RMSNorm(h W_qa);   [q_nope|q_pe]_i = c_q W_qb
    [c_kv|k_pe]   = h W_kva;  c_kv = RMSNorm(c_kv);  k_pe one for all heads
    [k_nope|v]_i  = c_kv W_kvb
    s_ij = (q_nope_i . k_nope_ij + rope(q_pe_i) . rope(k_pe_j)) * scale
    out  = concat_i(softmax_j(s_i) v_i) W_o

``scale`` = (nope + rope)^-0.5 * m^2, m = 0.1 mscale_all_dim ln(factor) + 1;
rope over the ``pe`` values alone, HALVES paired (value i with i + rope/2),
YaRN frequencies: f_i below the correction dim of ``beta_fast`` rotations
over ``original_max_position_embeddings``, f_i / factor above that of
``beta_slow``, the linear blend between; cos / sin factor mscale /
mscale_all_dim = 1. EXPANDED attention only: keys and values of every head
are computed from the latent, nothing is absorbed, nothing is cached.

Experts: ``s = sigmoid(u W_r)``, the chosen set the top-k of ``s + b``,
weights ``routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)``,
``expert(u) = W_down (silu(W_gate u) * W_up u)``, plus the shared expert.

Float32 ``jax.numpy`` at ``highest`` matmul precision, no cache, no
kernels, no batching. Three things are blocked so that a 16k context fits
beside a server's weights: attention runs a block of heads and a block of
queries at a time over all keys (masked), a feed-forward a block of rows at
a time, and an expert is applied to the positions that chose it, gathered
``EXPERT_ROWS`` at a time. The blocks are ``lax`` loops, so that a
sub-layer is one program whatever the sequence's length; with ``compiled``
each kind of sub-layer is jitted once (the layer's index an argument), and
with ``round_to`` every sequence of a run has one shape: the cell's check
then costs a few seconds a request and compiles three programs, where the
operation-by-operation form cost ~19 s a request and compiled every
operation anew for every length (PERF.md 6, PR 33). Independent of
``models/``; it reads only the program's
parameter tree (one stack a layer KIND, indexed by the rank among its kind):

    blocks.attn.{norm.scale [La,C], q_a.kernel [La,C,rq], q_a_norm.scale,
                 q_b.kernel [La,rq,N*(dn+dr)], kv_a.kernel [La,C,r+dr],
                 kv_norm.scale [La,r], kv_b.kernel [La,r,N*(dn+dv)] (a
                 head's k_nope then its v), o.kernel [La,N*dv,C], hc}
    blocks.mlp.{norm.scale, gate / up .kernel [Ld,C,F], down.kernel, hc}
    blocks.moe.{norm.scale, router.kernel [Le,C,E], router.bias [Le,E],
                gate / up .kernel [Le,E,C,Fe], down.kernel [Le,E,Fe,C],
                shared.{gate,up,down}.kernel, hc}
    hc = {norm.scale [.,n*C], phi.kernel [.,n*C,2n+n*n], a [.,3] (pre,
          post, res), b_pre [.,n], b_post [.,n], b_res [.,n,n]}
    embed.embedding [V,C]; final_norm.scale [C]; lm_head.kernel [C,V]

A norm's weight is stored as ``scale`` with the weight being ``1 + scale``
(the program's own convention).

``wrong`` computes a WRONG model on purpose, to show that a check against
this reference fails when it should: ``float8`` (every operand of every
matrix product rounded to float8_e4m3, the nearest precision under the
configuration's bfloat16), ``float8_latent`` (the latent row
[c_kv | rope(k_pe)] rounded to float8_e4m3, as quantised pages would hold
it), ``ckv_unnormed``, ``rope_wrong_dims`` (the rotation applied to the
first ``rope`` values of the nope part instead), ``scale_without_mscale``,
``yarn_interpolation`` (every frequency divided by the factor),
``no_sinkhorn`` (H_res a row softmax alone), ``one_stream`` (stream 0 alone
read and written), ``softmax_scores`` (router).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK, HEAD_BLOCK, ROW_BLOCK, VOCAB_BLOCK = 256, 8, 2048, 16384
EXPERT_ROWS = 1024


def _f32(x):
    return jnp.asarray(x, jnp.float32)


# ``wrong="float8"``: every operand of every matrix product (weights,
# activations, queries, keys, values) rounded to float8_e4m3, the nearest
# precision under the configuration's bfloat16; set by ``logits``
_FLOAT8 = [False]


def _r(x):
    x = _f32(x)
    return _f32(x.astype(jnp.float8_e4m3fn)) if _FLOAT8[0] else x


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + _f32(scale))


def yarn_inv_freq(config: dict, wrong: str | None = None):
    """[rope/2] inverse frequencies under the file's ``rope_scaling``."""
    d = int(config["qk_rope_head_dim"])
    base = float(config["rope_theta"])
    rs = config["rope_scaling"]
    factor = float(rs["factor"])
    freq = 1.0 / base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if wrong == "yarn_interpolation":
        return freq / factor

    def dim_of(rotations):
        return (d * math.log(rs["original_max_position_embeddings"]
                             / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freq / factor * ramp + freq * (1.0 - ramp)


def softmax_scale(config: dict, wrong: str | None = None) -> float:
    rs = config["rope_scaling"]
    m = 0.1 * float(rs["mscale_all_dim"]) * math.log(float(rs["factor"])) + 1
    if wrong == "scale_without_mscale":
        m = 1.0
    return (int(config["qk_nope_head_dim"])
            + int(config["qk_rope_head_dim"])) ** -0.5 * m * m


def _rope(x, inv_freq):
    """x [S, ..., d] rotated by its position (axis 0), halves paired."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq      # [S,d/2]
    ang = ang.reshape(s, *([1] * (x.ndim - 2)), -1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hyper_connection_maps(X, hc, i, config: dict, wrong: str | None = None):
    """(H_pre [S,n], H_post [S,n], H_res [S,n,n]) of sub-layer ``i`` of a
    stack from the streams X [S, n, C]."""
    s, n, c = X.shape
    eps = float(config["hc_eps"])
    x_hat = _rms_norm(X.reshape(s, n * c), hc["norm"]["scale"][i], eps)
    pqr = _r(x_hat) @ _r(hc["phi"]["kernel"][i])
    a = _f32(hc["a"][i])
    h_pre = jax.nn.sigmoid(a[0] * pqr[:, :n] + _f32(hc["b_pre"][i]))
    h_post = 2.0 * jax.nn.sigmoid(a[1] * pqr[:, n:2 * n]
                                  + _f32(hc["b_post"][i]))
    logits = a[2] * pqr[:, 2 * n:].reshape(s, n, n) + _f32(hc["b_res"][i])
    m = jnp.exp(jnp.clip(logits, float(config["mhc_h_res_clamp_min"]),
                         float(config["mhc_h_res_clamp_max"])))
    if wrong == "no_sinkhorn":
        return h_pre, h_post, m / jnp.sum(m, axis=-1, keepdims=True)
    def rows_then_columns(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return h_pre, h_post, jax.lax.fori_loop(
        0, int(config["hc_sinkhorn_iters"]), rows_then_columns, m)


def _latent_attention(h, w, config: dict, wrong):
    s = h.shape[0]
    n = int(config["num_attention_heads"])
    dn, dr, dv, r = (int(config[k]) for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank"))
    eps = float(config["rms_norm_eps"])
    inv_freq = yarn_inv_freq(config, wrong)
    c_q = _rms_norm(_r(h) @ _r(w["q_a"]), w["q_a_norm"], eps)
    q = (_r(c_q) @ _r(w["q_b"])).reshape(s, n, dn + dr)
    ckv = _r(h) @ _r(w["kv_a"])
    c_kv, k_pe = ckv[:, :r], ckv[:, r:]
    if wrong != "ckv_unnormed":
        c_kv = _rms_norm(c_kv, w["kv_norm"], eps)
    if wrong == "rope_wrong_dims":
        q = jnp.concatenate([_rope(q[..., :dr], inv_freq), q[..., dr:]], -1)
    else:
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv_freq)], -1)
        k_pe = _rope(k_pe, inv_freq)
    if wrong == "float8_latent":
        c_kv = _f32(c_kv.astype(jnp.float8_e4m3fn))
        k_pe = _f32(k_pe.astype(jnp.float8_e4m3fn))
    scale = softmax_scale(config, wrong)
    pos = jnp.arange(s)
    g = math.gcd(HEAD_BLOCK, n)
    w_kvb = _r(w["kv_b"]).reshape(r, n // g, g, dn + dv)
    c_kv, q = _r(c_kv), _r(q).reshape(s, n // g, g, dn + dr)

    def head_block(of):                            # a block of heads
        w_b, q_b = of                              # [r, g, dn+dv], [S, g, .]
        kv = jnp.einsum("sr,rnd->snd", c_kv, w_b)
        k_nope, v = kv[..., :dn], _r(kv[..., dn:])
        if wrong == "rope_wrong_dims":
            k_nope = jnp.concatenate(
                [_rope(k_nope[..., :dr], inv_freq), k_nope[..., dr:]], -1)
        k = _r(jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, None], (s, g, dr))], -1))

        def attend(qb, at):                        # a block of queries over
            sc = jnp.einsum("qnd,knd->nqk", qb, k) * scale    # all keys
            sc = jnp.where(pos[None, None, :] <= at[None, :, None], sc,
                           -jnp.inf)
            return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(sc, -1), v)
        return _by_rows(attend, QUERY_BLOCK, q_b, pos)
    out = jax.lax.map(head_block, (jnp.moveaxis(w_kvb, 1, 0),
                                   jnp.moveaxis(q, 1, 0)))   # [n/g, S, g, dv]
    return _r(jnp.moveaxis(out, 0, 1).reshape(s, n * dv)) @ _r(w["o"])


def _by_rows(f, block: int, *xs):
    """``f`` over blocks of ``block`` rows of ``xs`` (zeros follow the last
    block's rows and their outputs are cut off), one after another."""
    s = xs[0].shape[0]
    block = min(block, s)
    pad = -s % block
    out = jax.lax.map(lambda b: f(*b), tuple(
        jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
            -1, block, *x.shape[1:]) for x in xs))
    return out.reshape(-1, *out.shape[2:])[:s]


def _mlp(h, gate, up, down):
    # a routed expert's gate / up kernels lie [F, C] (out, in) where F is no
    # multiple of 128 (the program's toy sizes), else [C, F]: read off
    gate, up = (_r(w) if w.shape[0] == h.shape[-1] else _r(w).T
                for w in (gate, up))
    down = _r(down)
    return _by_rows(lambda rows: _r(jax.nn.silu(rows @ gate) * (rows @ up))
                    @ down, ROW_BLOCK, _r(h))


def route(h, router, bias, config: dict, wrong):
    """(weights [S, E] (zero off the chosen set), margin [S]: the distance
    between the k-th and the k+1-th largest biased score)."""
    k = int(config["num_experts_per_tok"])
    lg = _r(h) @ _r(router)
    if wrong == "softmax_scores":
        scores = jax.nn.softmax(lg, axis=-1)
    else:
        scores = jax.nn.sigmoid(lg)
    biased = scores + _f32(bias)
    top, chosen = jax.lax.top_k(biased, k + 1)
    margin = top[:, k - 1] - top[:, k]
    chosen = chosen[:, :k]
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if config.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * float(config.get("routed_scaling_factor", 1.0))
    full = jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(w)
    return full, margin


def _experts(h, moe, i, config: dict, wrong, live=None):
    """(the expert layer's output [S, C], routing margin [S]); rows from
    ``live`` on (``logits``' padding) choose no expert."""
    s = h.shape[0]
    weights, margin = route(h, moe["router"]["kernel"][i],
                            moe["router"]["bias"][i], config, wrong)
    if live is not None:
        weights = jnp.where(jnp.arange(s)[:, None] < live, weights, 0.0)
    out = _mlp(h, moe["shared"]["gate"]["kernel"][i],
               moe["shared"]["up"]["kernel"][i],
               moe["shared"]["down"]["kernel"][i])
    rows_at_once = min(EXPERT_ROWS, s)

    def expert(out, e):
        # the positions that chose expert e, then ``s`` (dropped) to the end
        w_e = weights[:, e]
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


def _sub_layer(X, stack, i, F, config: dict, wrong):
    """One hyper-connected sub-layer: X [S, n, C] -> X."""
    eps = float(config["rms_norm_eps"])
    if wrong == "one_stream":
        return X + F(_rms_norm(X[:, 0], stack["norm"]["scale"][i], eps))[
            :, None]
    h_pre, h_post, h_res = hyper_connection_maps(
        X, stack["hc"], i, config, wrong)
    u = jnp.einsum("sn,snc->sc", h_pre, X)
    out = F(_rms_norm(u, stack["norm"]["scale"][i], eps))
    return (jnp.einsum("sij,sjc->sic", h_res, X)
            + h_post[:, :, None] * out[:, None, :])


def _attention_sub_layer(X, a, i, config: dict, wrong):
    w = {k: a[k]["kernel"][i] for k in ("q_a", "q_b", "kv_a", "kv_b", "o")}
    w["q_a_norm"] = a["q_a_norm"]["scale"][i]
    w["kv_norm"] = a["kv_norm"]["scale"][i]
    return _sub_layer(X, a, i, lambda h: _latent_attention(
        h, w, config, wrong), config, wrong)


def _dense_sub_layer(X, m, i, config: dict, wrong):
    return _sub_layer(X, m, i, lambda h: _mlp(
        h, m["gate"]["kernel"][i], m["up"]["kernel"][i],
        m["down"]["kernel"][i]), config, wrong)


def _experts_sub_layer(X, moe, i, live, config: dict, wrong):
    """(X, the layer's routing margin [S])."""
    seen = {}

    def experts(h):
        out, seen["margin"] = _experts(h, moe, i, config, wrong, live)
        return out
    return _sub_layer(X, moe, i, experts, config, wrong), seen["margin"]


SUB_LAYERS = (_attention_sub_layer, _dense_sub_layer, _experts_sub_layer)


@functools.lru_cache(maxsize=None)
def _compiled_sub_layers(config_json: str, wrong):
    """``SUB_LAYERS`` jitted for one configuration: a program a KIND of
    sub-layer (the layer's index is an argument, the stacks go in whole)."""
    config = json.loads(config_json)
    return tuple(jax.jit(functools.partial(f, config=config, wrong=wrong),
                         donate_argnums=0) for f in SUB_LAYERS)


def hidden(params, tokens, config: dict, wrong: str | None = None,
           live: int | None = None, compiled: bool = False):
    """(summed streams [S, C] after the last layer, routing margin [S])."""
    b = params["blocks"]
    n = int(config["hc_mult"])
    emb = _f32(params["embed"]["embedding"][jnp.asarray(tokens, jnp.int32)])
    s = emb.shape[0]
    X = jnp.broadcast_to(emb[:, None], (s, n, emb.shape[-1]))
    if wrong == "one_stream":
        X = X[:, :1]                  # the plain residual
    margin = jnp.full((s,), jnp.inf)
    if compiled:
        attention, dense_ffn, experts = _compiled_sub_layers(
            json.dumps(config, sort_keys=True), wrong)
    else:
        attention, dense_ffn, experts = (
            functools.partial(f, config=config, wrong=wrong)
            for f in SUB_LAYERS)
    live = jnp.int32(s if live is None else live)
    dense = int(config["first_k_dense_replace"])
    for layer in range(int(config["num_hidden_layers"])):
        X = attention(X, b["attn"], jnp.int32(layer))
        if layer < dense:
            X = dense_ffn(X, b["mlp"], jnp.int32(layer))
        else:
            X, layer_margin = experts(X, b["moe"], jnp.int32(layer - dense),
                                      live)
            margin = jnp.minimum(margin, layer_margin)
    return jnp.sum(X, axis=1), margin


def logits(params, tokens, config: dict, positions=None,
           wrong: str | None = None, with_margin: bool = False,
           round_to: int = 0, compiled: bool = False):
    """Logits [len(positions) or S, V] of one sequence; with
    ``with_margin`` (logits, routing margin [len(positions) or S]).
    ``round_to``: zeros follow the sequence up to a multiple of it (one
    compiled shape for many lengths; no earlier position of a causal model
    sees them, and they choose no expert). ``compiled``: each kind of
    sub-layer runs as one jitted program."""
    tokens = list(tokens)
    live = len(tokens)
    if round_to and live % round_to:
        if positions is None:
            positions = range(live)
        tokens = tokens + [0] * (round_to - live % round_to)
    _FLOAT8[0] = wrong == "float8"
    # (a compiler may keep more precision than a fused pair of conversions
    # asks for: the roundings to float8 run operation by operation)
    compiled = compiled and wrong not in ("float8", "float8_latent")
    try:
        with jax.default_matmul_precision("highest"):
            x, margin = hidden(params, tokens, config, wrong, live, compiled)
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
        _FLOAT8[0] = False
    return (lg, margin) if with_margin else lg
