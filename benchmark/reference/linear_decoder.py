"""The plain reference of a decoder that mixes by Kimi Delta Attention (KDA,
a gated delta-rule linear attention) in most layers and by latent attention
(MLA) without a query bottleneck and without rope in the others, with
sparse experts behind a leading dense layer, as ``model_type: kimi_linear``
names them key for key (Kimi-Linear-48B-A3B-Instruct, config.json; the Kimi
Linear report, arXiv:2510.26692; MLA and the sigmoid / bias / scaling
router: DeepSeek-V2 / -V3).

One sequence at a time; ``x`` is the normed stream [S, C]. Decoder layer l
(1-indexed in ``linear_attn_config.kda_layers`` / ``full_attn_layers``) is a
mixer then a feed-forward, each under its own RMSNorm on the plain residual.

KDA, heads h of ``head_dim`` d (keys and values alike), TOKEN BY TOKEN:

    q~, k~, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
                depthwise, causal, width ``short_conv_kernel_size``, no bias
    q_h = q~_h / sqrt(|q~_h|^2 + 1e-6) * d^-1/2;   k_h likewise, unscaled
    g_h = -exp(A_log_h) * softplus((x W_fa W_fb)_h + dt_bias_h)     in R^d
    beta_h = sigmoid(x W_b)_h
    S' = Diag(exp(g_h)) S_{t-1};   S_t = S' + beta_h k_h (v_h - S'^T k_h)^T
    o_h = S_t^T q_h
    y = (RMSNorm_d(o_h; w) * sigmoid((x W_ga W_gb)_h)) W_o

Latent attention, heads i, EXPANDED (keys and values of every head computed
from the latent; nothing absorbed, nothing cached):

    [q_nope|q_pe]_i = x W_q                        direct: no W_qa, no norm
    [c_kv|k_pe] = x W_kva;  c_kv = RMSNorm(c_kv);  k_pe one for all heads
    [k_nope|v]_i = c_kv W_kvb
    s_ij = (q_nope_i . k_nope_ij + q_pe_i . k_pe_j) * (nope + rope)^-1/2
                                                   NO rotation (mla_use_nope)

Experts: ``s = sigmoid(x W_r)`` over ALL ``router_experts`` outputs, the
chosen set the top-k of ``s + b``, weights ``routed_scaling_factor * s_e /
(sum of the chosen s + 1e-20)`` (``moe_renormalize``); an expert is
``W_down (silu(W_gate u) * W_up u)``; plus the shared expert on every token.
Only the HELD experts (``first_expert ..< first_expert + num_experts``: this
chip's share of the layer) are applied: what the absent ones would add is
left out, as in the program.

Departures from the published description, all of LAYOUT (the function is
the same): the program stores W_q | W_k | W_v | W_fa | W_ga | W_b side by
side as one ``in_proj`` and the three convs as one kernel over the same
columns; a norm's weight is stored as ``scale`` with the weight ``1 +
scale``; A_log is one value a head and dt_bias one a channel, as
``modeling_kimi.py`` has them.

Float32 ``jax.numpy`` at ``highest`` matmul precision, no cache, no chunks,
no kernels, no batching. Blocked so that a 13k-token context fits beside a
server's weights (``latent_decoder``'s helpers: attention a block of heads
and of queries at a time, a feed-forward a block of rows at a time, an
expert over the positions that chose it), and with ``compiled`` each kind of
sub-layer is jitted once a padded length (``pad_to``: zeros follow the
sequence; no earlier position of a causal model sees them, and they choose
no expert). Independent of ``models/`` and ``ops/``; it reads only the
program's parameter tree (one stack a layer KIND, indexed by the rank among
its kind):

    blocks.kda.{norm.scale [Lk,C], in_proj.kernel [Lk,C,3nd+2d+n],
                conv.kernel [Lk,K,3nd], f_b.kernel / g_b.kernel [Lk,d,nd],
                A_log [Lk,n], dt_bias [Lk,nd], gate_norm.scale [Lk,d],
                out_proj.kernel [Lk,nd,C]}
    blocks.attn.{norm.scale, q.kernel [La,C,N*(dn+dr)], kv_a.kernel
                 [La,C,r+dr], kv_norm.scale [La,r], kv_b.kernel
                 [La,r,N*(dn+dv)] (a head's k_nope then its v), o.kernel}
    blocks.mlp.{norm.scale, gate / up .kernel [Ld,C,F], down.kernel}
    blocks.moe.{norm.scale, router.kernel [Le,C,E_all], router.bias,
                gate / up .kernel [Le,E,C,Fe], down.kernel [Le,E,Fe,C],
                shared.{gate,up,down}.kernel}
    embed.embedding [V,C]; final_norm.scale [C]; lm_head.kernel [C,V]

``wrong`` computes a WRONG model on purpose, to show that a check against
this reference fails when it should: ``float8`` (every operand of every
matrix product rounded to float8_e4m3, the nearest precision under the
configuration's bfloat16), ``bf16_state`` (the KDA state rounded to
bfloat16 after every token), ``rotated_pe`` (rope on the ``pe`` values),
``no_beta`` (beta = 1), ``per_head_decay`` (a head's channels all decay by
their mean), ``no_renorm`` (the chosen scores not renormalised). Several
at once are joined by ``+`` (``"bf16_state+rotated_pe"``).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmark.reference import latent_decoder
from benchmark.reference.latent_decoder import (
    HEAD_BLOCK, QUERY_BLOCK, VOCAB_BLOCK, EXPERT_ROWS, _by_rows, _f32, _mlp,
    _r, _rms_norm, _rope)

L2_EPS = 1e-6

# the faults of ``wrong`` that change each of SUB_LAYERS (a sub-layer is
# compiled once for the faults that concern it, whatever the others are)
CONCERNS = (("no_beta", "per_head_decay", "bf16_state"), ("rotated_pe",),
            (), ("no_renorm",))


def _has(wrong, fault: str) -> bool:
    return wrong is not None and fault in wrong.split("+")


def layer_kinds(config: dict) -> list:
    """[(mixer "K" | "*", feed-forward "D" | "E")] a decoder layer."""
    n = int(config["num_hidden_layers"])
    kda_at = set(config["linear_attn_config"]["kda_layers"])
    dense = int(config.get("first_k_dense_replace", 0))
    return [("K" if i + 1 in kda_at else "*", "D" if i < dense else "E")
            for i in range(n)]


def _kda(h, w, config: dict, wrong):
    """The KDA mixer over h [S, C], token by token from a zero state."""
    la = config["linear_attn_config"]
    n, d, K = (int(la["num_heads"]), int(la["head_dim"]),
               int(la["short_conv_kernel_size"]))
    nd, s = n * d, h.shape[0]
    eps = float(config["rms_norm_eps"])
    proj = _r(h) @ _r(w["in_proj"])
    qkv, f_lo, g_lo, b = (proj[:, :3 * nd], proj[:, 3 * nd:3 * nd + d],
                          proj[:, 3 * nd + d:3 * nd + 2 * d],
                          proj[:, 3 * nd + 2 * d:])
    # y_t = sum_j w[j] x_{t - (K - 1) + j}: zeros before the sequence
    padded = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
    conv = sum(padded[j:j + s] * _f32(w["conv"][j]) for j in range(K))
    act = jax.nn.silu(conv)
    q, k, v = (act[:, i * nd:(i + 1) * nd].reshape(s, n, d) for i in range(3))

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)
    q, k = unit(q) * d ** -0.5, unit(k)
    g = (-jnp.exp(_f32(w["A_log"]))[:, None] * jax.nn.softplus(
        _r(f_lo) @ _r(w["f_b"]) + _f32(w["dt_bias"])).reshape(s, n, d))
    beta = jax.nn.sigmoid(b)                                     # [S, n]
    if _has(wrong, "no_beta"):
        beta = jnp.ones_like(beta)
    if _has(wrong, "per_head_decay"):
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        Sd = S * jnp.exp(g_t)[:, :, None]                        # [n,dk,dv]
        u = b_t[:, None] * (v_t - jnp.sum(Sd * k_t[:, :, None], axis=1))
        S = Sd + k_t[:, :, None] * u[:, None, :]
        if _has(wrong, "bf16_state"):
            # (``reduce_precision`` and not a pair of conversions: compiled,
            # the chip's compiler keeps the excess precision of f32 -> bf16
            # -> f32 and the departure reads exactly as the right model)
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.sum(S * q_t[:, :, None], axis=1)
    _, o = jax.lax.scan(token, jnp.zeros((n, d, d), jnp.float32),
                        (q, k, v, g, beta))                      # [S,n,d]
    o = _rms_norm(o, w["gate_norm"], eps) * jax.nn.sigmoid(
        (_r(g_lo) @ _r(w["g_b"])).reshape(s, n, d))
    return _r(o.reshape(s, nd)) @ _r(w["out_proj"])


def _latent_attention(h, w, config: dict, wrong):
    s = h.shape[0]
    n = int(config["num_attention_heads"])
    dn, dr, dv, r = (int(config[k]) for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank"))
    eps = float(config["rms_norm_eps"])
    q = (_r(h) @ _r(w["q"])).reshape(s, n, dn + dr)
    ckv = _r(h) @ _r(w["kv_a"])
    c_kv, k_pe = _rms_norm(ckv[:, :r], w["kv_norm"], eps), ckv[:, r:]
    if _has(wrong, "rotated_pe"):
        inv_freq = 1.0 / float(config["rope_theta"]) ** (
            jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv_freq)], -1)
        k_pe = _rope(k_pe, inv_freq)
    scale = (dn + dr) ** -0.5
    pos = jnp.arange(s)
    g = math.gcd(HEAD_BLOCK, n)
    w_kvb = _r(w["kv_b"]).reshape(r, n // g, g, dn + dv)
    c_kv, q = _r(c_kv), _r(q).reshape(s, n // g, g, dn + dr)

    def head_block(of):                            # a block of heads
        w_b, q_b = of
        kv = jnp.einsum("sr,rnd->snd", c_kv, w_b)
        v = _r(kv[..., dn:])
        k = _r(jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe[:, None], (s, g, dr))], -1))

        def attend(qb, at):                        # a block of queries over
            sc = jnp.einsum("qnd,knd->nqk", qb, k) * scale    # all keys
            sc = jnp.where(pos[None, None, :] <= at[None, :, None], sc,
                           -jnp.inf)
            return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(sc, -1), v)
        return _by_rows(attend, QUERY_BLOCK, q_b, pos)
    out = jax.lax.map(head_block, (jnp.moveaxis(w_kvb, 1, 0),
                                   jnp.moveaxis(q, 1, 0)))   # [n/g, S, g, dv]
    return _r(jnp.moveaxis(out, 0, 1).reshape(s, n * dv)) @ _r(w["o"])


def route(h, router, bias, config: dict, wrong):
    """(weights [S, E_all] (zero off the chosen set), margin [S]: the
    distance between the k-th and the k+1-th largest biased score)."""
    k = int(config["num_experts_per_token"])
    scores = jax.nn.sigmoid(_r(h) @ _r(router))
    top, chosen = jax.lax.top_k(scores + _f32(bias), k + 1)
    margin = top[:, k - 1] - top[:, k]
    chosen = chosen[:, :k]
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if config.get("moe_renormalize", True) and not _has(wrong, "no_renorm"):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * float(config.get("routed_scaling_factor", 1.0))
    full = jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(w)
    return full, margin


def _experts(h, moe, i, live, config: dict, wrong):
    """(the expert layer's output [S, C] from the HELD experts and the
    shared one, routing margin [S]); rows from ``live`` on choose none."""
    s = h.shape[0]
    weights, margin = route(h, moe["router"]["kernel"][i],
                            moe["router"]["bias"][i], config, wrong)
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
                          jnp.arange(int(config["num_experts"])))
    return out, margin


def _normed(x, stack, i, config):
    return _rms_norm(x, stack["norm"]["scale"][i],
                     float(config["rms_norm_eps"]))


def _kda_sub_layer(x, a, i, config: dict, wrong):
    w = {k: a[k]["kernel"][i] for k in (
        "in_proj", "conv", "f_b", "g_b", "out_proj")}
    w.update(A_log=a["A_log"][i], dt_bias=a["dt_bias"][i],
             gate_norm=a["gate_norm"]["scale"][i])
    return x + _kda(_normed(x, a, i, config), w, config, wrong)


def _attention_sub_layer(x, a, i, config: dict, wrong):
    w = {k: a[k]["kernel"][i] for k in ("q", "kv_a", "kv_b", "o")}
    w["kv_norm"] = a["kv_norm"]["scale"][i]
    return x + _latent_attention(_normed(x, a, i, config), w, config, wrong)


def _dense_sub_layer(x, m, i, config: dict, wrong):
    return x + _mlp(_normed(x, m, i, config), m["gate"]["kernel"][i],
                    m["up"]["kernel"][i], m["down"]["kernel"][i])


def _experts_sub_layer(x, moe, i, live, config: dict, wrong):
    """(x, the layer's routing margin [S])."""
    out, margin = _experts(_normed(x, moe, i, config), moe, i, live, config,
                           wrong)
    return x + out, margin


SUB_LAYERS = (_kda_sub_layer, _attention_sub_layer, _dense_sub_layer,
              _experts_sub_layer)


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


def hidden(params, tokens, config: dict, wrong: str | None = None,
           live: int | None = None, compiled: bool = False):
    """(the stream [S, C] after the last layer, routing margin [S])."""
    b = params["blocks"]
    x = _f32(params["embed"]["embedding"][jnp.asarray(tokens, jnp.int32)])
    s = x.shape[0]
    margin = jnp.full((s,), jnp.inf)
    fs = (_compiled_sub_layers(json.dumps(config, sort_keys=True), wrong)
          if compiled else tuple(
              functools.partial(f, config=config, wrong=wrong)
              for f in SUB_LAYERS))
    kda, attention, dense_ffn, experts = fs
    live = jnp.int32(s if live is None else live)
    seen = {"K": 0, "*": 0, "D": 0, "E": 0}

    def rank(kind):
        seen[kind] += 1
        return jnp.int32(seen[kind] - 1)
    for mixer, ffn in layer_kinds(config):
        if mixer == "K":
            x = kda(x, b["kda"], rank("K"))
        else:
            x = attention(x, b["attn"], rank("*"))
        if ffn == "D":
            x = dense_ffn(x, b["mlp"], rank("D"))
        else:
            x, layer_margin = experts(x, b["moe"], rank("E"), live)
            margin = jnp.minimum(margin, layer_margin)
    return x, margin


def logits(params, tokens, config: dict, positions=None,
           wrong: str | None = None, with_margin: bool = False,
           pad_to: int = 0, compiled: bool = False):
    """Logits [len(positions) or S, V] of one sequence; with
    ``with_margin`` (logits, routing margin [len(positions) or S]).
    ``pad_to``: zeros follow the sequence up to that length (one compiled
    shape for many lengths). ``compiled``: each kind of sub-layer runs as
    one jitted program."""
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
        latent_decoder._FLOAT8[0] = False
    return (lg, margin) if with_margin else lg
