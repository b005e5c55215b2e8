"""The plain reference of a decoder with latent attention (MLA) behind a
query bottleneck, sigmoid-routed experts behind a leading dense layer, and ONE
next-token prediction module behind the main stack, as ``model_type:
joyai_llm_flash`` names them key for key (JoyAI-LLM-Flash, config.json; the
family is DeepSeek-V3's: MLA, the sigmoid / bias / scaling router and the
module are its technical report's sections 2.1.1, 2.1.2 and 2.2).

One sequence at a time, tokens t_0 .. t_{S-1}. Main stack, per decoder layer,
on the residual stream x (pre-norm, RMSNorm with ``rms_norm_eps``, a norm's
weight stored as ``1 + scale``):

    u = RMSNorm(x)
    c_q           = RMSNorm_q(u W_qa);  [q_nope|q_pe]_i = c_q W_qb   (head i)
    [c_kv|k_pe]   = u W_kva;  c_kv = RMSNorm_kv(c_kv);  k_pe one for all heads
    [k_nope|v]_i  = c_kv W_kvb
    s_ij = (q_nope_i . k_nope_ij + rope(q_pe_i) . rope(k_pe_j)) (nope+rope)^-0.5
    x   += concat_i(softmax_{j<=i}(s_i) v_i) W_o
    u = RMSNorm(x)
    layer < first_k_dense_replace:   x += W_down(silu(u W_gate) * (u W_up))
    else:  s = sigmoid(u W_r);  chosen = top-k of s + b;
           w = routed_scaling_factor * s[chosen] / (sum s[chosen] + 1e-20)
           x += sum_e w_e E_e(u) + E_shared(u)

rope: plain (``rope_scaling`` null), base ``rope_theta``, over the ``pe``
values alone, HALVES paired (value i with i + rope/2; the published
``rope_interleave`` pairs neighbours, the same function of a permuted
projection). logits_i = Head(RMSNorm_f(h_i)), h_i the stream after the last
layer. EXPANDED attention only: nothing is absorbed, nothing is cached.

The module (``num_nextn_predict_layers`` 1), for position i with t_{i+1}:

    z_i  = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]      (2C -> C)
    z'_i = one more decoder layer of the expert kind on z (its own attention
           over z' rows 0..i at rope positions 0..i, its own router)
    d_i  = Head(RMSNorm_m(z'_i))                 predicts t_{i+2}

h_i is the stream BEFORE the main model's final norm, the embedding comes
first in the concatenation, Emb and Head are the main model's.

HELD experts and vocabulary: where the configuration states ``router_experts``
(256) beside ``n_routed_experts`` (128 held from ``first_expert``), the router
keeps all its outputs and its top-k and normalisation run over all of them;
the held experts' part and the shared expert are added, the rest is left out.
The vocabulary is what embedding and head hold.

Float32 ``jax.numpy`` at ``highest`` matmul precision, no cache, no kernels,
no batching; blocked as ``reference/latent_decoder.py`` is (heads x queries,
rows of a feed-forward, an expert's own rows) so that a 12k context fits
beside a server's weights, each kind of sub-layer one jitted program with
``compiled``. Independent of ``models/``; it reads the program's parameter
tree alone (one stack a layer KIND, indexed by the rank among its kind; the
module's layer is the LAST entry of ``attn`` and ``moe``):

    blocks.attn.{norm.scale [La+1,C], q_a.kernel, q_a_norm.scale, q_b.kernel,
                 kv_a.kernel, kv_norm.scale, kv_b.kernel (a head's k_nope
                 then its v), o.kernel}
    blocks.mlp.{norm.scale, gate / up / down .kernel}
    blocks.moe.{norm.scale, router.kernel [Le+1,C,E], router.bias,
                gate / up .kernel [Le+1,held,C,F], down.kernel,
                shared.{gate,up,down}.kernel}
    mtp.{enorm.scale, hnorm.scale, eh_proj.kernel [2C,C], final_norm.scale}
    embed.embedding [V,C]; final_norm.scale [C]; lm_head.kernel [C,V]

``wrong`` computes a WRONG model on purpose, to show that a check against
this reference fails when it should: ``float8`` (every operand of every
matrix product rounded to float8_e4m3, the nearest precision under the
configuration's bfloat16), and of the module alone ``no_hnorm`` (RMSNorm_h
left out), ``normed_stream`` (the module reads the stream AFTER the main
model's final norm), ``swapped_concat`` (stream first), ``own_token`` (the
embedding of t_i, not of t_{i+1}). (Which rope position the module's row i
takes cannot be told: a rotation is relative, and i + c for every row gives
the same scores.)
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK, HEAD_BLOCK, ROW_BLOCK, VOCAB_BLOCK = 256, 8, 2048, 16384
EXPERT_ROWS = 1024
MODULE_WRONGS = ("no_hnorm", "normed_stream", "swapped_concat", "own_token")


def _f32(x):
    return jnp.asarray(x, jnp.float32)


# ``wrong="float8"``: every operand of every matrix product rounded to
# float8_e4m3; set by ``forward``
_FLOAT8 = [False]


def _r(x):
    x = _f32(x)
    return _f32(x.astype(jnp.float8_e4m3fn)) if _FLOAT8[0] else x


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + _f32(scale))


def _rope(x, inv_freq):
    """x [S, ..., d] rotated by its position (axis 0), halves paired."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    ang = ang.reshape(s, *([1] * (x.ndim - 2)), -1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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


def _latent_attention(h, w, config: dict):
    s = h.shape[0]
    n = int(config["num_attention_heads"])
    dn, dr, dv, r = (int(config[k]) for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank"))
    eps = float(config["rms_norm_eps"])
    inv_freq = 1.0 / float(config["rope_theta"]) ** (
        jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    c_q = _rms_norm(_r(h) @ _r(w["q_a"]), w["q_a_norm"], eps)
    q = (_r(c_q) @ _r(w["q_b"])).reshape(s, n, dn + dr)
    ckv = _r(h) @ _r(w["kv_a"])
    c_kv = _rms_norm(ckv[:, :r], w["kv_norm"], eps)
    k_pe = _rope(ckv[:, r:], inv_freq)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv_freq)], -1)
    scale = (dn + dr) ** -0.5
    pos = jnp.arange(s)
    g = math.gcd(HEAD_BLOCK, n)
    w_kvb = _r(w["kv_b"]).reshape(r, n // g, g, dn + dv)
    c_kv, q = _r(c_kv), _r(q).reshape(s, n // g, g, dn + dr)

    def head_block(of):                            # a block of heads
        w_b, q_b = of                              # [r, g, dn+dv], [S, g, .]
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


def _mlp(h, gate, up, down):
    # a routed expert's gate / up kernels lie [F, C] (out, in) where F is no
    # multiple of 128 (the program's toy sizes), else [C, F]: read off
    gate, up = (_r(w) if w.shape[0] == h.shape[-1] else _r(w).T
                for w in (gate, up))
    down = _r(down)
    return _by_rows(lambda rows: _r(jax.nn.silu(rows @ gate) * (rows @ up))
                    @ down, ROW_BLOCK, _r(h))


def route(h, router, bias, config: dict):
    """(weights [S, E] over ALL the router's experts (zero off the chosen
    set), the biased scores [S, E], margin [S]: the distance between the
    k-th and the k+1-th largest biased score)."""
    k = int(config["num_experts_per_tok"])
    scores = jax.nn.sigmoid(_r(h) @ _r(router))
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
    return full, biased, margin


def experts(h, moe, i, config: dict, live=None, shared: bool = True):
    """(the expert layer's output [S, C] over the experts HELD, the biased
    scores [S, E], the routing margin [S]); rows from ``live`` on (padding)
    choose no expert."""
    s = h.shape[0]
    held = moe["down"]["kernel"].shape[1]
    first = int(config.get("first_expert", 0))
    weights, biased, margin = route(h, moe["router"]["kernel"][i],
                                    moe["router"]["bias"][i], config)
    weights = weights[:, first:first + held]
    if live is not None:
        weights = jnp.where(jnp.arange(s)[:, None] < live, weights, 0.0)
    out = jnp.zeros_like(_f32(h))
    if shared:
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
    out, _ = jax.lax.scan(expert, out, jnp.arange(held))
    return out, biased, margin


def _attention_sub_layer(x, a, i, config: dict):
    w = {k: a[k]["kernel"][i] for k in ("q_a", "q_b", "kv_a", "kv_b", "o")}
    w["q_a_norm"] = a["q_a_norm"]["scale"][i]
    w["kv_norm"] = a["kv_norm"]["scale"][i]
    eps = float(config["rms_norm_eps"])
    return x + _latent_attention(
        _rms_norm(x, a["norm"]["scale"][i], eps), w, config)


def _dense_sub_layer(x, m, i, config: dict):
    eps = float(config["rms_norm_eps"])
    return x + _mlp(_rms_norm(x, m["norm"]["scale"][i], eps),
                    m["gate"]["kernel"][i], m["up"]["kernel"][i],
                    m["down"]["kernel"][i])


def _experts_sub_layer(x, moe, i, live, config: dict):
    """(x, the layer's biased scores [S, E], its routing margin [S])."""
    eps = float(config["rms_norm_eps"])
    out, biased, margin = experts(
        _rms_norm(x, moe["norm"]["scale"][i], eps), moe, i, config, live)
    return x + out, biased, margin


SUB_LAYERS = (_attention_sub_layer, _dense_sub_layer, _experts_sub_layer)


@functools.lru_cache(maxsize=None)
def _compiled_sub_layers(config_json: str):
    """``SUB_LAYERS`` jitted for one configuration: a program a KIND of
    sub-layer (the layer's index is an argument, the stacks go in whole)."""
    config = json.loads(config_json)
    return tuple(jax.jit(functools.partial(f, config=config),
                         donate_argnums=0) for f in SUB_LAYERS)


def _sub_layers(config: dict, compiled: bool):
    if compiled:
        return _compiled_sub_layers(json.dumps(config, sort_keys=True))
    return tuple(functools.partial(f, config=config) for f in SUB_LAYERS)


def hidden(params, tokens, config: dict, live=None, compiled: bool = False):
    """(the stream [S, C] after the main stack's last layer, the expert
    layers' biased scores [Le, S, E], the least routing margin [S])."""
    b = params["blocks"]
    x = _f32(params["embed"]["embedding"][jnp.asarray(tokens, jnp.int32)])
    s = x.shape[0]
    attention, dense_ffn, moe = _sub_layers(config, compiled)
    live = jnp.int32(s if live is None else live)
    dense = int(config["first_k_dense_replace"])
    margin, scores = jnp.full((s,), jnp.inf), []
    for layer in range(int(config["num_hidden_layers"])):
        x = attention(x, b["attn"], jnp.int32(layer))
        if layer < dense:
            x = dense_ffn(x, b["mlp"], jnp.int32(layer))
        else:
            x, biased, layer_margin = moe(x, b["moe"],
                                          jnp.int32(layer - dense), live)
            margin = jnp.minimum(margin, layer_margin)
            scores.append(biased)
    return x, jnp.stack(scores), margin


def module(params, stream, tokens, config: dict, live=None,
           wrong: str | None = None, compiled: bool = False):
    """The prediction module over a whole sequence: ``stream`` [S, C] the
    main stack's (before its final norm), row i reads the embedding of
    ``tokens[i + 1]`` (so ``tokens`` has S + 1 entries: the last is the
    token after the sequence). Returns (z' [S, C], its router's biased
    scores [S, E], its routing margin [S])."""
    b, m = params["blocks"], params["mtp"]
    eps = float(config["rms_norm_eps"])
    s = stream.shape[0]
    tokens = jnp.asarray(tokens, jnp.int32)
    read = tokens[:s] if wrong == "own_token" else tokens[1:s + 1]
    e = _rms_norm(_f32(params["embed"]["embedding"][read]),
                  m["enorm"]["scale"], eps)
    h = stream
    if wrong == "normed_stream":
        h = _rms_norm(h, params["final_norm"]["scale"], eps)
    if wrong != "no_hnorm":
        h = _rms_norm(h, m["hnorm"]["scale"], eps)
    both = [h, e] if wrong == "swapped_concat" else [e, h]
    z = _r(jnp.concatenate(both, -1)) @ _r(m["eh_proj"]["kernel"])
    attention, _dense, moe = _sub_layers(config, compiled)
    la = int(config["num_hidden_layers"])
    le = la - int(config["first_k_dense_replace"])
    z = attention(z, b["attn"], jnp.int32(la))
    live = jnp.int32(s if live is None else live)
    return moe(z, b["moe"], jnp.int32(le), live)


def _head(params, x, norm_scale, config: dict):
    x = _rms_norm(x, norm_scale, float(config["rms_norm_eps"]))
    head = params["lm_head"]["kernel"]
    return jnp.concatenate([                       # a block of the vocabulary
        _r(x) @ _r(head[:, lo:lo + VOCAB_BLOCK])
        for lo in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1)


def forward(params, tokens, config: dict, positions=None, next_token: int = 0,
            wrong: str | None = None, round_to: int = 0,
            compiled: bool = False, with_scores: bool = False) -> dict:
    """One sequence through main stack and module. ``positions`` (default:
    all) picks the rows returned:

    - ``main`` [P, V]: the main stack's logits (row i predicts t_{i+1});
    - ``draft`` [P, V]: the module's (row i, read with t_{i+1}, predicts
      t_{i+2}); the last row reads ``next_token``;
    - ``margin`` / ``draft_margin`` [P]: the least distance between the
      k-th and k+1-th biased router score over the main stack's expert
      layers at the row / in the module's router;
    - with ``with_scores``, ``scores`` [Le + 1, P, E]: every router's
      biased scores, the module's last.

    ``round_to``: zeros follow the sequence up to a multiple of it (one
    compiled shape for many lengths; no earlier position of a causal model
    sees them, and they choose no expert). ``compiled``: each kind of
    sub-layer runs as one jitted program."""
    tokens = [int(t) for t in tokens]
    live = len(tokens)
    if positions is None:
        positions = range(live)
    padded = tokens + [0] * (-live % round_to if round_to else 0)
    _FLOAT8[0] = wrong == "float8"
    # (a compiler may keep more precision than a fused pair of conversions
    # asks for: the roundings to float8 run operation by operation)
    compiled = compiled and wrong != "float8"
    try:
        with jax.default_matmul_precision("highest"):
            x, scores, margin = hidden(params, padded, config, live, compiled)
            at = jnp.asarray(list(positions), jnp.int32)
            reads = padded + [0]          # row i reads ``reads[i + 1]``
            reads[live] = int(next_token)
            z, m_scores, m_margin = module(
                params, x, reads, config, live,
                wrong if wrong in MODULE_WRONGS else None, compiled)
            out = {"main": _head(params, x[at],
                                 params["final_norm"]["scale"], config),
                   "draft": _head(params, z[at],
                                  params["mtp"]["final_norm"]["scale"],
                                  config),
                   "margin": margin[at], "draft_margin": m_margin[at]}
            if with_scores:
                out["scores"] = jnp.concatenate(
                    [scores[:, at], m_scores[None, at]])
    finally:
        _FLOAT8[0] = False
    return out
