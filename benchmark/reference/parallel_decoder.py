"""The plain reference of a decoder whose every layer runs attention heads
AND a Mamba-2 mixer side by side under ONE norm, as ``model_type:
falcon_h1`` describes it (tiiuae/Falcon-H1-34B-Instruct, config.json;
Mamba-2: Dao & Gu 2024, "Transformers are SSMs"; the multipliers: the
Falcon-H1 report's maximal-update parametrisation, every one a key of the
file).

One sequence x [S, H] at a time, every multiplier under its published name:

    e    = embed[ids] * embedding_multiplier
    h    = RMSNorm(x; w_in, eps)                        ONE norm, both mixers
    a    = h * attention_in_multiplier
    q, k, v = a W_q, (a W_k) * key_multiplier, a W_v    n_q / n_kv heads of D
    q, k = rope(q), rope(k)                             theta, the whole head
    attn = softmax(q k^T / sqrt(D), causal) v W_o * attention_out_multiplier
    p    = ((h * ssm_in_multiplier) W_in) * mup_vector  [z | x | B | C | dt]
           mup_vector: ssm_multipliers[0..4] on the columns of z, x, B, C, dt
    xBC  = silu(conv_K(p[x|B|C]) + conv_bias)           depthwise, causal
    dt   = softplus(p[dt] + dt_bias);  A = -exp(A_log)  a head, float32
    h_t[i] = exp(dt_t[i] A[i]) h_{t-1}[i] + dt_t[i] outer(x_t[i], B_t[g(i)])
    y_t[i] = h_t[i] C_t[g(i)] + D[i] x_t[i]             the plain recurrence
    y    = RMSNorm_grouped(y * silu(z); w_g, G groups)  the gate BEFORE the norm
    ssm  = y W_out * ssm_out_multiplier
    x    = x + attn + ssm
    m    = RMSNorm(x; w_ff, eps)                        the second norm
    x    = x + ((m W_up) * silu((m W_gate) * mlp_multipliers[0])) W_down
               * mlp_multipliers[1]
    logits = RMSNorm(x_L; w_final) W_head * lm_head_multiplier

Float32 ``jax.numpy`` with full-precision matrix multiplications, the
recurrence as a plain loop over the tokens (``jax.lax.scan`` of the one-token
update: no chunks), no cache, no kernels, no batching. Independent of the
program's ``models/`` and ``ops/``; it reads only that program's parameter
tree (one stack a layer KIND, indexed by the layer's rank among its kind):

    blocks.par.{norm.scale [L,H], q, k, v, o .kernel, in_proj.kernel
                [L,H,2 d_in + 2 G N + nh], conv.kernel [L,K,C], conv.bias
                [L,C], dt_bias, A_log, D [L,nh], gate_norm.scale [L,d_in],
                out_proj.kernel [L,d_in,H]}
    blocks.mlp.{norm.scale, gate, up, down .kernel}
    embed.embedding [V,H]; final_norm.scale [H]; lm_head.kernel [H,V]

It computes in BLOCKS, so that at the published widths it fits on a chip
beside the server's weights: one layer's weights are cast to float32 at a
time, the MLP by ``MLP_BLOCKS`` column blocks of its width (at 21,504: three
[5120, 5376] float32 kernels, 330 MB, the largest block this file holds
beside a layer's 189 MB in-projection), the attention a key-value head's
query group at a time, the head by ``HEAD_BLOCKS`` row blocks of the
vocabulary (at 261,120 x 5120: 334 MB a block).

Departures from the published form, each the program's own and noted at
its line: a norm's weight is stored as ``scale`` with the weight being ``1 +
scale`` (the gated norm's too); the conv kernel lies [K, C]; rope pairs a
head's value i with i + D/2 (the "rotate half" form, as the published code).

``wrong`` computes a WRONG model on purpose, to show that a comparison
against this reference fails when it should (tests/test_falcon_h1.py; the
chip check's readings in PERF.md): ``drop_attention``, ``drop_ssm``,
``one:<multiplier>`` (that multiplier set to 1: ``embedding``, ``lm_head``,
``attention_in``, ``attention_out``, ``key``, ``ssm_in``, ``ssm_out``,
``ssm0`` .. ``ssm4``, ``mlp0``, ``mlp1``), ``norm_before_gate``,
``one_group``, ``swap_bc``, ``drop_D``, ``bfloat16_state`` (the state
rounded to bfloat16 after every token), ``no_rope``, ``key_multiplier_on_q``,
``float8`` (every matmul operand rounded to float8_e4m3: the nearest
precision under the configuration's bfloat16).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
MLP_BLOCKS = 4
HEAD_BLOCKS = 16

MULTIPLIERS = ("embedding", "lm_head", "attention_in", "attention_out",
               "key", "ssm_in", "ssm_out", "ssm0", "ssm1", "ssm2", "ssm3",
               "ssm4", "mlp0", "mlp1")
WRONG = ("drop_attention", "drop_ssm", *(f"one:{m}" for m in MULTIPLIERS),
         "norm_before_gate", "one_group", "swap_bc", "drop_D",
         "bfloat16_state", "no_rope", "key_multiplier_on_q", "float8")


def multipliers(config: dict, wrong: str | None = None) -> dict:
    """{name: value} of the fourteen scalars (twelve keys, two of them
    lists) as the file gives them; ``wrong`` ``one:<name>`` sets one to 1."""
    m = {"embedding": config["embedding_multiplier"],
         "lm_head": config["lm_head_multiplier"],
         "attention_in": config["attention_in_multiplier"],
         "attention_out": config["attention_out_multiplier"],
         "key": config["key_multiplier"],
         "ssm_in": config["ssm_in_multiplier"],
         "ssm_out": config["ssm_out_multiplier"],
         **{f"ssm{i}": v for i, v in enumerate(config["ssm_multipliers"])},
         **{f"mlp{i}": v for i, v in enumerate(config["mlp_multipliers"])}}
    m = {k: float(v) for k, v in m.items()}
    if wrong and wrong.startswith("one:"):
        m[wrong[4:]] = 1.0
    return m


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
    "n_q", "n_kv", "theta", "m_in", "m_key", "m_out", "float8",
    "key_on_q"))
def _attention(h, w, *, n_q, n_kv, theta, m_in, m_key, m_out, float8,
               key_on_q):
    """The attention branch's output [S, H] from the normed stream h."""
    w = jax.tree_util.tree_map(_f32, w)
    s = h.shape[0]
    a = h * m_in
    q = _mm(a, w["q"], float8).reshape(s, n_q, -1)
    k = _mm(a, w["k"], float8).reshape(s, n_kv, -1)
    v = _mm(a, w["v"], float8).reshape(s, n_kv, -1)
    if key_on_q:
        q = q * m_key
    else:
        k = k * m_key
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
    return _mm(att, w["o"], float8) * m_out


@functools.partial(jax.jit, static_argnames=(
    "nh", "p", "n", "g", "eps", "m_in", "m_parts", "m_out", "float8",
    "norm_before_gate", "one_group", "swap_bc", "drop_d", "bf16_state"))
def _mamba(h, w, *, nh, p, n, g, eps, m_in, m_parts, m_out, float8,
           norm_before_gate, one_group, swap_bc, drop_d, bf16_state):
    """The state-space branch's output [S, H] from the normed stream h."""
    w = jax.tree_util.tree_map(_f32, w)
    s = h.shape[0]
    d_in, gn = nh * p, g * n
    by_column = jnp.concatenate([
        jnp.full((width,), m) for width, m in zip(
            (d_in, d_in, gn, gn, nh), m_parts)])
    proj = _mm(h * m_in, w["in_proj"], float8) * by_column
    z, xbc, dt = (proj[:, :d_in], proj[:, d_in:2 * d_in + 2 * gn],
                  proj[:, 2 * d_in + 2 * gn:])
    k = w["conv_kernel"].shape[0]                   # (the kernel lies [K, C])
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc], 0)
    conv = w["conv_bias"] + sum(w["conv_kernel"][j] * padded[j:j + s]
                                for j in range(k))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_in].reshape(s, nh, p)
    b = xbc[:, d_in:d_in + gn].reshape(s, g, n)
    c = xbc[:, d_in + gn:].reshape(s, g, n)
    if swap_bc:
        b, c = c, b
    if one_group:
        # every head reads the FIRST group's B and C
        b, c = (jnp.repeat(t[:, :1], g, axis=1) for t in (b, c))
    b, c = (jnp.repeat(t, nh // g, axis=1) for t in (b, c))      # [S,nh,N]
    step = jax.nn.softplus(dt + w["dt_bias"])                    # [S,nh]
    a = -jnp.exp(w["A_log"])
    skip = jnp.zeros_like(w["D"]) if drop_d else w["D"]

    def one(state, t):
        x_t, b_t, c_t, d_t = t
        state = (jnp.exp(d_t * a)[:, None, None] * state
                 + d_t[:, None, None] * x_t[:, :, None] * b_t[:, None, :])
        if bf16_state:
            state = jax.lax.reduce_precision(state, 8, 7)
        return state, jnp.sum(state * c_t[:, None, :], -1) \
            + skip[:, None] * x_t

    _, y = jax.lax.scan(one, jnp.zeros((nh, p, n)), (xs, b, c, step))
    y = y.reshape(s, d_in)

    def group_norm(v):
        vg = v.reshape(s, g, d_in // g)
        vg = vg * jax.lax.rsqrt(jnp.mean(vg * vg, -1, keepdims=True) + eps)
        return vg.reshape(s, d_in) * (1.0 + w["gate_norm"])

    y = (group_norm(y) * jax.nn.silu(z) if norm_before_gate
         else group_norm(y * jax.nn.silu(z)))
    return _mm(y, w["out_proj"], float8) * m_out


@functools.partial(jax.jit, static_argnames=("width", "m_gate", "float8"))
def _mlp_block(m, gate, up, down, i, j, *, width, m_gate, float8):
    """Column block j (``width`` columns) of layer i's gated MLP: [S, H]
    from the normed stream. The stacks come in whole; only this block's
    slices are cast."""
    def cols(stack):
        return _f32(jax.lax.dynamic_slice_in_dim(stack[i], j * width, width,
                                                 axis=1))
    hidden = _mm(m, cols(up), float8) * jax.nn.silu(
        _mm(m, cols(gate), float8) * m_gate)
    rows = _f32(jax.lax.dynamic_slice_in_dim(down[i], j * width, width,
                                             axis=0))
    return _mm(hidden, rows, float8)


@functools.partial(jax.jit, static_argnames=("width", "float8"))
def _head_block(x, head, j, *, width, float8):
    """The logits of ``width`` rows of the vocabulary from row j * width."""
    return _mm(x, _f32(jax.lax.dynamic_slice_in_dim(head, j * width, width,
                                                    axis=1)), float8)


def hidden(params, tokens, config: dict, wrong: str | None = None):
    """The final hidden states [S, H] (before the last norm) of ONE
    sequence of token ids, float32."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"no wrong model {wrong!r}: {WRONG}")
    par, mlp = params["blocks"]["par"], params["blocks"]["mlp"]
    eps = float(config["rms_norm_eps"])
    m = multipliers(config, wrong)
    float8 = wrong == "float8"
    x = _f32(params["embed"]["embedding"][jnp.asarray(tokens, jnp.int32)]) \
        * m["embedding"]
    for i in range(config["num_hidden_layers"]):
        h = _norm(x, par["norm"]["scale"][i], eps=eps)
        out = jnp.zeros_like(x)
        if wrong != "drop_attention":
            out = out + _attention(
                h, {n: par[n]["kernel"][i] for n in ("q", "k", "v", "o")},
                n_q=config["num_attention_heads"],
                n_kv=config["num_key_value_heads"],
                theta=0.0 if wrong == "no_rope" else float(
                    config["rope_theta"]),
                m_in=m["attention_in"], m_key=m["key"],
                m_out=m["attention_out"], float8=float8,
                key_on_q=wrong == "key_multiplier_on_q")
        if wrong != "drop_ssm":
            w = {"in_proj": par["in_proj"]["kernel"][i],
                 "conv_kernel": par["conv"]["kernel"][i],
                 "conv_bias": par["conv"]["bias"][i],
                 "dt_bias": par["dt_bias"][i], "A_log": par["A_log"][i],
                 "D": par["D"][i], "gate_norm": par["gate_norm"]["scale"][i],
                 "out_proj": par["out_proj"]["kernel"][i]}
            out = out + _mamba(
                h, w, nh=config["mamba_n_heads"], p=config["mamba_d_head"],
                n=config["mamba_d_state"], g=config["mamba_n_groups"],
                eps=eps, m_in=m["ssm_in"],
                m_parts=tuple(m[f"ssm{j}"] for j in range(5)),
                m_out=m["ssm_out"], float8=float8,
                norm_before_gate=wrong == "norm_before_gate",
                one_group=wrong == "one_group", swap_bc=wrong == "swap_bc",
                drop_d=wrong == "drop_D",
                bf16_state=wrong == "bfloat16_state")
        x = x + out
        u = _norm(x, mlp["norm"]["scale"][i], eps=eps)
        width = mlp["up"]["kernel"].shape[-1]
        blocks = MLP_BLOCKS if width % MLP_BLOCKS == 0 else 1
        step = width // blocks
        ffn = jnp.zeros_like(x)
        for j in range(blocks):     # the MLP by column blocks of its width
            ffn = ffn + _mlp_block(
                u, mlp["gate"]["kernel"], mlp["up"]["kernel"],
                mlp["down"]["kernel"], i, j, width=step, m_gate=m["mlp0"],
                float8=float8)
        x = x + ffn * m["mlp1"]
    return x


def logits(params, tokens, config: dict, positions=None,
           wrong: str | None = None, round_to: int = 0):
    """Logits [len(positions) or S, V] of one sequence. ``round_to``: zeros
    follow the sequence up to a multiple of it (one compiled shape for many
    lengths; no earlier position of a causal model sees them)."""
    tokens = list(tokens)
    if round_to and len(tokens) % round_to:
        if positions is None:
            positions = range(len(tokens))
        tokens = tokens + [0] * (round_to - len(tokens) % round_to)
    x = hidden(params, tokens, config, wrong)
    if positions is not None:
        x = x[jnp.asarray(list(positions), jnp.int32)]
    x = _norm(x, params["final_norm"]["scale"],
              eps=float(config["rms_norm_eps"]))
    head = params["lm_head"]["kernel"]
    vocab = head.shape[-1]
    blocks = HEAD_BLOCKS if vocab % HEAD_BLOCKS == 0 else 1
    step = vocab // blocks
    out = [_head_block(x, head, j, width=step, float8=wrong == "float8")
           for j in range(blocks)]   # the head by row blocks of the vocabulary
    return jnp.concatenate(out, -1) * multipliers(config, wrong)["lm_head"]
