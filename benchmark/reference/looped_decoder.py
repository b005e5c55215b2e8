"""The plain reference of a LOOPED decoder (``model_type: ouro``): one stack
of sandwich-normed layers walked ``total_ut_steps`` times over one set of
weights, the final norm and an exit gate after every pass.

``x`` the token ids, ``L`` layers, ``T`` passes, ``N`` an RMSNorm with its
own scale, rope by rotate-half at the token's position in EVERY pass:

    h = Embed(x)
    for t in 1..T:                          # the same L layers' weights
        for l in 1..L:
            a = h + N2_l(Attn_l(N1_l(h)))   # causal; K and V are this
                                            # pass's own projections
            h = a + N4_l(W_down_l(silu(W_gate_l N3_l(a)) * (W_up_l N3_l(a))))
        z_t = N_f(h)                        # the ONE final norm
        g_t = sigmoid(w_g . z_t + b_g)      # the exit gate
        h   = z_t                           # the NORMED state goes on
    p_t = g_t prod_{j<t}(1 - g_j) for t < T;  p_T = prod_{j<T}(1 - g_j)
    a token leaves at the first t with sum_{j<=t} p_j >= early_exit_threshold
    logits = W_out z_t at that t

Straightforward ``jax.numpy`` in float32 with full-precision matrix
multiplications, no cache, no kernels, no batching: one sequence at a time,
one layer's weights cast at a time (sliced out of the program's stacks
inside the layer's own jitted function, by a traced index: one program for
every layer), so it fits beside a loaded engine.
Independent of the program's package; it only reads that program's
parameter tree:

    embed.embedding [V,H]; blocks.{q,k,v,o}.kernel [L,in,out];
    blocks.mlp.{gate,up,down}.kernel [L,in,out];
    blocks.{attn_norm,attn_out_norm,mlp_norm,mlp_out_norm}.scale [L,H]
    (N1, N2, N3, N4); final_norm.scale [H]; exit_gate.kernel [H,1],
    exit_gate.bias [1]; lm_head.kernel [H,V]

One departure from the published form, the program's own: a norm's weight
is stored as ``scale`` with the weight being ``1 + scale``.

``wrong`` makes it another model, to show that a comparison fails when it
should: ``passes_3`` (one pass fewer), ``plane_1`` (every pass attends the
FIRST pass's keys and values of the layer), ``no_out_norm`` (N2 and N4 left
out), ``unnormed_state`` (the next pass reads h, not z_t), ``float8`` (every
matmul's operands rounded to 4 exponent and 3 mantissa bits: the nearest
precision under bfloat16).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
WRONG = ("passes_3", "plane_1", "no_out_norm", "unnormed_state", "float8")


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


def _matmul(float8: bool):
    def mm(a, w):
        if float8:
            a, w = (jax.lax.reduce_precision(t, 4, 3) for t in (a, w))
        return jnp.matmul(a, w, precision=_HIGHEST)
    return mm


def _layer_weights(blocks, i) -> dict:
    """Layer ``i``'s weights out of the stacks [L, ...], float32 (``i`` is
    traced: ONE program serves every layer)."""
    b = blocks
    picks = {"attn_norm": b["attn_norm"]["scale"],
             "attn_out_norm": b["attn_out_norm"]["scale"],
             "mlp_norm": b["mlp_norm"]["scale"],
             "mlp_out_norm": b["mlp_out_norm"]["scale"],
             "q": b["q"]["kernel"], "k": b["k"]["kernel"],
             "v": b["v"]["kernel"], "o": b["o"]["kernel"],
             "gate": b["mlp"]["gate"]["kernel"],
             "up": b["mlp"]["up"]["kernel"],
             "down": b["mlp"]["down"]["kernel"]}
    return {name: _f32(jax.lax.dynamic_index_in_dim(a, i, keepdims=False))
            for name, a in picks.items()}


@functools.partial(jax.jit, static_argnames=(
    "n_q", "n_kv", "eps", "theta", "out_norm", "float8"))
def _layer(x, blocks, i, kv, *, n_q, n_kv, eps, theta, out_norm, float8):
    """Block ``i`` on one sequence x [S, H]; ``blocks`` the program's
    stacks, of which that layer's slices alone are cast; ``kv`` None, or
    the (k, v) [S, Nkv, D] to attend in place of this pass's own. Returns
    (x, (k, v) of this pass)."""
    mm = _matmul(float8)
    w = _layer_weights(blocks, i)
    s = x.shape[0]
    h = _rms_norm(x, w["attn_norm"], eps)
    q = _rope(mm(h, w["q"]).reshape(s, n_q, -1), theta)
    own = (_rope(mm(h, w["k"]).reshape(s, n_kv, -1), theta),
           mm(h, w["v"]).reshape(s, n_kv, -1))
    k, v = own if kv is None else kv
    d = q.shape[-1]
    group = n_q // n_kv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qnd,knd->nqk", q, k, precision=_HIGHEST) / d ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, -1), v,
                     precision=_HIGHEST)
    out = mm(att.reshape(s, n_q * d), w["o"])
    x = x + (_rms_norm(out, w["attn_out_norm"], eps) if out_norm else out)
    h = _rms_norm(x, w["mlp_norm"], eps)
    out = mm(jax.nn.silu(mm(h, w["gate"])) * mm(h, w["up"]), w["down"])
    return x + (_rms_norm(out, w["mlp_out_norm"], eps) if out_norm
                else out), own


@functools.partial(jax.jit, static_argnames=("eps",))
def _close(x, final_scale, gate_kernel, gate_bias, *, eps):
    """(z_t, g_t) of a pass's last stream x [S, H]."""
    z = _rms_norm(x, _f32(final_scale), eps)
    g = jax.nn.sigmoid(jnp.matmul(z, _f32(gate_kernel), precision=_HIGHEST
                                  )[:, 0] + _f32(gate_bias)[0])
    return z, g


def _walk(params, tokens, config: dict, wrong, round_to: int, with_kv: bool):
    """Every pass of one sequence at its PADDED length (a multiple of
    ``round_to``: causal attention, a position sees nothing behind it), so
    that every operation compiles once a padded length and not once a
    sequence: (z [T, S', H], g [T, S'], {(t, l): (k, v)} or {})."""
    if wrong not in (None, *WRONG):
        raise ValueError(f"wrong must be one of {WRONG} (got {wrong!r})")
    tokens = list(tokens)
    if round_to:
        tokens += [0] * (-len(tokens) % round_to)
    T = config["total_ut_steps"] - (wrong == "passes_3")
    how = dict(n_q=config["num_attention_heads"],
               n_kv=config["num_key_value_heads"],
               eps=float(config["rms_norm_eps"]),
               theta=float(config["rope_theta"]),
               out_norm=wrong != "no_out_norm", float8=wrong == "float8")
    x = _f32(params["embed"]["embedding"][jnp.asarray(tokens, jnp.int32)])
    zs, gs, kvs, first = [], [], {}, {}
    for t in range(T):
        for i in range(config["num_hidden_layers"]):
            x, kv = _layer(x, params["blocks"], jnp.int32(i), first.get(i),
                           **how)
            if wrong == "plane_1" and t == 0:
                first[i] = kv
            if with_kv:
                kvs[t, i] = kv
        z, g = _close(x, params["final_norm"]["scale"],
                      params["exit_gate"]["kernel"],
                      params["exit_gate"]["bias"], eps=how["eps"])
        zs.append(z)
        gs.append(g)
        x = x if wrong == "unnormed_state" else z
    return jnp.stack(zs), jnp.stack(gs), kvs


def passes(params, tokens, config: dict, wrong: str | None = None,
           round_to: int = 0, with_kv: bool = False):
    """Every pass of ONE sequence of token ids, float32: (z [T, S, H] the
    normed states, g [T, S] the gates), with ``with_kv`` also {(t, l): (k,
    v)} each [S, Nkv, D] (rotated keys: what a cache keeps)."""
    n = len(tokens)
    z, g, kvs = _walk(params, tokens, config, wrong, round_to, with_kv)
    kvs = {at: tuple(a[:n] for a in kv) for at, kv in kvs.items()}
    return (z[:, :n], g[:, :n], kvs) if with_kv else (z[:, :n], g[:, :n])


def exit_pass(g, threshold: float):
    """The pass (0-based) each position leaves at, [S]: the first t whose
    running sum of p reaches ``threshold``; the last pass takes what is
    left, so everything has left by then."""
    T = g.shape[0]
    stay = jnp.cumprod(1.0 - g, axis=0)
    before = jnp.concatenate([jnp.ones_like(g[:1]), stay[:-1]])
    p = jnp.concatenate([(g * before)[:-1], before[-1:]])
    reached = jnp.cumsum(p, axis=0) >= threshold
    reached = reached.at[T - 1].set(True)
    return jnp.argmax(reached, axis=0)


@functools.partial(jax.jit, static_argnames=("threshold", "float8"))
def _logits_at(z, g, positions, head, *, threshold, float8):
    """W_out z_t at the pass each of ``positions`` leaves at."""
    at = exit_pass(g, threshold)
    left = jnp.take_along_axis(z, at[None, :, None], axis=0)[0]
    return _matmul(float8)(left[positions], _f32(head))


def logits(params, tokens, config: dict, positions=None,
           wrong: str | None = None, round_to: int = 0,
           with_passes: bool = False):
    """Logits [len(positions) or S, V] of one sequence; ``with_passes``
    also returns (z [T, S, H], g [T, S]) of every position. ``round_to``
    pads the sequence to a multiple, so that a few lengths compile."""
    n = len(tokens)
    z, g, _ = _walk(params, tokens, config, wrong, round_to, False)
    positions = jnp.asarray(range(n) if positions is None else positions,
                            jnp.int32)
    out = _logits_at(z, g, positions, params["lm_head"]["kernel"],
                     threshold=float(config["early_exit_threshold"]),
                     float8=wrong == "float8")
    return (out, (z[:, :n], g[:, :n])) if with_passes else out
