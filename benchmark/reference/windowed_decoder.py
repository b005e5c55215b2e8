"""The plain reference of a decoder whose layers are WINDOW layers and full
ones mixed (``model_type: mellum``; Mellum2-12B-A2.5B-Instruct's config.json:
``layer_types``, ``sliding_window``, ``rope_parameters`` keyed by layer kind),
every layer with sparse experts.

``x`` the token ids, ``n`` an RMSNorm with its own scale (eps 1e-6), two a
layer, one sequence [S, H] at a time:

    h = x + Attn_l(n(x));   y = h + MoE(n(h));   logits = W_out n_f(y_L)

    Attn_l: q = W_q n as Nq heads of D, k, v as Nkv heads (no bias); an
      RMSNorm with a learned [D] scale over each head of q and of k; rope over
      the whole head (pairs (i, i + D/2)) at theta: a WINDOW layer by the plain
      frequencies theta^(-2i/D); a FULL layer by YaRN's (each frequency blended
      between f and f / factor by a linear ramp between the correction dims of
      beta_fast and beta_slow rotations over the original context) with cos and
      sin multiplied by ``attention_factor``; scores q . k / sqrt(D), Nq / Nkv
      query heads a K/V head; query i sees key j iff j <= i and, in a window
      layer, j > i - sliding_window (``sliding_window`` keys, itself included);
      softmax; W_o.
    MoE: p = softmax(W_r n) over E; the k largest, their weights divided by
      their sum (``norm_topk_prob``); sum_e w_e W_down,e (silu(W_gate,e n) *
      W_up,e n); dropless, no shared expert.

Straightforward ``jax.numpy`` in float32 with full-precision matrix
multiplications, no cache, no kernels, no sorting: EVERY expert is applied to
every row and masked by the top-k weights. It is computed in BLOCKS of
``ROW_BLOCK`` rows (a block's queries against all keys; a block's rows through
one expert at a time) over the live blocks alone, inside one jitted function a
layer kind whose sequence length is the PADDED one: a 15k-token sequence costs
0.5 GB of scores a block and compiles once a padded length, not once a
sequence. One layer's weights are cast to float32 at a time (sliced out of the
program's stacks by a traced index). Independent of the program's package; it
only reads that program's parameter tree:

    embed.embedding [V,H]; blocks.{q,k,v,o}.kernel [L,in,out];
    blocks.{q_norm,k_norm}.scale [L,D]; blocks.{attn_norm,mlp_norm}.scale
    [L,H]; blocks.moe.router.kernel [L,H,E]; blocks.moe.{gate,up}.kernel
    [L,E,H,F]; blocks.moe.down.kernel [L,E,F,H]; final_norm.scale [H];
    lm_head.kernel [H,V]

One departure from the published form, the program's own: a norm's weight is
stored as ``scale`` with the weight being ``1 + scale`` (the q/k norms too).

``wrong`` makes it another model, to show that a comparison fails when it
should: ``all_full`` (no layer has a window), ``window_minus_1`` and
``window_plus_1`` (1,023 and 1,025 keys), ``yarn_everywhere`` (the window
layers rotate by the full layers' rope too), ``no_attention_factor`` (YaRN's
frequencies with cos and sin as they are), ``no_renorm`` (the top-k weights as
the softmax gave them), ``full_first`` (the period's full layer first:
``F W W W``), ``float8`` (every matmul's operands rounded to 4 exponent and 3
mantissa bits: the nearest precision under bfloat16). Several at once are
joined by ``+`` (``no_renorm+no_attention_factor``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
WRONG = ("all_full", "window_minus_1", "window_plus_1", "yarn_everywhere",
         "no_attention_factor", "no_renorm", "full_first", "float8")
ROW_BLOCK = 512


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + scale)


def rope_of(group: dict, dim: int) -> tuple:
    """(inverse frequencies [dim / 2] as a tuple of floats, what cos and sin
    are multiplied by) of one ``rope_parameters`` group."""
    theta = float(group["rope_theta"])
    freqs = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if group.get("rope_type", "default") == "default":
        return tuple(freqs), 1.0
    factor = float(group["factor"])
    original = float(group["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(float(group["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(group["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = [min(max((i - low) / (high - low), 0.0), 1.0)
            for i in range(dim // 2)]
    return (tuple(f / factor * r + f * (1.0 - r)
                  for f, r in zip(freqs, ramp)),
            float(group.get("attention_factor", 1.0)))


def _rope(x, inv_freq, scale):
    """x [S, N, D]: rotate the pair (i, i + D/2) of every head by position *
    inv_freq[i], cos and sin times ``scale``."""
    s, _, d = x.shape
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv_freq, jnp.float32)[None, :])
    cos, sin = scale * jnp.cos(ang)[:, None, :], scale * jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _matmul(float8: bool):
    def mm(a, w):
        if float8:
            a, w = (jax.lax.reduce_precision(t, 4, 3) for t in (a, w))
        return jnp.matmul(a, w, precision=_HIGHEST)
    return mm


def _layer_weights(blocks, i) -> dict:
    """Layer ``i``'s attention, norm and router weights out of the stacks
    [L, ...], float32 (``i`` is traced: one program a layer kind)."""
    b = blocks
    picks = {"attn_norm": b["attn_norm"]["scale"],
             "mlp_norm": b["mlp_norm"]["scale"],
             "q_norm": b["q_norm"]["scale"], "k_norm": b["k_norm"]["scale"],
             "q": b["q"]["kernel"], "k": b["k"]["kernel"],
             "v": b["v"]["kernel"], "o": b["o"]["kernel"],
             "router": b["moe"]["router"]["kernel"]}
    return {name: _f32(jax.lax.dynamic_index_in_dim(a, i, keepdims=False))
            for name, a in picks.items()}


@functools.partial(jax.jit, static_argnames=(
    "n_q", "n_kv", "eps", "window", "inv_freq", "rope_scale", "top_k",
    "renormalise", "float8", "all_blocks"))
def _layer(x, blocks, i, n_blocks, *, n_q, n_kv, eps, window, inv_freq,
           rope_scale, top_k, renormalise, float8, all_blocks=False):
    """Block ``i`` on one sequence x [S, H] (S a multiple of ROW_BLOCK), its
    first ``n_blocks`` row blocks computed and the rest left as they are
    (``all_blocks``: every one, a static count, which a gradient can be
    taken through); ``window`` 0: a full layer."""
    if all_blocks:
        n_blocks = x.shape[0] // ROW_BLOCK
    mm = _matmul(float8)
    w = _layer_weights(blocks, i)
    s, hidden = x.shape
    h = _rms_norm(x, w["attn_norm"], eps)
    q = _rms_norm(mm(h, w["q"]).reshape(s, n_q, -1), w["q_norm"], eps)
    k = _rms_norm(mm(h, w["k"]).reshape(s, n_kv, -1), w["k_norm"], eps)
    q, k = _rope(q, inv_freq, rope_scale), _rope(k, inv_freq, rope_scale)
    v = mm(h, w["v"]).reshape(s, n_kv, -1)
    d = q.shape[-1]
    group = n_q // n_kv
    keys = jnp.arange(s)[None, :]

    def attend(b, out):
        rows = b * ROW_BLOCK + jnp.arange(ROW_BLOCK)[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, b * ROW_BLOCK, ROW_BLOCK)
        qb = qb.reshape(ROW_BLOCK, n_kv, group, d)
        scores = jnp.einsum("qngd,knd->ngqk", qb, k,
                            precision=_HIGHEST) / d ** 0.5
        sees = keys <= rows
        if window:
            sees = sees & (keys > rows - window)
        scores = jnp.where(sees[None, None], scores, -jnp.inf)
        att = jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(scores, -1), v,
                         precision=_HIGHEST)
        return jax.lax.dynamic_update_slice_in_dim(
            out, mm(att.reshape(ROW_BLOCK, n_q * d), w["o"]),
            b * ROW_BLOCK, 0)
    x = x + jax.lax.fori_loop(0, n_blocks, attend, jnp.zeros_like(x))

    moe = blocks["moe"]
    n_experts = w["router"].shape[-1]

    def experts(b, out):
        xb = jax.lax.dynamic_slice_in_dim(x, b * ROW_BLOCK, ROW_BLOCK)
        hb = _rms_norm(xb, w["mlp_norm"], eps)
        p = jax.nn.softmax(mm(hb, w["router"]), -1)
        top_p, top_e = jax.lax.top_k(p, top_k)
        if renormalise:
            top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
        weights = jnp.zeros_like(p).at[
            jnp.arange(ROW_BLOCK)[:, None], top_e].set(top_p)

        def one(e, y):
            gate, up, down = (_f32(moe[n]["kernel"][i, e])
                              for n in ("gate", "up", "down"))
            return y + weights[:, e, None] * mm(
                jax.nn.silu(mm(hb, gate)) * mm(hb, up), down)
        yb = jax.lax.fori_loop(0, n_experts, one, jnp.zeros_like(xb))
        return jax.lax.dynamic_update_slice_in_dim(out, yb, b * ROW_BLOCK, 0)
    return x + jax.lax.fori_loop(0, n_blocks, experts, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("eps", "float8"))
def _head(x, final_scale, head, *, eps, float8):
    return _matmul(float8)(_rms_norm(x, _f32(final_scale), eps), _f32(head))


def faults(wrong: str | None) -> frozenset:
    """The faults ``wrong`` names (``a+b``: both)."""
    found = frozenset(wrong.split("+")) if wrong else frozenset()
    if not found <= set(WRONG):
        raise ValueError(f"wrong must be of {WRONG}, joined by + "
                         f"(got {wrong!r})")
    return found


def layer_kinds(config: dict, wrong: str | None = None) -> list:
    """Every layer's (window, inverse frequencies, rope scale), with
    ``wrong``'s faults where they touch them."""
    wrong = faults(wrong)
    dim = config["head_dim"]
    full = rope_of(config["rope_parameters"]["full_attention"], dim)
    plain = rope_of(config["rope_parameters"]["sliding_attention"], dim)
    if "no_attention_factor" in wrong:
        full = (full[0], 1.0)
    window = (config["sliding_window"] - ("window_minus_1" in wrong)
              + ("window_plus_1" in wrong))
    types = list(config["layer_types"])
    if "full_first" in wrong:
        # every run of window layers that ends in a full one turned round
        period = types.index("full_attention") + 1
        types = [types[j - j % period + (j % period + period - 1) % period]
                 for j in range(len(types))]
    kinds = []
    for t in types:
        sliding = t == "sliding_attention" and "all_full" not in wrong
        rope = plain if t == "sliding_attention" and \
            "yarn_everywhere" not in wrong else full
        kinds.append((window if sliding else 0, *rope))
    return kinds


def hidden(params, tokens, config: dict, wrong: str | None = None,
           round_to: int = 0):
    """Final hidden states [S', H] (before the last norm) of ONE sequence of
    token ids, float32, S' the padded length (causal attention: a position
    sees nothing behind it, so the padding changes no live row)."""
    wrongs = faults(wrong)
    tokens = list(tokens)
    n_blocks = -(-len(tokens) // ROW_BLOCK)
    pad_to = max(round_to, ROW_BLOCK)
    pad_to = -(-pad_to // ROW_BLOCK) * ROW_BLOCK
    tokens += [0] * (-len(tokens) % pad_to)
    x = _f32(params["embed"]["embedding"][jnp.asarray(tokens, jnp.int32)])
    for i, (window, inv_freq, scale) in enumerate(layer_kinds(config, wrong)):
        x = _layer(x, params["blocks"], jnp.int32(i), jnp.int32(n_blocks),
                   n_q=config["num_attention_heads"],
                   n_kv=config["num_key_value_heads"],
                   eps=float(config["rms_norm_eps"]), window=window,
                   inv_freq=inv_freq, rope_scale=scale,
                   top_k=config["num_experts_per_tok"],
                   renormalise=bool(config.get("norm_topk_prob", False))
                   and "no_renorm" not in wrongs, float8="float8" in wrongs,
                   all_blocks=not round_to)
    return x


def logits(params, tokens, config: dict, positions=None,
           wrong: str | None = None, round_to: int = 0):
    """Logits [len(positions) or S, V] of one sequence. ``round_to`` pads the
    sequence to a multiple, so that a few lengths compile."""
    n = len(tokens)
    x = hidden(params, tokens, config, wrong, round_to)
    positions = jnp.asarray(range(n) if positions is None else positions,
                            jnp.int32)
    return _head(x[positions], params["final_norm"]["scale"],
                 params["lm_head"]["kernel"],
                 eps=float(config["rms_norm_eps"]),
                 float8="float8" in faults(wrong))
