"""The plain reference of a decoder that generates by DIFFUSION OVER BLOCKS,
as SDAR's published ``modeling_sdar_moe.py`` and ``block_diffusion_generate``
describe it (JetLM/SDAR-30B-A3B-Chat, ``model_type: sdar_moe``; the layer is
Qwen3-MoE's, the mask and the generation loop are SDAR's).

Every layer alike, one sequence x [S, H] at a time, ``Bd`` the block length:

    h  = n1(x);  q = rope(RMSNorm_head(Wq h)),  k = rope(RMSNorm_head(Wk h))
    a_ij = softmax_j(q_i . k_j / sqrt(D) + M_ij),
           M_ij = 0 iff floor(j / Bd) <= floor(i / Bd), else -inf
    x  = x + O(a v)
    p  = softmax_fp32(Wr n2(x)) over all E experts;  S = top-k(p);
         w = p_S / sum(p_S)   (``norm_topk_prob``)
    x  = x + sum_{e in S} w_e * down_e( silu(gate_e n2(x)) * up_e n2(x) )

``RMSNorm_head`` norms EACH head's D values under one learned [D] scale
(configuration key ``qk_norm: "head"``). Row i's logits predict the token AT
position i (a masked row holds the mask token's embedding): no shift by one.

Float32 ``jax.numpy`` with full-precision matrix multiplications, no cache,
no kernels, no sorting, no batching: EVERY expert is applied to every
position and masked by the routing weights, one expert's weights cast to
float32 at a time (a loop over the experts inside one jitted call a layer,
so that it fits beside a loaded engine and runs as a few programs, not
operation by operation). Independent of ``models/`` and ``serve/``; it reads
only that program's parameter tree:

    embed.embedding [V,H]; blocks.{q,k,v,o}.kernel [L,in,out];
    blocks.{q_norm,k_norm}.scale [L,D]; blocks.moe.router.kernel [L,H,E];
    blocks.moe.{gate,up}.kernel [L,E,H,F]; blocks.moe.down.kernel [L,E,F,H];
    blocks.{attn_norm,mlp_norm}.scale [L,H]; final_norm.scale [H];
    lm_head.kernel [H,V]

Departures from the published description, each the program's own or forced:

- a norm's weight is stored as ``scale`` with the weight being ``1 + scale``;
- the published loop was written from memory (no network here): block length,
  steps, mask token, strategy and threshold are the configuration file's
  ``assumed`` keys;
- the published ``topk`` over the confidences may pick a row that is no
  longer masked when fewer rows are masked than the schedule asks for (a
  first window whose leading rows are the prompt's) and overwrite it; here
  only masked rows are ever fixed, and a tie goes to the row further left;
- a row's confidence is softmax(logits)[token] of the logits as they came,
  also for a sampled token (the published sampler reads it after its
  temperature and filters); ``generate`` here is greedy;
- the mask token is never drawn: its logit is -inf before the argmax and
  the softmax (a row fixed TO the mask token would read as masked for ever;
  trained weights never put it first, seeded random ones do, once in a
  vocabulary's worth of rows);
- ``generate`` forwards the WHOLE canvas at every step (masks after the
  block too: under M no row sees a later block, so they change nothing), so
  that every step runs the same compiled programs.

What the check's wrong variants switch (``logits`` keywords): ``mask_block``
(1 = the CAUSAL mask), ``config["qk_norm"] = "none"``, ``operand_bits``
(matmul operands rounded to float8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _round(x, bits):
    """Round to a float of ``bits`` = (exponent, mantissa) bits; None: as
    it is. (``reduce_precision``: an ``astype`` pair is elided on the chip.)"""
    return x if bits is None else jax.lax.reduce_precision(x, *bits)


def _mm(a, b, bits=None):
    return jnp.matmul(_round(a, bits), _round(b, bits), precision=_HIGHEST)


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


@functools.partial(jax.jit, static_argnames=(
    "n_q", "n_kv", "eps", "theta", "qk_norm", "block", "bits"))
def _attention(x, w, *, n_q, n_kv, eps, theta, qk_norm, block, bits):
    """x + O(attn(...)) on one sequence x [S, H] under the block mask."""
    w = jax.tree_util.tree_map(_f32, w)
    s = x.shape[0]
    h = _rms_norm(x, w["attn_norm"], eps)
    q = _mm(h, w["q"], bits).reshape(s, n_q, -1)
    k = _mm(h, w["k"], bits).reshape(s, n_kv, -1)
    if qk_norm == "head":
        q = _rms_norm(q, w["q_norm"], eps)
        k = _rms_norm(k, w["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    v = _mm(h, w["v"], bits).reshape(s, n_kv, -1)
    d = q.shape[-1]
    k, v = (jnp.repeat(a, n_q // n_kv, axis=1) for a in (k, v))
    scores = jnp.einsum("qnd,knd->nqk", _round(q, bits), _round(k, bits),
                        precision=_HIGHEST) / d ** 0.5
    pos = jnp.arange(s) // block
    scores = jnp.where((pos[None, :] <= pos[:, None])[None], scores,
                       -jnp.inf)
    att = jnp.einsum("nqk,knd->qnd",
                     _round(jax.nn.softmax(scores, -1), bits),
                     _round(v, bits), precision=_HIGHEST)
    return x + _mm(att.reshape(s, n_q * d), w["o"], bits)


@functools.partial(jax.jit, static_argnames=("top_k", "eps", "renormalise",
                                             "bits"))
def _experts(x, mlp_norm, moe, i, *, top_k, eps, renormalise, bits):
    """x + the expert layer i on one sequence: the router in float32, then
    EVERY expert on every position, weighted by its column of the routing
    weights (zero where the position did not choose it). The stacks come
    in whole; the loop casts one expert's slices at a time."""
    h = _rms_norm(x, _f32(mlp_norm[i]), eps)
    p = jax.nn.softmax(_mm(h, _f32(moe["router"]["kernel"][i])), -1)
    top_p, top_e = jax.lax.top_k(p, top_k)
    if renormalise:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    weights = jnp.zeros_like(p).at[rows, top_e].set(top_p)

    def one(e, acc):
        gate, up, down = (_f32(moe[n]["kernel"][i, e])
                          for n in ("gate", "up", "down"))
        y = _mm(jax.nn.silu(_mm(h, gate, bits)) * _mm(h, up, bits), down,
                bits)
        return acc + jax.lax.dynamic_slice_in_dim(weights, e, 1, 1) * y
    return jax.lax.fori_loop(0, p.shape[-1], one, x)


@functools.partial(jax.jit, static_argnames=("eps", "bits"))
def _head(x, final_scale, head, *, eps, bits):
    return _mm(_rms_norm(x, _f32(final_scale), eps), _f32(head), bits)


def hidden(params, tokens, config: dict, mask_block=None, operand_bits=None):
    """Final hidden states [S, H] (before the last norm) of ONE sequence of
    token ids, float32, under the block mask of ``mask_block`` positions
    (default: the configuration's ``block_length``; 1 is the causal mask)."""
    b, moe = params["blocks"], params["blocks"]["moe"]
    qk_norm = config.get("qk_norm", "none")
    eps = float(config["rms_norm_eps"])
    block = int(mask_block or config["block_length"])
    x = _f32(params["embed"]["embedding"][jnp.asarray(tokens, jnp.int32)])
    for i in range(config["num_hidden_layers"]):
        w = {"attn_norm": b["attn_norm"]["scale"][i],
             "q": b["q"]["kernel"][i], "k": b["k"]["kernel"][i],
             "v": b["v"]["kernel"][i], "o": b["o"]["kernel"][i]}
        if qk_norm == "head":
            w["q_norm"] = b["q_norm"]["scale"][i]
            w["k_norm"] = b["k_norm"]["scale"][i]
        x = _attention(x, w, n_q=config["num_attention_heads"],
                       n_kv=config["num_key_value_heads"], eps=eps,
                       theta=float(config["rope_theta"]), qk_norm=qk_norm,
                       block=block, bits=operand_bits)
        x = _experts(x, b["mlp_norm"]["scale"], moe, i,
                     top_k=config["num_experts_per_tok"], eps=eps,
                     renormalise=bool(config.get("norm_topk_prob", False)),
                     bits=operand_bits)
    return x


def logits(params, tokens, config: dict, positions=None, mask_block=None,
           operand_bits=None):
    """Logits [len(positions) or S, V] of one sequence: the full forward
    under the dense block mask. Row i's logits are for the token AT i."""
    x = hidden(params, tokens, config, mask_block, operand_bits)
    if positions is not None:
        x = x[jnp.asarray(positions, jnp.int32)]
    return _head(x, params["final_norm"]["scale"],
                 params["lm_head"]["kernel"],
                 eps=float(config["rms_norm_eps"]), bits=operand_bits)


def transfer_schedule(config: dict) -> list:
    """Rows fixed at denoise step 0, 1, ...: ``block_length`` split evenly
    over ``denoising_steps``, the remainder on the first steps."""
    base, rem = divmod(config["block_length"], config["denoising_steps"])
    return [base + (s < rem) for s in range(config["denoising_steps"])]


def transfer(conf: np.ndarray, masked: np.ndarray, wanted: int,
             strategy: str, threshold: float) -> np.ndarray:
    """The rows of one window a denoise step fixes (bool [Bd])."""
    idx = np.flatnonzero(masked)
    n = min(wanted, len(idx))
    if strategy == "sequential":
        chosen = idx[:n]
    else:
        # largest confidence first, a tie to the row further left
        order = sorted(idx, key=lambda i: (-conf[i], i))
        chosen = order[:n]
        if strategy == "low_confidence_dynamic":
            high = [i for i in idx if conf[i] > threshold]
            if len(high) >= n:
                chosen = high
    fix = np.zeros(len(masked), bool)
    fix[list(chosen)] = True
    return fix


def generate(params, prompt, config: dict, max_tokens: int,
             stop_token_ids=(), **forward) -> tuple[list, list]:
    """The published generation loop, greedy, with a FULL forward at every
    denoise step: (the reply's tokens, the denoise step at which each was
    fixed). The canvas is the prompt followed by mask tokens up to a whole
    number of blocks; the prompt's whole blocks need no step; then block by
    block, until no mask is left in it, forward, take each masked row's
    argmax and its probability, and fix rows by the transfer rule. (The
    published loop forwards the finished block once more to store its K/V:
    with no cache there is nothing to store.) A stop token ends the reply
    at the end of its block; the reply is cut at ``max_tokens``."""
    Bd, mask_id = config["block_length"], config["mask_token_id"]
    n = len(prompt)
    total = -(-(n + max_tokens) // Bd) * Bd
    canvas = np.full(total, mask_id, np.int64)
    canvas[:n] = prompt
    fixed_at = np.full(total, -1, np.int64)
    # (kept beside the canvas: a prompt may hold the mask token's id)
    masked = np.arange(total) >= n
    schedule = transfer_schedule(config)
    for start in range(n // Bd * Bd, total, Bd):
        rows = slice(start, start + Bd)
        step = 0
        while masked[rows].any():
            lg = np.asarray(logits(params, canvas, config,
                                   positions=range(start, start + Bd),
                                   **forward), np.float64)
            lg[:, mask_id] = -np.inf        # the mask token is never drawn
            x0 = lg.argmax(-1)
            lse = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) \
                + lg.max(-1)
            conf = np.exp(lg[np.arange(Bd), x0] - lse)
            fix = transfer(conf, masked[rows],
                           schedule[min(step, len(schedule) - 1)],
                           config["remasking_strategy"],
                           config["confidence_threshold"])
            canvas[rows][fix] = x0[fix]
            fixed_at[rows][fix] = step
            masked[rows][fix] = False
            step += 1
        if any(t in stop_token_ids for t in canvas[n:start + Bd]):
            break
    out, steps = canvas[n:n + max_tokens], fixed_at[n:n + max_tokens]
    left = masked[n:n + max_tokens]
    keep = int(np.argmax(left)) if left.any() else len(out)
    for i, t in enumerate(out[:keep]):
        if t in stop_token_ids:
            keep = i + 1
            break
    return out[:keep].tolist(), steps[:keep].tolist()
