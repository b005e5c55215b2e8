"""Decoder-only GPT/Llama model: init + forward over a stacked-layer pytree.

TPU-first design choices (vs. the reference's per-module torch graph):

- **Stacked layer params.** All transformer blocks live in one pytree whose
  leaves carry a leading ``num_layers`` axis, consumed with ``jax.lax.scan``.
  One trace/compile of the block regardless of depth, and the leading axis
  is exactly what pipeline parallelism shards into stages
  (parallel/pipeline.py) — no per-layer Python objects to re-partition.
- **Explicit PRNG, pure functions.** `init(cfg, key)` -> params;
  `forward(params, tokens, cfg, ...)` -> logits. Determinism is structural
  (SURVEY §5.2: the reference plumbs a seed it never applies).
- **bf16 compute / fp32 master.** Params are created fp32; `forward` casts
  to ``cfg.dtype`` for compute; logits and softmax statistics stay fp32.

Capability parity: replaces HF AutoModelForCausalLM usage at reference
engine.py:119-140 and server.py:146-170 for the architectures the reference
configures (configs/models/llama-7b.json, init.py MODEL_TEMPLATES).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..config.schema import ModelConfig
from .layers import (
    attend_dense_cache,
    attend_fresh,
    decoder_block,
    layer_kinds,
    model_rope_frequencies,
    rms_norm,
    rope_scale,
    scaled,
)

Params = Any


# ---------------------------------------------------------------------------
# Initialisation
# ---------------------------------------------------------------------------

def init(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    """Create the parameter pytree. Truncated-normal(0.02) init, output
    projections scaled 1/sqrt(2L) (GPT-2 style residual scaling)."""
    H, D = cfg.hidden_size, cfg.head_dim
    Nq, Nkv, F, V, L = (cfg.num_heads, cfg.num_kv_heads, cfg.ffn_size,
                        cfg.vocab_size, cfg.num_layers)
    std = 0.02
    resid_std = std / jnp.sqrt(2.0 * L)

    keys = iter(jax.random.split(key, 32))

    def norm_init(*shape):
        return jnp.zeros(shape, dtype)  # scale stored as (1 + s)

    def dense(key_, *shape, scale=std):
        return (jax.random.truncated_normal(key_, -3, 3, shape, jnp.float32)
                * scale).astype(dtype)

    mup_std = _mup_init_std(cfg)

    def around(blocks):
        params = {
            "embed": {"embedding": dense(next(keys), V, H,
                                         scale=mup_std.get("embed", std))},
            "blocks": blocks,
            "final_norm": {"scale": norm_init(H)},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"kernel": dense(
                next(keys), H, V, scale=mup_std.get("lm_head", std))}
        if cfg.is_looped:
            # the exit gate on each pass's normed state: one output, a bias
            params["exit_gate"] = {"kernel": dense(next(keys), H, 1),
                                   "bias": jnp.zeros((1,), dtype)}
        return params

    if cfg.layer_pattern:
        params = around(_init_table_blocks(cfg, keys, norm_init, dense,
                                           resid_std, dtype))
        if cfg.mtp_layers:
            # the next-token prediction module: its decoder layer is the
            # last entry of the ``attn`` and ``moe`` stacks; here what it
            # has beside: the two norms, the [embedding | stream] -> H
            # projection and its own final norm (embedding and head are the
            # main model's)
            params["mtp"] = {
                "enorm": {"scale": norm_init(H)},
                "hnorm": {"scale": norm_init(H)},
                "eh_proj": {"kernel": dense(next(keys), 2 * H, H)},
                "final_norm": {"scale": norm_init(H)},
            }
        return params

    blocks = {
        "attn_norm": {"scale": norm_init(L, H)},
        "q": {"kernel": dense(next(keys), L, H, Nq * D)},
        "k": {"kernel": dense(next(keys), L, H, Nkv * D)},
        "v": {"kernel": dense(next(keys), L, H, Nkv * D)},
        "o": {"kernel": dense(next(keys), L, Nq * D, H, scale=resid_std)},
        "mlp_norm": {"scale": norm_init(L, H)},
    }
    if cfg.sandwich_norm:
        blocks["attn_out_norm"] = {"scale": norm_init(L, H)}
        blocks["mlp_out_norm"] = {"scale": norm_init(L, H)}
    if cfg.qk_norm == "projection":
        blocks["q_norm"] = {"scale": norm_init(L, Nq * D)}
        blocks["k_norm"] = {"scale": norm_init(L, Nkv * D)}
    elif cfg.qk_norm == "head":
        blocks["q_norm"] = {"scale": norm_init(L, D)}
        blocks["k_norm"] = {"scale": norm_init(L, D)}
    if cfg.attention_bias:
        blocks["q"]["bias"] = jnp.zeros((L, Nq * D), dtype)
        blocks["k"]["bias"] = jnp.zeros((L, Nkv * D), dtype)
        blocks["v"]["bias"] = jnp.zeros((L, Nkv * D), dtype)
    if cfg.is_moe:
        E = cfg.moe.num_experts
        blocks["moe"] = {
            "router": {"kernel": dense(next(keys), L, H, E)},
            "gate": {"kernel": dense(next(keys), L, E, H, F)},
            "up": {"kernel": dense(next(keys), L, E, H, F)},
            "down": {"kernel": dense(next(keys), L, E, F, H, scale=resid_std)},
        }
    else:
        blocks["mlp"] = {
            "gate": {"kernel": dense(next(keys), L, H, F)},
            "up": {"kernel": dense(next(keys), L, H, F)},
            "down": {"kernel": dense(next(keys), L, F, H, scale=resid_std)},
        }

    return around(blocks)


def _mup_init_std(cfg: ModelConfig) -> dict:
    """{kernel: std of its seeded init} for a model WITH muP multipliers
    (``cfg.mup``; {} for any other, which keeps 0.02). The published
    multipliers are trained with: under the plain 0.02 an attention branch
    times 0.0375 and a state-space branch times 0.088 vanish beside an
    embedding times 5.66, and a comparison of logits is blind to both. So a
    kernel's std is ``target / sqrt(fan_in) / its multipliers``, ``target``
    being what the projection puts out for an input of unit RMS AFTER its
    multipliers: every branch then adds about half the stream's RMS to the
    residual whatever the widths and whatever the multipliers' values, which
    keep their meaning (set one to 1 and its branch's scale moves by it).
    ``o`` and ``down`` reckon with inputs under unit RMS (a softmax average
    of values; silu(gate) x up at ~0.6). ``in_proj``: the x part; z, B, C
    and dt follow by ``mup.ssm``'s ratios."""
    from ..config.schema import MupConfig
    m = cfg.mup
    if m == MupConfig():
        return {}
    H, F = cfg.hidden_size, cfg.dense_ffn_size or cfg.ffn_size
    # kernel: (target RMS, fan-in, the multipliers on its way out)
    table = {"embed": (1.0, 1, m.embedding),
             "q": (2.0, H, m.attention_in),
             "k": (1.0, H, m.attention_in * m.key),
             "v": (1.0, H, m.attention_in),
             "o": (1.0, cfg.num_heads * cfg.head_dim, m.attention_out),
             "in_proj": (1.0, H, m.ssm_in * m.ssm[1]),
             "out_proj": (0.5, cfg.ssm.inner_size, m.ssm_out),
             "gate": (1.0, H, m.mlp[0]), "up": (1.0, H, 1.0),
             "down": (0.5, F, 0.6 * m.mlp[1]),
             "lm_head": (2.0, H, m.lm_head)}
    return {name: target / (fan_in ** 0.5 * multiplier)
            for name, (target, fan_in, multiplier) in table.items()}


def _init_table_blocks(cfg: ModelConfig, keys, norm_init, dense, resid_std,
                       dtype) -> Params:
    """The blocks of a layer table: one stack a KIND (``ssm``, ``attn``,
    ``moe``), each leaf with a leading axis over the layers of that kind in
    table order. The state-space parameters start as Mamba-2's own init:
    step sizes log-uniform in [1e-3, 1e-1] (``dt_bias`` their inverse
    softplus), ``A`` uniform in [1, 16], ``D`` one, the conv uniform in
    +-1/sqrt(K)."""
    H, D, F = cfg.hidden_size, cfg.head_dim, cfg.ffn_size
    Nq, Nkv = cfg.num_heads, cfg.num_kv_heads
    blocks = {}
    mup_std, plain = _mup_init_std(cfg), dense

    def dense(key_, *shape, scale=None, name=None):
        # (``name``: a kernel that a model with muP multipliers seeds at
        # its own std)
        scale = mup_std.get(name, scale)
        return plain(key_, *shape, **({} if scale is None
                                      else {"scale": scale}))
    Lp = cfg.layers_of("P")
    Lm, La, Le = cfg.ssm_layers - Lp, cfg.kv_layers - Lp, cfg.moe_layers
    Ld = cfg.layers_of("D")

    def ssm_stack(Lm):
        s = cfg.ssm
        nh, d_in, C, K = s.num_heads, s.inner_size, s.conv_channels, \
            s.conv_kernel
        step = jnp.exp(jax.random.uniform(
            next(keys), (Lm, nh), jnp.float32,
            jnp.log(1e-3), jnp.log(1e-1)))
        bound = 1.0 / jnp.sqrt(float(K))
        return {
            "norm": {"scale": norm_init(Lm, H)},
            "in_proj": {"kernel": dense(next(keys), Lm, H,
                                        d_in + C + nh, name="in_proj")},
            "conv": {"kernel": jax.random.uniform(
                         next(keys), (Lm, K, C), jnp.float32, -bound,
                         bound).astype(dtype),
                     "bias": jax.random.uniform(
                         next(keys), (Lm, C), jnp.float32, -bound,
                         bound).astype(dtype)},
            # small vectors the recurrence exponentiates: kept float32
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jax.random.uniform(
                next(keys), (Lm, nh), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((Lm, nh), jnp.float32),
            "gate_norm": {"scale": norm_init(Lm, d_in)},
            "out_proj": {"kernel": dense(next(keys), Lm, d_in, H,
                                         scale=resid_std, name="out_proj")},
        }

    def attention_stack(La):
        stack = {
            "norm": {"scale": norm_init(La, H)},
            "q": {"kernel": dense(next(keys), La, H, Nq * D, name="q")},
            "k": {"kernel": dense(next(keys), La, H, Nkv * D, name="k")},
            "v": {"kernel": dense(next(keys), La, H, Nkv * D, name="v")},
            "o": {"kernel": dense(next(keys), La, Nq * D, H,
                                  scale=resid_std, name="o")},
        }
        if cfg.qk_norm == "head":
            stack["q_norm"] = {"scale": norm_init(La, D)}
            stack["k_norm"] = {"scale": norm_init(La, D)}
        return stack
    if cfg.conv_layers:
        # the gated short convolution: [B | C | u] in, one K-tap filter a
        # channel (uniform in +-1/sqrt(K), as the state-space conv), out
        Lc, K = cfg.conv_layers, cfg.shortconv_kernel
        bound = 1.0 / jnp.sqrt(float(K))
        blocks["conv"] = {
            "norm": {"scale": norm_init(Lc, H)},
            "in_proj": {"kernel": dense(next(keys), Lc, H, 3 * H)},
            "conv": {"kernel": jax.random.uniform(
                next(keys), (Lc, K, H), jnp.float32, -bound,
                bound).astype(dtype)},
            "out_proj": {"kernel": dense(next(keys), Lc, H, H,
                                         scale=resid_std)},
        }
    if Lm:
        blocks["ssm"] = ssm_stack(Lm)
    if Lp:
        # ONE stack, ONE norm: the attention's kernels beside the mixer's
        blocks["par"] = {**attention_stack(Lp), **ssm_stack(Lp)}
    if cfg.kda_layers:
        blocks["kda"] = _init_kda_blocks(cfg, next(keys), norm_init, dense,
                                         resid_std, dtype)
    if La and cfg.is_latent:
        a = cfg.mla
        # through a bottleneck and its norm, or (``q_lora_rank`` 0) direct
        query = ({"q_a": {"kernel": dense(next(keys), La, H, a.q_lora_rank)},
                  "q_a_norm": {"scale": norm_init(La, a.q_lora_rank)},
                  "q_b": {"kernel": dense(next(keys), La, a.q_lora_rank,
                                          Nq * D)}}
                 if a.q_lora_rank else
                 {"q": {"kernel": dense(next(keys), La, H, Nq * D)}})
        blocks["attn"] = {
            "norm": {"scale": norm_init(La, H)},
            **query,
            "kv_a": {"kernel": dense(next(keys), La, H, a.latent_size)},
            "kv_norm": {"scale": norm_init(La, a.kv_lora_rank)},
            # a head's [k_nope | v] side by side, as the published kv_b_proj
            "kv_b": {"kernel": dense(
                next(keys), La, a.kv_lora_rank,
                Nq * (a.qk_nope_head_dim + a.v_head_dim))},
            "o": {"kernel": dense(next(keys), La, Nq * a.v_head_dim, H,
                                  scale=resid_std)},
        }
    elif La:
        blocks["attn"] = attention_stack(La)
        if cfg.attention_gate:
            blocks["attn"]["gate"] = {
                "kernel": dense(next(keys), La, H, Nq * D)}
    if Le:
        m = cfg.moe
        E, Fs = m.num_experts, m.shared_expert_size
        names = ("gate", "up") if cfg.mlp_gated else ("up",)
        moe = {"norm": {"scale": norm_init(Le, H)},
               "router": {"kernel": dense(next(keys), Le, H, m.router_width)}}
        if m.selection_bias:
            moe["router"]["bias"] = jnp.zeros((Le, m.router_width),
                                              jnp.float32)
        # a width that is no multiple of the chip's 128 lanes is stored
        # (out, in) = [F, H]: ops/moe_gmm.py says why. (moe_block reads the
        # order off the stack's shape, which F == H would not tell.)
        up_shape = (F, H) if F % 128 and F != H else (H, F)
        for n in names:
            moe[n] = {"kernel": dense(next(keys), Le, E, *up_shape)}
        moe["down"] = {"kernel": dense(next(keys), Le, E, F, H,
                                       scale=resid_std)}
        if Fs:
            moe["shared"] = {n: {"kernel": dense(next(keys), Le, H, Fs)}
                             for n in names}
            moe["shared"]["down"] = {"kernel": dense(next(keys), Le, Fs, H,
                                                     scale=resid_std)}
        blocks["moe"] = moe
    if Ld:
        Fd = cfg.dense_ffn_size or F
        names = ("gate", "up") if cfg.mlp_gated else ("up",)
        blocks["mlp"] = {"norm": {"scale": norm_init(Ld, H)}, **{
            n: {"kernel": dense(next(keys), Ld, H, Fd, name=n)}
            for n in names}}
        blocks["mlp"]["down"] = {"kernel": dense(
            next(keys), Ld, Fd, H, scale=resid_std, name="down")}
    if cfg.hc_mult > 1:
        # a hyper-connection a sub-layer, float32. The maps start visibly
        # NOT the identity: phi's product has a spread of ~0.6 under a = 0.25
        # (x_hat has unit entries, phi 0.02 over n*H of them), H_pre ~ 1/2 a
        # stream, H_post ~ 1, H_res doubly stochastic with a diagonal
        # preferred e : 1
        n, nH = cfg.hc_mult, cfg.hc_mult * H
        for stack in blocks.values():
            L = stack["norm"]["scale"].shape[0]
            stack["hc"] = {
                "norm": {"scale": jnp.zeros((L, nH), jnp.float32)},
                "phi": {"kernel": dense(next(keys), L, nH, 2 * n + n * n
                                        ).astype(jnp.float32)},
                "a": jnp.full((L, 3), 0.25, jnp.float32),
                "b_pre": jnp.zeros((L, n), jnp.float32),
                "b_post": jnp.zeros((L, n), jnp.float32),
                "b_res": jnp.broadcast_to(jnp.eye(n, dtype=jnp.float32),
                                          (L, n, n)),
            }
    return blocks


def _init_kda_blocks(cfg: ModelConfig, key, norm_init, dense, resid_std,
                     dtype) -> Params:
    """The ``K`` stack (Kimi Delta Attention), from ONE key. The decay's
    vectors start as the hybrid configuration seeds its like: step sizes
    log-uniform in [1e-3, 1e-1] (``dt_bias`` their inverse softplus, a
    channel), ``A`` uniform in [1, 16] a head, the conv uniform in
    +-1/sqrt(K)."""
    kd, H = cfg.kda, cfg.hidden_size
    Lk, nh, d_in, K = cfg.kda_layers, kd.num_heads, kd.inner_size, \
        kd.conv_kernel
    ks = iter(jax.random.split(key, 8))
    step = jnp.exp(jax.random.uniform(
        next(ks), (Lk, d_in), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    bound = 1.0 / jnp.sqrt(float(K))
    return {
        "norm": {"scale": norm_init(Lk, H)},
        # [q | k | v | decay low-rank | gate low-rank | beta]
        "in_proj": {"kernel": dense(next(ks), Lk, H, kd.in_proj_size)},
        "conv": {"kernel": jax.random.uniform(
            next(ks), (Lk, K, kd.conv_channels), jnp.float32, -bound,
            bound).astype(dtype)},
        "f_b": {"kernel": dense(next(ks), Lk, kd.head_dim, d_in)},
        "g_b": {"kernel": dense(next(ks), Lk, kd.head_dim, d_in)},
        # small vectors the recurrence exponentiates: kept float32
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(
            next(ks), (Lk, nh), jnp.float32, 1.0, 16.0)),
        "gate_norm": {"scale": norm_init(Lk, kd.head_dim)},
        "out_proj": {"kernel": dense(next(ks), Lk, d_in, H,
                                     scale=resid_std)},
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

# the expert kernels of blocks["moe"]: the dropless block takes them as
# the whole [L, E, in, out] stacks with the layer's index
_EXPERT_KERNELS = ("gate", "up", "down")


def split_expert_stacks(blocks: Params):
    """(blocks without the experts' kernels, those kernels' stacks) when
    they are plain arrays; (blocks, None) for a dense model or quantized
    experts, which ride the layer scan and dequantize a layer at a time.
    A scan over the stacked blocks hands each layer its slice, and a slice
    that feeds a kernel (a custom call) is COPIED out: 805 MB a layer at
    OLMoE's widths. The stacks stay outside the scan instead and the
    kernel addresses ``stack[layer, expert]`` (ops/moe_gmm.py)."""
    moe = blocks.get("moe")
    names = [n for n in _EXPERT_KERNELS if moe is not None and n in moe]
    if moe is None or not all(
            isinstance(moe[n]["kernel"], jax.Array) for n in names):
        return blocks, None
    scanned = dict(blocks, moe={k: v for k, v in moe.items()
                                if k not in names})
    return scanned, {n: moe[n] for n in names}


def table_layers(cfg: ModelConfig) -> list[tuple[str, int]]:
    """The layer table as (kind, index among the layers of that kind) in
    layer order: ``MEM*`` -> [("M", 0), ("E", 0), ("M", 1), ("*", 0)]. The
    index addresses the kind's parameter stack, and for ``*`` the K/V
    pools, for ``M`` the state pools, for ``P`` both, for ``E`` the expert
    stacks."""
    seen: dict = {}
    out = []
    for kind in cfg.layer_pattern:
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = out[-1][1] + 1
    return out


def table_period(cfg: ModelConfig) -> tuple[list, list, int]:
    """The layer table as (head, unit, repetitions): the shortest ``head``
    after which the kinds repeat ``unit`` at least twice to the table's
    end; ``*D*E*E*E`` -> ([("*", 0), ("D", 0)], [("*", 1), ("E", 0)], 3).
    Repetition r's layer of a kind is ``unit``'s index + r x that kind's
    count in ``unit``. A table that ends in no period is all head."""
    layers, kinds = table_layers(cfg), cfg.layer_pattern
    for h in range(len(kinds)):
        rest = kinds[h:]
        for p in range(1, len(rest) // 2 + 1):
            if rest == rest[:p] * (len(rest) // p):
                return layers[:h], layers[h:h + p], len(rest) // p
    return layers, [], 0


def table_period_and_tail(cfg: ModelConfig) -> tuple[list, list, int, list]:
    """``table_period`` for a table that need not END in its period: (head,
    unit, repetitions, tail), the tail being the layers behind the last
    whole repetition: the split that puts most layers under the loop
    (``lfm2_moe``'s 48 entries: the head ``CDCD``, ``*ECECECE`` x 4, and a
    tail ``*ECECE*ECECE`` whose last attention layer comes a layer early,
    where ``table_period`` finds that tail's ``*ECECE`` x 2 alone), taken
    where it covers more than ``table_period``'s own split AND at least two
    thirds of the table (a loop over less is not worth a second walk of its
    layers: the short test tables stay all head); else ``table_period``'s
    split and no tail."""
    head, unit, reps = table_period(cfg)
    layers, kinds = table_layers(cfg), cfg.layer_pattern
    best = (len(unit) * reps, 0, 0, 0)      # covered, -head, -period, reps
    for h in range(len(kinds)):
        for p in range(1, (len(kinds) - h) // 2 + 1):
            r = 1
            while kinds[h + r * p:h + (r + 1) * p] == kinds[h:h + p]:
                r += 1
            if r >= 2 and r * p > best[0]:
                best = (r * p, -h, -p, r)
    covered, h, p, r = best[0], -best[1], -best[2], best[3]
    if not r or 3 * covered < 2 * len(kinds):
        return head, unit, reps, []
    return layers[:h], layers[h:h + p], r, layers[h + covered:]


def table_layer(blocks: Params, kind: str, index) -> Params:
    """One layer of ``kind``'s stack (a static index where the table is
    walked by a Python loop, a traced one inside ``table_period``'s loop).
    An expert layer keeps its routed experts' kernels as the WHOLE stacks,
    which ``moe_block`` addresses by ``index``."""
    from ..config.schema import LAYER_KINDS
    stack = blocks[LAYER_KINDS[kind]]
    whole = {}
    if kind == "E":
        stack, whole = split_expert_stacks({"moe": stack})
        stack, whole = stack["moe"], whole or {}
    layer = jax.tree_util.tree_map(lambda a: a[index], stack)
    return dict(layer, **whole)


def layer_experts(layer: Params, expert_stacks, layer_index):
    """(one layer of the scan as ``decoder_block`` takes it, the
    ``layer_index`` its ``moe_block`` needs): the layer's router beside the
    whole expert stacks when ``split_expert_stacks`` kept them out of the
    scan."""
    if expert_stacks is None:
        return layer, None
    return dict(layer, moe=dict(layer["moe"], **expert_stacks)), layer_index


# the float32 vectors of a state-space layer (exponentiated every token)
# and the router's selection bias: never rounded to the compute dtype
_KEPT_FLOAT32 = (("ssm", "dt_bias"), ("ssm", "A_log"), ("ssm", "D"),
                 ("par", "dt_bias"), ("par", "A_log"), ("par", "D"),
                 ("kda", "dt_bias"), ("kda", "A_log"),
                 ("moe", "router", "bias"))


def cast_table_blocks(blocks: Params, dtype) -> Params:
    """``precast_params`` for the blocks of a layer table: every plain leaf
    to the compute dtype but the state-space layers' ``dt_bias`` / ``A_log``
    / ``D``, the router's selection ``bias`` and a hyper-connection's
    parameters (``hc``: its maps are float32), which stay as stored."""
    def one(path, x):
        names = tuple(k.key for k in path)
        kept = names in _KEPT_FLOAT32 or "hc" in names
        return x if kept else x.astype(dtype)
    return jax.tree_util.tree_map_with_path(one, blocks)


def _block_fn(cfg: ModelConfig, attn_impl: str, norm_impl: str,
              x, layer, positions, segment_ids, inv_freq,
              kv_cache=None, cache_offset=None, *, moe_impl: str = "dropless",
              expert_stacks=None, layer_index=None, kind=None, recur=None,
              layer_kind=None):
    """``decoder_block`` with no cache or over one layer's dense
    ``kv_cache``. Returns (x, new_kv_cache, aux): ``aux`` is the router's
    load-balancing loss, or under ``moe_impl="dropless"`` the layer's
    ``moe_stats`` (``segment_ids`` 0 marks a token that is not live).
    ``layer_kind`` (a stack with window layers: this layer's
    ``LayerKind``, traced) gives the mask its window and the rope its
    frequencies in place of ``inv_freq``."""
    window, scale = None, rope_scale(cfg.rope)
    if layer_kind is not None:
        window, inv_freq, scale = layer_kind
    attend = (attend_fresh(positions, segment_ids, attn_impl,
                           cfg.attention_block, window)
              if kv_cache is None
              else attend_dense_cache(kv_cache, cache_offset, positions,
                                      cfg.attention_block, window))
    if cfg.is_moe and moe_impl == "dropless" and kind is None:
        layer, layer_index = layer_experts(layer, expert_stacks, layer_index)
    x, new_cache, aux = decoder_block(
        x, layer, cfg, positions, inv_freq, attend, norm_impl=norm_impl,
        live=segment_ids, moe_impl=moe_impl, layer_index=layer_index,
        kind=kind, recur=recur, rope_scale=scale)
    if aux is None:
        aux = jnp.float32(0.0)
    # anchor GSPMD propagation at the block boundary (no-op off-mesh;
    # residual STREAMS [B, S, n, H] run on one chip and are left alone)
    from ..parallel.sharding import constrain
    if x.ndim == 4:
        return x, new_cache, aux
    return constrain(x, "activations"), new_cache, aux


def _remat_wrap(fn, policy: str):
    """Wrap the block in jax.checkpoint per the activation-checkpoint policy
    (the reference's `activation_checkpoint: "selective"` flag that no code
    reads — reference init.py:138, SURVEY §2.2 row act-ckpt)."""
    if policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn)
    dots = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if policy == "selective_attn":
        # dots + the named flash-attention output: avoids re-running the
        # O(S^2) attention forward during backward at the cost of one
        # [B, S, Nq*D] residual per layer (not measured on the attached
        # chip: both training cells run "selective")
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.save_from_both_policies(
            dots, jax.checkpoint_policies.save_only_these_names("attn_out")))
    # selective: keep matmul outputs resident, recompute the cheap stuff
    return jax.checkpoint(fn, policy=dots)


def mtp_layer_index(cfg: ModelConfig) -> tuple[int, int]:
    """Where the prediction module's decoder layer lies in the ``attn``
    stack (and the latent pool) and in the ``moe`` stack: behind the main
    stack's layers of each kind."""
    return cfg.layers_of("*"), cfg.layers_of("E")


def mtp_forward(params: Params, blocks: Params, cfg: ModelConfig,
                next_tokens: jax.Array, stream: jax.Array,
                positions: jax.Array, inv_freq: jax.Array, attend, *,
                live=None, matmul=None, norm_impl: str = "xla"):
    """The next-token prediction module (DeepSeek-V3 technical report,
    section 2.2; depth 1) over rows whose NEXT token is known:

        z_i  = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]
        z'_i = one decoder layer (latent attention over z' rows 0..i at
               positions 0..i, then experts) of z_i

    ``stream`` [B, S, H] is the main stack's residual stream h BEFORE its
    final norm, ``next_tokens`` [B, S] the tokens one position on,
    ``blocks`` the cast block stacks (the module's layer is their last
    ``attn`` and ``moe`` entry), ``attend`` where the layer's latent rows
    live (``attend_fresh`` for a whole sequence; serve/decode.py
    ``mtp_attend_pages`` over the latent pool). Returns (z' [B, S, H], the
    attention's state, the expert layer's ``moe_stats``); ``mtp_head``
    makes the draft logits of t_{i+2}."""
    from .layers import dense_matmul
    matmul = matmul or dense_matmul
    m, dt = params["mtp"], stream.dtype
    la, le = mtp_layer_index(cfg)
    with jax.named_scope("mtp_embed_proj"):
        e = scaled(params["embed"]["embedding"][next_tokens].astype(dt),
                   cfg.mup.embedding)
        e = rms_norm(e, m["enorm"]["scale"].astype(dt), cfg.norm_eps,
                     impl=norm_impl)
        h = rms_norm(stream, m["hnorm"]["scale"].astype(dt), cfg.norm_eps,
                     impl=norm_impl)
        z = matmul(jnp.concatenate([e, h], axis=-1),
                   m["eh_proj"]["kernel"].astype(dt))
    with jax.named_scope("mtp_layer"):
        z, state, _ = decoder_block(
            z, table_layer(blocks, "*", la), cfg, positions, inv_freq,
            attend, matmul=matmul, norm_impl=norm_impl, live=live,
            layer_index=la, kind="*")
        z, _, stats = decoder_block(
            z, table_layer(blocks, "E", le), cfg, positions, inv_freq,
            None, matmul=matmul, norm_impl=norm_impl, live=live,
            layer_index=le, kind="E")
    return z, state, stats


def mtp_head(params: Params, z: jax.Array, cfg: ModelConfig,
             norm_impl: str = "xla") -> jax.Array:
    """The module's draft logits: its own final norm, the main model's
    head."""
    with jax.named_scope("mtp_head"):
        return unembed(dict(params, final_norm=params["mtp"]["final_norm"]),
                       z, cfg, norm_impl=norm_impl)


def unembed(params: Params, x: jax.Array, cfg: ModelConfig,
            norm_impl: str = "xla") -> jax.Array:
    """Final RMSNorm + LM head logits (tied or untied), fp32 output.

    Shared by the plain forward and the pipeline-parallel runner so the
    head semantics can never diverge between them.
    """
    return head_logits(params, final_norm(params, x, cfg, norm_impl), cfg)


def final_norm(params: Params, x: jax.Array, cfg: ModelConfig,
               norm_impl: str = "xla") -> jax.Array:
    return rms_norm(x, params["final_norm"]["scale"].astype(x.dtype),
                    cfg.norm_eps, impl=norm_impl)


def head_logits(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The LM head over a NORMED state (``unembed`` behind its norm; what a
    looped stack's last pass leaves is normed already), fp32 output."""
    with jax.named_scope("lm_head"):
        if cfg.tie_word_embeddings:
            logits = jnp.einsum(
                "bsh,vh->bsv", x,
                params["embed"]["embedding"].astype(x.dtype),
                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum(
                "bsh,hv->bsv", x,
                params["lm_head"]["kernel"].astype(x.dtype),
                preferred_element_type=jnp.float32)
    return scaled(logits.astype(jnp.float32), cfg.mup.lm_head)


class ExitState(NamedTuple):
    """What a looped stack carries from pass to pass of a row's way out:
    ``out`` the normed state of the pass the row LEFT at (what the head
    reads), ``stay`` the product of (1 - g) over the passes so far, ``total``
    the exit distribution's running sum, ``left`` whether the row has left."""
    out: jax.Array      # [B, S, H]
    stay: jax.Array     # [B, S] float32
    total: jax.Array    # [B, S] float32
    left: jax.Array     # [B, S] bool

    @classmethod
    def start(cls, x: jax.Array) -> "ExitState":
        rows = x.shape[:2]
        return cls(jnp.zeros_like(x), jnp.ones(rows, jnp.float32),
                   jnp.zeros(rows, jnp.float32), jnp.zeros(rows, bool))


def close_pass(params: Params, x: jax.Array, cfg: ModelConfig, t,
               exits: ExitState, norm_impl: str = "xla"):
    """What closes pass ``t`` (0-based; static or traced) of a looped stack
    over its stream ``x`` [B, S, H]:

        z_t = N_f(x)                      the ONE final norm, every pass
        g_t = sigmoid(w_g . z_t + b_g)    the exit gate, float32
        p_t = g_t prod_{j<t}(1 - g_j)     (the last pass takes what is left)

    a row leaves at the first pass where the running sum of p reaches
    ``cfg.exit_threshold`` (at the last pass every row that is still there),
    and the head reads the z of the pass it left at. Every pass RUNS for
    every row whatever the gate says (its K and V are written in every
    plane): at the published threshold 1 that is the model, and a threshold
    below 1 is refused at load. Returns (z_t, what the next pass reads;
    g_t [B, S]; the new ``ExitState``)."""
    z = final_norm(params, x, cfg, norm_impl)
    with jax.named_scope("exit_gate"):
        gate = params["exit_gate"]
        g = jax.nn.sigmoid(
            jnp.sum(z.astype(jnp.float32)
                    * gate["kernel"][:, 0].astype(jnp.float32), axis=-1)
            + gate["bias"].astype(jnp.float32)[0])
        last = t == cfg.num_passes - 1
        total = exits.total + jnp.where(last, exits.stay, g * exits.stay)
        leaves = ~exits.left & ((total >= cfg.exit_threshold) | last)
        exits = ExitState(jnp.where(leaves[..., None], z, exits.out),
                          exits.stay * (1.0 - g), total, exits.left | leaves)
    return z, g, exits


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: ModelConfig,
    *,
    positions: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    kv_cache: Optional[tuple[jax.Array, jax.Array]] = None,
    cache_offset: Optional[jax.Array] = None,
    attn_impl: str = "xla",          # xla | flash | ring | ulysses
    norm_impl: str = "xla",          # xla | pallas
    remat: str = "none",             # none | selective | full
    return_aux: bool = False,
    unembed_positions: Optional[jax.Array] = None,
    return_hidden: bool = False,
    moe_impl: str = "dropless",      # dropless | capacity (training)
    return_moe_stats: bool = False,
    return_ssm_state: bool = False,
    return_latent: bool = False,
    return_stream: bool = False,
    return_mtp: bool = False,
    return_passes: bool = False,
):
    """Compute logits [B, S, V] (fp32) — or, with ``return_hidden=True``,
    the final-normed hidden states [B, S, H] in the compute dtype (consumed
    by models.loss.chunked_next_token_loss so [B,S,V] never materialises).

    - ``segment_ids`` [B,S] enables packed sequences (0 = pad).
    - ``kv_cache`` ([L,B,Smax,Nkv,D], [L,B,Smax,Nkv,D]) + ``cache_offset``
      [B] enable incremental decoding; the updated cache is returned.
    - ``attn_impl='ring'`` runs context-parallel ring attention over the
      'sp' mesh axis (sequence must be sharded on 'sp').
    - ``unembed_positions`` [B] restricts the LM head to one position per
      row, returning [B, 1, V] — prefill needs only the last position's
      logits, and skipping the [S, V] unembed saves HBM and MXU time
      (the reference recomputes and discards full-vocab logits every step,
      reference serve/server.py:199-204).
    - ``moe_impl``: an MoE model's feed-forward is dropless (every token
      served by all of its k experts; inference) unless the caller asks for
      training's ``capacity`` dispatch, the only route with the router's
      aux loss (``return_aux``). ``return_moe_stats`` (dropless) appends
      the [E + 1] int32 vector of the live tokens' choices per expert
      summed over the layers and, last, the (layer, expert) pairs that got
      any (``segment_ids`` 0 = not live: prefill padding is never counted).
    - a model with a LAYER TABLE (``cfg.layer_pattern``) is walked layer by
      layer in a Python loop over one parameter stack a kind
      (``table_layers``); ``kv_cache`` then holds the ATTENTION layers
      alone ([La, B, Smax, Nkv, D]). Its state-space layers start from a
      zero state and keep padding (``segment_ids`` 0, which must follow
      the live tokens) out of it; ``return_ssm_state`` appends
      (conv tails [Lm, B, K-1, C], states [Lm, B, nh, P, N] float32) after
      the last live token: what cold prefill arms a slot with. The ``K``
      layers of a ``kimi_linear`` table (a table has ``M`` or ``K`` layers,
      not both) do the same: (conv tails [Lk, B, K-1, 3 d_in], states
      [Lk, B, nh, dk, dv] float32); the ``C`` layers of an ``lfm2_moe``
      table keep their windows alone: (rows [Lc, B, K-1, H],).
    - a model with LATENT attention keeps no dense cache (``kv_cache`` is
      refused): its window attends in the expanded form over its own
      tokens, and ``return_latent`` appends the rows a cache would keep,
      [La, B, S, kv_lora_rank + qk_rope_head_dim], which cold prefill
      writes to the latent pages. With ``cfg.hc_mult`` > 1 the residual is
      ``hc_mult`` streams: copies of the embedding at the start, summed
      before the final norm.
    - a model with a next-token prediction module (``cfg.mtp_layers``):
      ``return_stream`` appends the residual stream BEFORE the final norm
      [B, S, H] (what the module reads; cold prefill runs ``mtp_forward``
      on it once the first token is sampled), and ``return_mtp`` the
      module's draft logits [B, S, V] of the whole sequence, row i read
      with the embedding of ``tokens[i + 1]`` (the last row's wraps around
      and means nothing) and predicting token i + 2; the main stack's
      logits are what they are without it.
    - a LOOPED stack (``cfg.num_passes`` > 1) walks the uniform stack's
      scan once a pass inside a scan over the passes, ``close_pass``
      between them; ``kv_cache`` is [passes x L, ...], pass t's layer l at
      ``t * L + l``. ``return_passes`` appends (every pass's normed state
      [passes, B, S, H], every pass's gate [passes, B, S] float32).
    """
    compute_dtype = jnp.dtype(cfg.dtype)
    B, S = tokens.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, axis=0)
        if cache_offset is not None:
            positions = positions + cache_offset[:, None]

    from ..parallel.sharding import constrain
    emb = params["embed"]["embedding"]
    x = constrain(scaled(emb[tokens].astype(compute_dtype),
                         cfg.mup.embedding), "activations")

    inv_freq = model_rope_frequencies(cfg)

    if moe_impl not in ("dropless", "capacity"):
        raise ValueError(f"moe_impl must be dropless|capacity: {moe_impl!r}")
    dropless = cfg.is_moe and moe_impl == "dropless"
    if dropless and return_aux:
        raise ValueError(
            "the router's aux loss belongs to training's capacity dispatch: "
            "pass moe_impl='capacity' with return_aux")
    if return_moe_stats and not dropless:
        raise ValueError("return_moe_stats needs a dropless MoE model")
    if return_ssm_state and not cfg.is_recurrent:
        raise ValueError("return_ssm_state needs a model with state-space "
                         "layers")
    if cfg.is_latent and kv_cache is not None or (
            return_latent and not cfg.is_latent):
        raise ValueError(
            "a model with latent attention keeps no dense K/V cache (ask for "
            "return_latent and write the rows to the latent pages); "
            "return_latent needs such a model")
    if cfg.layer_pattern:
        if moe_impl != "dropless" or remat != "none":
            # training's capacity route drops tokens, the grouped matmul
            # and the chunked scan have no backward written (ROADMAP A5)
            raise ValueError(
                "a model with a layer table runs the dropless inference "
                "forward only (moe_impl='dropless', remat='none')")
        if cfg.hc_mult > 1:
            x = jnp.broadcast_to(x[:, :, None], (B, S, cfg.hc_mult,
                                                 cfg.hidden_size))
        x, new_cache, aux_total, ssm_state = _walk_table(
            params, x, cfg, positions, segment_ids, inv_freq, kv_cache,
            cache_offset, attn_impl, norm_impl, compute_dtype)
        if cfg.hc_mult > 1:
            x = jnp.sum(x.astype(jnp.float32), axis=2).astype(compute_dtype)
        mtp = []
        if return_mtp:
            if not cfg.mtp_layers:
                raise ValueError("return_mtp needs a model with a "
                                 "next-token prediction module")
            z, _rows, _stats = mtp_forward(
                params, cast_table_blocks(params["blocks"], compute_dtype),
                cfg, jnp.roll(tokens, -1, axis=1), x, positions, inv_freq,
                attend_fresh(positions, segment_ids, attn_impl),
                live=segment_ids, norm_impl=norm_impl)
            mtp = [mtp_head(params, z, cfg, norm_impl)]
        return _finish_forward(
            params, x, cfg, norm_impl, unembed_positions, return_hidden,
            [new_cache] * (kv_cache is not None or return_latent)
            + [aux_total] * return_moe_stats
            + [ssm_state] * return_ssm_state
            + [x] * return_stream + mtp)

    # plain leaves are cast to the compute dtype ONCE before the scan
    # (casting inside the body would stream fp32 master weights from HBM
    # every layer); int8 QuantTensor leaves ride the
    # scan quantized and dequantize one layer at a time inside the body,
    # so the whole-tree int8 storage saving survives the forward
    from ..ops.quantization import cast_params as _cast, precast_params

    blocks = precast_params(params["blocks"], compute_dtype)
    layer_ids = None        # scanned only where a kernel indexes a stack
    if dropless:
        blocks, expert_stacks = split_expert_stacks(blocks)
        if expert_stacks is not None:
            layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        block = functools.partial(_block_fn, cfg, attn_impl, norm_impl,
                                  moe_impl="dropless",
                                  expert_stacks=expert_stacks)
        aux0 = jnp.zeros((cfg.moe.stats_size,), jnp.int32)
    else:
        block = functools.partial(_block_fn, cfg, attn_impl, norm_impl,
                                  moe_impl="capacity")
        aux0 = jnp.float32(0.0)
    block = _remat_wrap(block, remat)
    # a stack with WINDOW layers: each layer's window, frequencies and rope
    # scale ride the scan beside its weights (None, an empty pytree, for
    # every other model: its scan is what it was). The dense cache stays
    # one plane a layer, full-length and masked: the ring is the pages'
    kinds = layer_kinds(cfg)

    def walk(x, aux, cache):
        """ONE walk of the L layers: (x, aux, that walk's planes of the
        dense cache [L, ...] twice, or None)."""
        if cache is None:
            def body(carry, layer_and_index):
                x, aux = carry
                layer, li, kind = layer_and_index
                x, _, aux_l = block(x.astype(compute_dtype),
                                    _cast(layer, compute_dtype), positions,
                                    segment_ids, inv_freq, layer_index=li,
                                    layer_kind=kind)
                return (x, aux + aux_l), None

            (x, aux), _ = jax.lax.scan(body, (x, aux),
                                       (blocks, layer_ids, kinds))
            return x, aux, None

        def body(carry, layer_and_cache):
            x, aux = carry
            layer, li, kind, kc, vc = layer_and_cache
            x, new_kv, aux_l = block(x.astype(compute_dtype),
                                     _cast(layer, compute_dtype), positions,
                                     segment_ids, inv_freq,
                                     kv_cache=(kc, vc), cache_offset=cache_offset,
                                     layer_index=li, layer_kind=kind)
            return (x, aux + aux_l), new_kv

        (x, aux), new_kvs = jax.lax.scan(
            body, (x, aux), (blocks, layer_ids, kinds, *cache))
        return x, aux, new_kvs

    if return_passes and not cfg.is_looped:
        raise ValueError("return_passes needs a looped stack "
                         "(total_ut_steps > 1)")
    passes = None
    if cfg.is_looped:
        # the stack's scan inside a scan over the passes: the same weights
        # every pass, the cache's planes [T, L, ...] a pass at a time
        T, L = cfg.num_passes, cfg.num_layers

        def one_pass(carry, t_and_cache):
            x, aux, exits = carry
            t, cache = t_and_cache
            with jax.named_scope("loop_pass"):
                x, aux, new = walk(x, aux, cache)
            z, g, exits = close_pass(params, x, cfg, t, exits, norm_impl)
            return (z, aux, exits), (new, (z, g) if return_passes else None)

        planes = (None if kv_cache is None else tuple(
            c.reshape(T, L, *c.shape[1:]) for c in kv_cache))
        (_, aux_total, exits), (new_cache, passes) = jax.lax.scan(
            one_pass, (x, aux0, ExitState.start(x)),
            (jnp.arange(T, dtype=jnp.int32), planes))
        if new_cache is not None:
            new_cache = tuple(c.reshape(T * L, *c.shape[2:])
                              for c in new_cache)
        x = exits.out           # NORMED: the head has no norm left to do
    else:
        x, aux_total, new_cache = walk(x, aux0, kv_cache)

    extras = []
    if kv_cache is not None:
        extras.append(new_cache)
    if return_aux or return_moe_stats:
        extras.append(aux_total)
    if return_passes:
        extras.append(passes)
    return _finish_forward(params, x, cfg, norm_impl, unembed_positions,
                           return_hidden, extras, normed=cfg.is_looped)


def _walk_table(params, x, cfg: ModelConfig, positions, segment_ids,
                inv_freq, kv_cache, cache_offset, attn_impl, norm_impl,
                compute_dtype):
    """The layers of a layer table over the residual stream ``x``: a Python
    loop over ``table_layers`` (no scan: the kinds' parameter shapes
    differ), each layer one ``decoder_block`` of its kind. Returns (x, the
    attention layers' updated dense cache (a latent model's rows
    [La, B, S, latent]) or None, the summed ``moe_stats``, (conv tails,
    states) of the state-space or ``K`` layers)."""
    from ..ops import kda, shortconv, ssm
    blocks = cast_table_blocks(params["blocks"], compute_dtype)
    window = ssm.recur_window(cfg, segment_ids)
    recurs = {"M": window, "P": window,
              "K": kda.recur_window(cfg, segment_ids),
              "C": shortconv.recur_window(cfg, segment_ids)}
    aux_total = jnp.zeros((cfg.moe.stats_size,), jnp.int32)
    caches, states, latents = [], [], []
    for kind, i in table_layers(cfg):
        cache = (None if kv_cache is None or kind not in "*P"
                 else (kv_cache[0][i], kv_cache[1][i]))
        x, state, aux = _block_fn(
            cfg, attn_impl, norm_impl, x.astype(compute_dtype),
            table_layer(blocks, kind, i), positions, segment_ids, inv_freq,
            kv_cache=cache, cache_offset=cache_offset, layer_index=i,
            kind=kind, recur=recurs.get(kind))
        if kind == "P":
            # both mixers' states: the dense cache, then (conv tail, state)
            if cache is not None:
                caches.append(state[0])
            state = state[1]
        if kind in recurs:
            states.append(state)
        elif kind == "*" and cache is not None:
            caches.append(state)
        elif kind == "*" and cfg.is_latent:
            latents.append(state)
        elif kind == "E":
            aux_total = aux_total + aux
    new_cache = jnp.stack(latents) if latents else None
    if caches:
        new_cache = (jnp.stack([c[0] for c in caches]),
                     jnp.stack([c[1] for c in caches]))
    # a part of the kind's state a position: (conv tails, states); a ``C``
    # layer's windows alone
    ssm_state = (tuple(jnp.stack(part) for part in zip(*states))
                 if states else None)
    return x, new_cache, aux_total, ssm_state


def _finish_forward(params, x, cfg: ModelConfig, norm_impl,
                    unembed_positions, return_hidden, extras: list,
                    normed: bool = False):
    """The head of ``forward``: logits (or the final-normed hidden states)
    at all or one position a row, then ``extras`` in order. ``normed``: x
    IS the final-normed state (a looped stack's)."""
    if unembed_positions is not None:
        x = jnp.take_along_axis(
            x, unembed_positions[:, None, None].astype(jnp.int32), axis=1)
    if not normed:
        x = final_norm(params, x, cfg, norm_impl)
    if return_hidden:
        # final-normed hidden [B,S,H] for chunked-loss consumers
        # (models.loss.chunked_next_token_loss) — skips the [S,V] unembed
        out = x
    else:
        out = head_logits(params, x, cfg)
    result = [out, *extras]
    return tuple(result) if len(result) > 1 else result[0]


# ---------------------------------------------------------------------------
# KV cache helpers (dense cache for the simple generate/eval path; the paged
# cache for serving lives in serve/kv_cache.py)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> tuple[jax.Array, jax.Array]:
    shape = (cfg.kv_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def flops_per_token(cfg: ModelConfig, seq_len: int) -> float:
    """Training FLOPs per token: 6*N_active + attention O(S) term.

    Honest accounting (SURVEY §7.3.4): the reference's planner uses
    2*P*B*S for a fwd+bwd step (reference plan.py:97-102), a 3x
    underestimate that also ignores attention FLOPs. Used by MFU metrics
    and bench.py.
    """
    # active params exclude embedding lookup (no matmul) but include lm_head
    H, V, L = cfg.hidden_size, cfg.vocab_size, cfg.num_layers
    D, Nq, Nkv, F = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads, cfg.ffn_size
    attn_proj = H * Nq * D + 2 * H * Nkv * D + Nq * D * H
    if cfg.is_moe:
        ffn = 3 * H * F * cfg.moe.experts_per_token  # active experts only
    else:
        ffn = 3 * H * F if cfg.activation in ("silu", "gelu") else 2 * H * F
    head = H * V
    # (a looped stack multiplies by every layer's weights once a pass)
    matmul_params = cfg.num_passes * L * (attn_proj + ffn) + head
    # fwd 2 flops/param/token, bwd 4
    dense_flops = 6.0 * matmul_params
    # attention scores+values: 2 * 2 * Nq * D * S per token fwd, x3 with bwd
    attn_flops = 12.0 * cfg.num_passes * L * Nq * D * seq_len
    return dense_flops + attn_flops
