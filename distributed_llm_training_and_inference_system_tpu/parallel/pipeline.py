"""Pipeline parallelism: collective GPipe schedule in one SPMD program.

The reference plans PP as a cost-model dimension and nothing else
(reference plan.py:140, :91-93 — no stage partitioning or schedule exists;
SURVEY §2.2 row PP, §7.3 risk #1). Here the schedule is expressed the
TPU-native way — not per-rank programs with P2P sends, but ONE jitted
program in which the pipeline-stage index is an ARRAY DIMENSION sharded
over the 'pp' mesh axis:

- block params [L, ...] reshape to [pp, L/pp, ...] with the stage dim
  sharded on 'pp' — each device group holds its stage's layers;
- activations live in a stage buffer x[pp, mb, S, H]; one schedule tick
  runs ALL stages in parallel (vmap over the stage dim) on the microbatch
  each currently holds, then `jnp.roll(..., axis=0)` advances activations
  to the next stage — XLA lowers a roll over a sharded dim to a
  collective-permute over ICI;
- stage 0 injects a fresh microbatch's embeddings each tick; the last
  stage computes logits+loss for the microbatch completing there
  (masked out during the (pp-1)-tick fill/drain bubble);
- tokens/segments/positions ride along in rolling buffers so every stage
  masks and (at the end) scores against the right microbatch.

Because stages are an array axis, tensor/fsdp/sequence sharding inside
each stage still comes from GSPMD (the same PARAM_RULES), and autodiff
through scan+roll yields the reverse schedule — backward is a pipeline
too. Bubble fraction is (pp-1)/(M+pp-1), exactly what the planner prices.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..config.schema import ModelConfig, ParallelConfig
from ..models.gpt import _block_fn, _remat_wrap, unembed
from ..models.layers import rope_frequencies
from ..models.loss import next_token_loss
from .sharding import current_mesh, _shrink_to_fit


def _constrain(x, spec):
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return x
    spec = _shrink_to_fit(P(*spec[: x.ndim]), x.shape, mesh)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def _refuse_looped(model_cfg: ModelConfig) -> None:
    if model_cfg.is_looped:
        raise ValueError(
            f"{model_cfg.name} walks its stack {model_cfg.num_passes} times "
            "(total_ut_steps): pipeline stages are refused for a looped "
            "stack (a stage holds a slice of the layers and a micro-batch "
            "would have to come back to stage 0 after every pass, through "
            "the final norm and the exit gate: a schedule that is not here)")


def _refuse_windowed(model_cfg: ModelConfig) -> None:
    if model_cfg.has_window:
        raise ValueError(
            f"{model_cfg.name} has window layers (sliding_window "
            f"{model_cfg.sliding_window}): pipeline stages are refused (a "
            "stage's scan hands every layer one rope and one causal mask; "
            "the layer kinds' window, frequencies and rope scale do not "
            "ride it; ROADMAP B3)")


def make_pipeline_loss_fn(
    model_cfg: ModelConfig,
    par: ParallelConfig,
    attn_impl: str = "xla",
) -> Callable:
    """Build loss_fn(params, batch) with batch tokens [M, mb, S].

    Plugs into exec.make_train_step(loss_fn=...) so the optimizer/clip/
    metrics path is shared with the non-pipelined step.
    """
    _refuse_looped(model_cfg)
    _refuse_windowed(model_cfg)
    pp = par.pipeline_parallel
    M = par.num_microbatches
    L = model_cfg.num_layers
    assert L % pp == 0, f"layers {L} not divisible by pp {pp}"
    remat = par.activation_checkpoint

    def loss_fn(params: Any, batch: dict[str, jax.Array]):
        tokens = batch["tokens"]                      # [M, mb, S]
        assert tokens.ndim == 3 and tokens.shape[0] == M, tokens.shape
        mb, S = tokens.shape[1], tokens.shape[2]
        segs = batch.get("segment_ids")
        if segs is None:
            segs = jnp.ones_like(tokens)
        pos = batch.get("positions")
        if pos is None:
            pos = jnp.arange(S, dtype=jnp.int32)[None, None, :].repeat(
                M, 0).repeat(mb, 1)

        compute_dtype = jnp.dtype(model_cfg.dtype)
        H = model_cfg.hidden_size
        emb = params["embed"]["embedding"]
        inv_freq = rope_frequencies(
            model_cfg.head_dim, model_cfg.rope.base, model_cfg.rope.scaling,
            model_cfg.rope.scaling_factor)

        # [L, ...] -> [pp, L/pp, ...], stage dim sharded on 'pp'
        def to_stages(x):
            return x.reshape(pp, L // pp, *x.shape[1:]).astype(compute_dtype)
        stage_blocks = jax.tree_util.tree_map(to_stages, params["blocks"])

        block = functools.partial(_block_fn, model_cfg, attn_impl, "xla",
                                  moe_impl="capacity")
        block = _remat_wrap(block, remat)

        def stage_fn(blocks_one, x, positions, segments):
            """Run this stage's L/pp layers. x: [mb, S, H]."""
            def body(carry, layer):
                x, aux = carry
                x, _, aux_l = block(x, layer, positions, segments, inv_freq)
                return (x, aux + aux_l), None
            (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), blocks_one)
            return x, aux

        vstage = jax.vmap(stage_fn, in_axes=(0, 0, 0, 0))

        act_spec = ("pp", ("dp", "fsdp"), "sp", None)
        buf_spec = ("pp", ("dp", "fsdp"), "sp")

        T = M + pp - 1
        x0 = _constrain(jnp.zeros((pp, mb, S, H), compute_dtype), act_spec)
        tok0 = _constrain(jnp.zeros((pp, mb, S), tokens.dtype), buf_spec)
        seg0 = _constrain(jnp.zeros((pp, mb, S), segs.dtype), buf_spec)
        pos0 = _constrain(jnp.zeros((pp, mb, S), pos.dtype), buf_spec)

        def tick(carry, t):
            x_st, tok_st, seg_st, pos_st, loss_sum, cnt_sum, aux_sum = carry
            idx = jnp.clip(t, 0, M - 1)
            tok_t = jax.lax.dynamic_index_in_dim(tokens, idx, 0, False)
            seg_t = jax.lax.dynamic_index_in_dim(segs, idx, 0, False)
            pos_t = jax.lax.dynamic_index_in_dim(pos, idx, 0, False)

            # inject at stage 0
            x_in = x_st.at[0].set(emb[tok_t].astype(compute_dtype))
            tok_st = tok_st.at[0].set(tok_t)
            seg_st = seg_st.at[0].set(seg_t)
            pos_st = pos_st.at[0].set(pos_t)
            x_in = _constrain(x_in, act_spec)

            # one tick: every stage advances its current microbatch
            y, aux = vstage(stage_blocks, x_in, pos_st, seg_st)
            y = _constrain(y, act_spec)

            # stage activity mask for aux (fill/drain bubble)
            stage_ids = jnp.arange(pp)
            active = ((t - stage_ids) >= 0) & ((t - stage_ids) < M)
            aux_sum = aux_sum + jnp.sum(aux * active)

            # last stage completes microbatch t-(pp-1); chunked CE keeps the
            # [mb, S, V] fp32 logits pair off the per-tick memory peak
            from ..models.layers import rms_norm
            from ..models.loss import chunked_next_token_loss
            h = rms_norm(y[pp - 1],
                         params["final_norm"]["scale"].astype(y.dtype),
                         model_cfg.norm_eps)
            tied_ = model_cfg.tie_word_embeddings
            w_ = (params["embed"]["embedding"] if tied_
                  else params["lm_head"]["kernel"])
            loss_mb, cnt_mb = chunked_next_token_loss(
                h, w_, tok_st[pp - 1], seg_st[pp - 1], tied=tied_)
            out_active = ((t - (pp - 1)) >= 0) & ((t - (pp - 1)) < M)
            loss_sum = loss_sum + jnp.where(out_active, loss_mb * cnt_mb, 0.0)
            cnt_sum = cnt_sum + jnp.where(out_active, cnt_mb, 0.0)

            # advance the pipeline: stage p's output becomes p+1's input
            x_next = _constrain(jnp.roll(y, 1, axis=0), act_spec)
            tok_st = _constrain(jnp.roll(tok_st, 1, axis=0), buf_spec)
            seg_st = _constrain(jnp.roll(seg_st, 1, axis=0), buf_spec)
            pos_st = _constrain(jnp.roll(pos_st, 1, axis=0), buf_spec)
            return (x_next, tok_st, seg_st, pos_st,
                    loss_sum, cnt_sum, aux_sum), None

        init = (x0, tok0, seg0, pos0, jnp.float32(0.0), jnp.float32(0.0),
                jnp.float32(0.0))
        (_, _, _, _, loss_sum, cnt_sum, aux_sum), _ = jax.lax.scan(
            tick, init, jnp.arange(T))

        loss = loss_sum / jnp.maximum(cnt_sum, 1.0)
        total = loss + aux_sum / M
        return total, (loss, cnt_sum)

    return loss_fn


def make_pipeline_grad_fn(
    model_cfg: ModelConfig,
    par: ParallelConfig,
    attn_impl: str = "xla",
) -> Callable:
    """1F1B-style interleaved pipeline schedule with a MANUAL backward.

    GPipe above differentiates through the schedule scan, so XLA stores the
    scan carry for every tick — activation memory grows linearly with the
    microbatch count M (per chip: (M+pp-1) x mb x S x H). This builds
    grad_fn(params, batch) -> ((total, (loss, count)), grads) computing the
    backward INSIDE the same scan, 1F1B style (BASELINE config 3):

    - each tick, every stage runs one forward microbatch AND one backward
      microbatch (SPMD lockstep: all stages do identical work per tick);
      backward for microbatch j at stage s fires at tick j + 2(pp-1) - s,
      i.e. as soon as its cotangent arrives from stage s+1 — the last
      stage backpropagates a microbatch the same tick its loss is computed;
    - stage INPUTS are saved in a ring buffer of W = 2(pp-1)+1 slots per
      stage (the maximum in-flight microbatches at stage 0), and each
      stage's forward is RECOMPUTED during its backward tick via jax.vjp —
      activation memory is W x mb x S x H per chip, CONSTANT in M (true
      per-device 1F1B holds <= pp inputs; the lockstep collective form
      holds <= 2(pp-1)+1 — same constant-in-M bound, ~2x the constant);
    - cotangents ride a reverse-rolling buffer (ppermute down the 'pp'
      axis, the mirror of the forward roll);
    - out-of-range (fill/drain) backward ticks carry zero cotangents, so
      their vjp contributions vanish without explicit masking.

    Dense models only (MoE's aux-loss gradient path needs the autodiff
    schedule — ShardedTrainer falls back to GPipe for MoE).
    """
    _refuse_looped(model_cfg)
    _refuse_windowed(model_cfg)
    pp = par.pipeline_parallel
    M = par.num_microbatches
    L = model_cfg.num_layers
    assert L % pp == 0, f"layers {L} not divisible by pp {pp}"
    assert not model_cfg.is_moe, "1f1b schedule: dense models only (use gpipe)"
    W = 2 * (pp - 1) + 1
    remat = par.activation_checkpoint
    tied = model_cfg.tie_word_embeddings

    def grad_fn(params: Any, batch: dict[str, jax.Array]):
        tokens = batch["tokens"]                      # [M, mb, S]
        assert tokens.ndim == 3 and tokens.shape[0] == M, tokens.shape
        mb, S = tokens.shape[1], tokens.shape[2]
        segs = batch.get("segment_ids")
        if segs is None:
            segs = jnp.ones_like(tokens)
        pos = batch.get("positions")
        if pos is None:
            pos = jnp.arange(S, dtype=jnp.int32)[None, None, :].repeat(
                M, 0).repeat(mb, 1)

        compute_dtype = jnp.dtype(model_cfg.dtype)
        H = model_cfg.hidden_size
        inv_freq = rope_frequencies(
            model_cfg.head_dim, model_cfg.rope.base, model_cfg.rope.scaling,
            model_cfg.rope.scaling_factor)

        # Params are cast to the compute dtype ONCE outside the scan (the
        # cast transpose is a cast, so vjp-in-bf16 + fp32 accumulation gives
        # the same grads as value_and_grad through an in-scan cast, without
        # re-reading the fp32 master copy every tick).
        cast = functools.partial(jax.tree_util.tree_map,
                                 lambda p: p.astype(compute_dtype))

        def to_stages(x):
            return x.reshape(pp, L // pp, *x.shape[1:])
        stage_blocks = jax.tree_util.tree_map(to_stages,
                                              cast(params["blocks"]))
        head_params = {"final_norm": cast(params["final_norm"])}
        if tied:
            head_params["embed"] = cast(params["embed"])
        else:
            head_params["lm_head"] = cast(params["lm_head"])
        emb_c = params["embed"]["embedding"].astype(compute_dtype)

        block = functools.partial(_block_fn, model_cfg, attn_impl, "xla",
                                  moe_impl="capacity")
        block = _remat_wrap(block, remat)

        def stage_fn(blocks_one, x, positions, segments):
            def body(x, layer):
                x, _, _ = block(x, layer, positions, segments, inv_freq)
                return x, None

            x, _ = jax.lax.scan(body, x, blocks_one)
            return x

        def stage_bwd(blocks_one, x_saved, pos_s, seg_s, dy_s):
            _, vjp = jax.vjp(
                lambda b, x: stage_fn(b, x, pos_s, seg_s), blocks_one,
                x_saved)
            db, dx = vjp(dy_s)
            return db, dx

        vstage = jax.vmap(stage_fn, in_axes=(0, 0, 0, 0))
        vbwd = jax.vmap(stage_bwd)

        def embed_fn(emb, toks):
            return emb[toks]

        def head_fn(hp, y, toks, sg):
            # SUM loss (loss*count) so per-microbatch grads add linearly;
            # everything is rescaled by 1/count_total after the scan.
            # Chunked CE: the dense [mb, S, V] fp32 logits pair would
            # otherwise materialise on the last stage EVERY tick — the same
            # HBM ceiling models/loss.py removes from the non-pipelined path
            from ..models.layers import rms_norm
            from ..models.loss import chunked_next_token_loss
            h = rms_norm(y, hp["final_norm"]["scale"].astype(y.dtype),
                         model_cfg.norm_eps)
            w = (hp["embed"]["embedding"] if tied
                 else hp["lm_head"]["kernel"])
            loss, cnt = chunked_next_token_loss(h, w, toks, sg, tied=tied)
            return loss * cnt, cnt

        head_vg = jax.value_and_grad(head_fn, argnums=(0, 1), has_aux=True)

        act_spec = ("pp", ("dp", "fsdp"), "sp", None)
        ring_spec = ("pp", None, ("dp", "fsdp"), "sp", None)
        buf_spec = ("pp", None, ("dp", "fsdp"), "sp")

        T = M + 2 * (pp - 1)
        zeros_x = jnp.zeros((pp, mb, S, H), compute_dtype)
        x0 = _constrain(zeros_x, act_spec)
        dy0 = _constrain(zeros_x, act_spec)
        ring_x = _constrain(jnp.zeros((pp, W, mb, S, H), compute_dtype),
                            ring_spec)
        ring_tok = _constrain(jnp.zeros((pp, W, mb, S), tokens.dtype),
                              buf_spec)
        ring_seg = _constrain(jnp.zeros((pp, W, mb, S), segs.dtype), buf_spec)
        ring_pos = _constrain(jnp.zeros((pp, W, mb, S), pos.dtype), buf_spec)

        # fp32 grad accumulators (the bf16 per-tick contributions promote)
        f32 = functools.partial(jax.tree_util.tree_map,
                                lambda p: jnp.zeros(p.shape, jnp.float32))
        g_blocks0 = f32(stage_blocks)
        g_head0 = f32(head_params)
        g_emb0 = jnp.zeros(params["embed"]["embedding"].shape, jnp.float32)

        stage_ids = jnp.arange(pp)

        def tick(carry, t):
            (x_st, ring_x, ring_tok, ring_seg, ring_pos, dy_st,
             g_blocks, g_head, g_emb, loss_sum, cnt_sum) = carry

            # ---- forward half ------------------------------------------------
            idx = jnp.clip(t, 0, M - 1)
            tok_t = jax.lax.dynamic_index_in_dim(tokens, idx, 0, False)
            seg_t = jax.lax.dynamic_index_in_dim(segs, idx, 0, False)
            pos_t = jax.lax.dynamic_index_in_dim(pos, idx, 0, False)

            x_in = x_st.at[0].set(embed_fn(emb_c, tok_t))
            x_in = _constrain(x_in, act_spec)

            # save each stage's input (+ its microbatch's tok/seg/pos) into
            # ring slot (t - s) mod W
            slots_f = (t - stage_ids) % W
            upd = jax.vmap(
                lambda ring, val, slot: jax.lax.dynamic_update_index_in_dim(
                    ring, val, slot, 0))
            # stage s's tok/seg/pos buffers: the rolling values from the
            # fwd rings one tick ago are exactly what stage s processes now,
            # so store fresh per-stage copies read from the previous ring
            # state via the SAME slot arithmetic: stage s processes mb t-s,
            # whose tok/seg/pos are tokens[t-s] — gather directly.
            mb_f = jnp.clip(t - stage_ids, 0, M - 1)        # [pp]
            tok_f = tokens[mb_f]                             # [pp, mb, S]
            seg_f = segs[mb_f]
            pos_f = pos[mb_f]
            ring_x = _constrain(upd(ring_x, x_in, slots_f), ring_spec)
            ring_tok = upd(ring_tok, tok_f, slots_f)
            ring_seg = upd(ring_seg, seg_f, slots_f)
            ring_pos = upd(ring_pos, pos_f, slots_f)

            y = vstage(stage_blocks, x_in, pos_f, seg_f)
            y = _constrain(y, act_spec)

            # ---- last-stage loss + its cotangent -----------------------------
            o = t - (pp - 1)                     # microbatch completing now
            out_active = ((o >= 0) & (o < M)).astype(jnp.float32)
            (sumloss, cnt), (dhead, dy_last) = head_vg(
                head_params, y[pp - 1], tok_f[pp - 1], seg_f[pp - 1])
            loss_sum = loss_sum + out_active * sumloss
            cnt_sum = cnt_sum + out_active * cnt
            g_head = jax.tree_util.tree_map(
                lambda a, d: a + out_active * d, g_head, dhead)
            dy_last = dy_last * out_active.astype(dy_last.dtype)

            # ---- backward half ----------------------------------------------
            # stage s backprops microbatch b_s = t - 2(pp-1) + s; its
            # cotangent arrived via the reverse roll (zero when inactive)
            dy_in = _constrain(dy_st.at[pp - 1].set(dy_last), act_spec)
            slots_b = (t - 2 * (pp - 1) + stage_ids) % W
            pick = jax.vmap(
                lambda ring, slot: jax.lax.dynamic_index_in_dim(
                    ring, slot, 0, False))
            x_saved = pick(ring_x, slots_b)
            tok_b = pick(ring_tok, slots_b)
            seg_b = pick(ring_seg, slots_b)
            pos_b = pick(ring_pos, slots_b)

            db_st, dx_st = vbwd(stage_blocks, x_saved, pos_b, seg_b, dy_in)
            g_blocks = jax.tree_util.tree_map(lambda a, d: a + d,
                                              g_blocks, db_st)

            # stage 0's dx is the embedding-injection cotangent for its
            # backward microbatch (zero when inactive — dy was zero)
            _, emb_vjp = jax.vjp(lambda e: embed_fn(e, tok_b[0]), emb_c)
            g_emb = g_emb + emb_vjp(dx_st[0])[0].astype(jnp.float32)

            # ---- advance both pipelines -------------------------------------
            x_next = _constrain(jnp.roll(y, 1, axis=0), act_spec)
            dy_next = _constrain(jnp.roll(dx_st, -1, axis=0), act_spec)
            return (x_next, ring_x, ring_tok, ring_seg, ring_pos, dy_next,
                    g_blocks, g_head, g_emb, loss_sum, cnt_sum), None

        init = (x0, ring_x, ring_tok, ring_seg, ring_pos, dy0,
                g_blocks0, g_head0, g_emb0, jnp.float32(0.0), jnp.float32(0.0))
        (_, _, _, _, _, _, g_blocks, g_head, g_emb, loss_sum, cnt_sum), _ = (
            jax.lax.scan(tick, init, jnp.arange(T)))

        cnt_total = jnp.maximum(cnt_sum, 1.0)
        inv = 1.0 / cnt_total

        def from_stages(x):
            return x.reshape(L, *x.shape[2:])

        grads = {"blocks": jax.tree_util.tree_map(
            lambda g: from_stages(g) * inv, g_blocks)}
        grads["final_norm"] = jax.tree_util.tree_map(
            lambda g: g * inv, g_head["final_norm"])
        if tied:
            grads["embed"] = {"embedding":
                              (g_emb + g_head["embed"]["embedding"]) * inv}
        else:
            grads["embed"] = {"embedding": g_emb * inv}
            grads["lm_head"] = jax.tree_util.tree_map(
                lambda g: g * inv, g_head["lm_head"])

        loss = loss_sum * inv
        return (loss, (loss, cnt_sum)), grads

    return grad_fn


def reshape_batch_for_pipeline(batch: dict, num_microbatches: int) -> dict:
    """[B, S] host batch -> [M, B/M, S] microbatch-major layout."""
    def split(x):
        B = x.shape[0]
        assert B % num_microbatches == 0, (B, num_microbatches)
        return x.reshape(num_microbatches, B // num_microbatches, *x.shape[1:])
    return {k: split(v) for k, v in batch.items()}


def pipeline_batch_specs(batch: dict, mesh) -> dict:
    """Specs for [M, mb, S, ...] batches: microbatch dim replicated, batch
    over (dp, fsdp), sequence over sp."""
    def spec(x):
        if x.ndim >= 3:
            s = P(None, ("dp", "fsdp"), "sp", *(None,) * (x.ndim - 3))
        elif x.ndim == 2:
            s = P(None, ("dp", "fsdp"))
        else:
            s = P()
        return _shrink_to_fit(s, x.shape, mesh)
    return jax.tree_util.tree_map(spec, batch)
