"""The training step: loss → grad → clip → update, with grad accumulation.

Parity: the reference's hot loop is engine.py:281-326 (forward,
accelerator.backward, clip+step+sched at accumulation boundaries). Here the
whole step — including accumulation — is ONE jitted XLA program:
accumulation is a `lax.scan` over microbatches (constant memory, no Python
loop), clipping uses the true global norm, and the update is pure. Under
pjit this same function runs SPMD on any mesh; gradient all-reduce is
inserted by XLA from the shardings (no DDP hooks).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax

from ..config.schema import ModelConfig, OptimizerConfig, ParallelConfig
from ..models import forward, next_token_loss
from ..models.loss import chunked_next_token_loss
from ..utils.tree import global_norm
from .fused_update import fused_adamw_apply
from .optimizer import _decay_mask, make_optimizer


@jax.tree_util.register_dataclass
@dataclass
class TrainState:
    """Carried training state (params fp32 master, sharded opt state)."""
    step: jax.Array
    params: Any
    opt_state: Any

    @classmethod
    def create(cls, params: Any, tx: optax.GradientTransformation) -> "TrainState":
        return cls(step=jnp.zeros((), jnp.int32), params=params,
                   opt_state=tx.init(params))


def _loss_fn(params, batch, model_cfg: ModelConfig, attn_impl: str, remat: str,
             loss_chunk: int = 512):
    """Training loss. With ``loss_chunk > 0`` the LM head + cross-entropy run
    a piece at a time (models.loss.chunked_next_token_loss), so no [B, S, V]
    fp32 logits are ever resident: the forward walks chunks of ``loss_chunk``
    positions and keeps each row's logsumexp, the hand-written backward
    walks slices of the vocabulary or chunks of rows, whichever rewrites
    fewer bytes (models.loss.chunked_loss_backward_plan)."""
    out = forward(
        params, batch["tokens"], model_cfg,
        positions=batch.get("positions"),
        segment_ids=batch.get("segment_ids"),
        attn_impl=attn_impl, remat=remat,
        return_aux=model_cfg.is_moe,
        return_hidden=loss_chunk > 0,
        # training keeps the capacity dispatch (plain einsums: it has a
        # gradient and shards over 'ep'); serving is dropless
        moe_impl="capacity",
    )
    if model_cfg.is_moe:
        head_in, aux = out
    else:
        head_in, aux = out, 0.0
    if loss_chunk > 0:
        tied = model_cfg.tie_word_embeddings
        w = (params["embed"]["embedding"] if tied
             else params["lm_head"]["kernel"])
        loss, count = chunked_next_token_loss(
            head_in, w, batch["tokens"], batch.get("segment_ids"),
            chunk=loss_chunk, tied=tied)
    else:
        loss, count = next_token_loss(head_in, batch["tokens"],
                                      batch.get("segment_ids"))
    return loss + aux, (loss, count)


def make_train_step(
    model_cfg: ModelConfig,
    opt_cfg: OptimizerConfig,
    par_cfg: Optional[ParallelConfig] = None,
    attn_impl: str = "xla",
    loss_fn: Optional[Callable] = None,
    loss_chunk: int = 512,
    grad_fn: Optional[Callable] = None,
) -> tuple[Callable, optax.GradientTransformation, Callable]:
    """Build (train_step, tx, schedule).

    train_step(state, batch) -> (state, metrics). ``batch["tokens"]`` is
    [accum*mb, S]; with gradient_accumulation_steps>1 the leading dim is
    split and scanned, averaging grads — semantics of the reference's
    accumulation boundary (engine.py:294-305) in one compiled program.

    A custom ``loss_fn(params, batch) -> (total, (loss, count))`` overrides
    the default forward (used by the GPipe pipeline runner, which packs its
    own microbatching — accumulation is then forced to 1). A custom
    ``grad_fn(params, batch) -> ((total, (loss, count)), grads)`` bypasses
    autodiff entirely (the 1F1B pipeline schedule computes its backward
    inside its own schedule scan).
    """
    par_cfg = par_cfg or ParallelConfig()
    tx, schedule = make_optimizer(opt_cfg)
    custom = loss_fn is not None or grad_fn is not None
    accum = 1 if custom else max(par_cfg.gradient_accumulation_steps, 1)
    remat = par_cfg.activation_checkpoint
    if grad_fn is None:
        if loss_fn is None:
            loss_fn = functools.partial(_loss_fn, model_cfg=model_cfg,
                                        attn_impl=attn_impl, remat=remat,
                                        loss_chunk=loss_chunk)
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state: TrainState, batch: dict[str, jax.Array]):
        if accum == 1:
            (total, (loss, count)), grads = grad_fn(state.params, batch)
        else:
            # the carry is a params-sized tree resident across the whole
            # scan; accum_dtype=bfloat16 halves it (OptimizerConfig
            # docstring — the fp32 carry OOM'd gpt-7b-4l accumulation)
            acc_dtype = jnp.dtype(opt_cfg.accum_dtype)

            def micro(carry, mb):
                grads_acc, loss_acc, count_acc = carry
                (_, (loss, count)), grads = grad_fn(state.params, mb)
                grads_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(acc_dtype), grads_acc, grads)
                return (grads_acc, loss_acc + loss * count, count_acc + count), None

            def split(x):
                return x.reshape(accum, x.shape[0] // accum, *x.shape[1:])

            micro_batches = jax.tree_util.tree_map(split, batch)
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, acc_dtype), state.params)
            (grads, loss_sum, count), _ = jax.lax.scan(
                micro, (zeros, jnp.float32(0.0), jnp.float32(0.0)), micro_batches)
            # mean in fp32: clip/update math is fp32 regardless of carry
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32) / accum, grads)
            loss = loss_sum / jnp.maximum(count, 1.0)

        gnorm = global_norm(grads)
        if opt_cfg.grad_clip > 0:
            clip_scale = jnp.minimum(1.0, opt_cfg.grad_clip / (gnorm + 1e-9))
        else:
            clip_scale = jnp.float32(1.0)

        with jax.named_scope("optimizer_update"):
            if opt_cfg.fused and opt_cfg.type in ("adamw", "adam"):
                # One HBM pass per leaf: clip folded into the update, no
                # clipped-grads / updates trees materialised
                # (exec/fused_update.py; numerics == the optax chain below).
                adam = state.opt_state[0]   # ScaleByAdamState (chain head)
                lr = schedule(adam.count)
                wd = opt_cfg.weight_decay if opt_cfg.type == "adamw" else 0.0
                new_params, new_mu, new_nu = fused_adamw_apply(
                    state.params, grads, adam.mu, adam.nu, adam.count,
                    lr=lr, b1=opt_cfg.betas[0], b2=opt_cfg.betas[1],
                    eps=opt_cfg.eps, weight_decay=wd,
                    decay_mask=_decay_mask(state.params),
                    clip_scale=clip_scale)
                new_opt_state = (adam._replace(count=adam.count + 1,
                                               mu=new_mu, nu=new_nu),
                                 ) + tuple(
                    s._replace(count=s.count + 1)
                    if "count" in getattr(s, "_fields", ()) else s
                    for s in state.opt_state[1:])
            else:
                grads = jax.tree_util.tree_map(lambda g: g * clip_scale, grads)
                updates, new_opt_state = tx.update(grads, state.opt_state,
                                                   state.params)
                new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(step=state.step + 1, params=new_params,
                               opt_state=new_opt_state)
        metrics = {
            "loss": loss,
            "grad_norm": gnorm,
            "lr": schedule(state.step),
            "tokens": jnp.float32(batch["tokens"].size),
        }
        return new_state, metrics

    return train_step, tx, schedule


def make_eval_step(model_cfg: ModelConfig, attn_impl: str = "xla") -> Callable:
    """eval_step(params, batch) -> {loss, tokens} (parity: engine.py:341-361)."""
    def eval_step(params, batch):
        logits = forward(params, batch["tokens"], model_cfg,
                         positions=batch.get("positions"),
                         segment_ids=batch.get("segment_ids"),
                         attn_impl=attn_impl, moe_impl="capacity")
        loss, count = next_token_loss(logits, batch["tokens"],
                                      batch.get("segment_ids"))
        return {"loss": loss, "tokens": count}
    return eval_step
