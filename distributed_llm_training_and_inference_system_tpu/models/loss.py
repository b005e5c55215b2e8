"""Loss functions for causal LM training/eval.

Parity: the reference relies on HF's internal loss (labels=input_ids,
reference engine.py:206-215, :284). Implemented explicitly here: shifted
next-token cross-entropy in fp32 with padding masks and optional z-loss
(stabilises bf16 training at scale).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def cross_entropy(
    logits: jax.Array,           # [B, S, V] fp32
    targets: jax.Array,          # [B, S] int
    weights: Optional[jax.Array] = None,   # [B, S] 0/1 mask
    z_loss_weight: float = 0.0,
) -> tuple[jax.Array, jax.Array]:
    """Mean token cross-entropy. Returns (loss, token_count)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)                    # [B,S]
    target_logit = jnp.take_along_axis(
        logits, targets[..., None], axis=-1).squeeze(-1)        # [B,S]
    nll = logz - target_logit
    if z_loss_weight > 0.0:
        nll = nll + z_loss_weight * jnp.square(logz)
    if weights is None:
        weights = jnp.ones_like(nll)
    weights = weights.astype(jnp.float32)
    total = jnp.sum(nll * weights)
    count = jnp.maximum(jnp.sum(weights), 1.0)
    return total / count, count


def next_token_loss(
    logits: jax.Array,           # [B, S, V]
    tokens: jax.Array,           # [B, S] the input tokens
    segment_ids: Optional[jax.Array] = None,
    z_loss_weight: float = 0.0,
) -> tuple[jax.Array, jax.Array]:
    """Shifted LM loss: predict tokens[:, 1:] from logits[:, :-1].

    With packed sequences, positions where the *target* starts a new segment
    (or is padding) are masked out.
    """
    shift_logits = logits[:, :-1]
    shift_targets = tokens[:, 1:]
    if segment_ids is not None:
        same_seg = segment_ids[:, 1:] == segment_ids[:, :-1]
        not_pad = segment_ids[:, 1:] != 0
        weights = (same_seg & not_pad).astype(jnp.float32)
    else:
        weights = None
    return cross_entropy(shift_logits, shift_targets, weights, z_loss_weight)


@jax.named_scope("chunked_loss")
def chunked_next_token_loss(
    hidden: jax.Array,           # [B, S, H] final-normed hidden (bf16 ok)
    unembed_w: jax.Array,        # [V, H] (tied embedding) or [H, V] (head)
    tokens: jax.Array,           # [B, S] the input tokens
    segment_ids: Optional[jax.Array] = None,
    z_loss_weight: float = 0.0,
    chunk: int = 512,
    tied: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Shifted LM loss WITHOUT materialising [B, S, V] logits.

    The fp32 logits pair (fwd activation + bwd cotangent) for a 50k vocab at
    B=4, S=2048 is ~3.3 GB of HBM — the round-1 single-chip memory ceiling.
    This computes the loss in sequence chunks under ``jax.checkpoint``: the
    forward keeps only per-chunk [B, chunk, V] logits transiently, and the
    backward recomputes each chunk's logits when it needs them, accumulating
    d(unembed_w) across chunks via the scan transpose. Numerics match
    ``next_token_loss`` (fp32 softmax, same masking) up to reduction order.
    """
    from ..parallel.sharding import constrain

    B, S, H = hidden.shape
    shift_h = hidden[:, :-1]
    shift_t = tokens[:, 1:]
    if segment_ids is not None:
        same_seg = segment_ids[:, 1:] == segment_ids[:, :-1]
        not_pad = segment_ids[:, 1:] != 0
        weights = (same_seg & not_pad).astype(jnp.float32)
    else:
        weights = jnp.ones((B, S - 1), jnp.float32)

    n = S - 1
    chunk = max(min(chunk, n), 1)
    pad = (-n) % chunk
    if pad:
        shift_h = jnp.pad(shift_h, ((0, 0), (0, pad), (0, 0)))
        shift_t = jnp.pad(shift_t, ((0, 0), (0, pad)))
        weights = jnp.pad(weights, ((0, 0), (0, pad)))
    nc = (n + pad) // chunk
    # [B, nc, chunk, ...] -> scan over nc
    h_c = shift_h.reshape(B, nc, chunk, H).transpose(1, 0, 2, 3)
    t_c = shift_t.reshape(B, nc, chunk).transpose(1, 0, 2)
    w_c = weights.reshape(B, nc, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def one_chunk(h, t, w):
        # On a mesh, pin what moves: the chunk's ROWS go to the weight's
        # vocabulary shards (gathered over fsdp), the weight, its gradient
        # and the logits stay. Targets and weights with the rows: left on
        # (dp, fsdp), the partitioner moved the logits to THEM at dp > 1.
        h, t, w = (constrain(x, "loss_rows") for x in (h, t, w))
        if tied:
            logits = jnp.einsum("bsh,vh->bsv", h, unembed_w.astype(h.dtype),
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("bsh,hv->bsv", h, unembed_w.astype(h.dtype),
                                preferred_element_type=jnp.float32)
        logits = constrain(logits.astype(jnp.float32), "loss_logits")
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t[..., None], axis=-1).squeeze(-1)
        nll = logz - tgt
        if z_loss_weight > 0.0:
            nll = nll + z_loss_weight * jnp.square(logz)
        return jnp.sum(nll * w), jnp.sum(w)

    def body(carry, xs):
        total, count = carry
        s, c = one_chunk(*xs)
        return (total + s, count + c), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (h_c, t_c, w_c))
    count = jnp.maximum(count, 1.0)
    return total / count, count


def perplexity(loss: jax.Array) -> jax.Array:
    return jnp.exp(loss)
