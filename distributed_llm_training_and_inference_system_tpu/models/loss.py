"""Loss functions for causal LM training/eval.

Parity: the reference relies on HF's internal loss (labels=input_ids,
reference engine.py:206-215, :284). Implemented explicitly here: shifted
next-token cross-entropy in fp32 with padding masks and optional z-loss
(stabilises bf16 training at scale).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

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
    return cross_entropy(logits[:, :-1], tokens[:, 1:],
                         _target_weights(segment_ids), z_loss_weight)


def _target_weights(segment_ids: Optional[jax.Array]) -> Optional[jax.Array]:
    """[B, S - 1] 0/1: a target counts unless it starts a new segment or is
    padding (None without segment ids: every target counts)."""
    if segment_ids is None:
        return None
    same_seg = segment_ids[:, 1:] == segment_ids[:, :-1]
    not_pad = segment_ids[:, 1:] != 0
    return (same_seg & not_pad).astype(jnp.float32)


# The widest float32 slice of logits the loss's backward holds on a device at
# once: the vocabulary walk takes the fewest slices that stay under it (the
# forward's chunk of 2 x 512 rows x 92,544 columns is 0.38 GB).
SLICE_BYTES_LIMIT = 1 << 30
_LANES = 128


class LossBackwardPlan(NamedTuple):
    """Which axis the loss's backward walks, in what ONE device holds."""
    axis: str               # "vocabulary" or "rows"
    slices: int             # iterations of the loop
    width: int              # columns (vocabulary) or positions (rows) of one
    carry_bytes: int        # what the loop carries and rewrites, float32
    transient_bytes: int    # one iteration's float32 logits

    @property
    def cost_bytes(self) -> int:
        """The bytes the walk moves that its matmuls do not need: the carry,
        read and written once an iteration."""
        return self.carry_bytes * self.slices


def loss_backward_plans(rows: int, chunks: int, hidden: int, vocab: int,
                        shards: int = 1
                        ) -> tuple[LossBackwardPlan, Optional[LossBackwardPlan]]:
    """(the walk over rows, the walk over the vocabulary or None) for
    ``rows`` of ``hidden`` a device holds of the micro-batch, in ``chunks``
    forward chunks, against ``vocab`` columns over ``shards`` devices.

    Walking ROWS, every chunk touches the whole local head, so its float32
    gradient ``[hidden, vocab / shards]`` is the loop's carry, read and
    written once a chunk; across shards a chunk's rows are gathered and
    their gradient reduced inside the loop, beside the next chunk's matmuls.
    Walking the VOCABULARY, every slice touches all the rows, so their
    float32 gradient ``[rows, hidden]`` is the carry and a slice of the
    head's gradient is one matmul's output, written once. The columns are
    cut in the fewest equal slices whose logits stay under
    ``SLICE_BYTES_LIMIT``: of widths that are a multiple of the lane width
    if there is such a cut, else of any width of a lane or more (a single
    slice may have any); a vocabulary with no such cut has no such walk.
    Nor has one that is spread over ``shards`` devices: every shard would
    need all the rows before its loop and their gradient reduced behind it,
    with no matmul beside either (PR 48, ``fsdp=4`` at InternLM2's shapes:
    40.87 ms a micro-batch against the row walk's 39.40)."""
    local = vocab // shards
    by_rows = LossBackwardPlan("rows", chunks, rows // chunks,
                               hidden * local * 4,
                               rows // chunks * local * 4)
    if shards > 1:
        return by_rows, None

    def fits(s):
        return vocab % s == 0 and rows * (vocab // s) * 4 <= SLICE_BYTES_LIMIT
    cuts = [1, *range(2, vocab // _LANES + 1)]
    slices = (next((s for s in cuts if fits(s)
                    and (s == 1 or vocab // s % _LANES == 0)), None)
              or next((s for s in cuts if fits(s)), None))
    if slices is None:
        return by_rows, None
    return by_rows, LossBackwardPlan(
        "vocabulary", slices, vocab // slices, rows * hidden * 4,
        rows * (vocab // slices) * 4)


def plan_loss_backward(**shapes) -> LossBackwardPlan:
    """The walk with the smaller ``cost_bytes``, from
    ``loss_backward_plans``' shapes alone."""
    by_rows, by_vocab = loss_backward_plans(**shapes)
    if by_vocab is None:
        return by_rows
    return min(by_vocab, by_rows, key=lambda plan: plan.cost_bytes)


def chunked_loss_backward_plan(batch: int, seq: int, hidden: int, vocab: int,
                               chunk: int = 512) -> LossBackwardPlan:
    """The walk ``chunked_next_token_loss`` gives its backward for a
    ``[batch, seq, hidden]`` micro-batch against ``vocab`` columns, on the
    ambient mesh: what a trainer records of the step it is about to trace."""
    from ..parallel.sharding import shard_counts
    chunk = max(min(chunk, seq - 1), 1)
    positions = -(-(seq - 1) // chunk) * chunk
    row_shards = math.prod(shard_counts("loss_rows", (batch, chunk, hidden)))
    return plan_loss_backward(
        rows=batch * positions // row_shards, chunks=positions // chunk,
        hidden=hidden, vocab=vocab,
        shards=shard_counts("loss_logits", (batch, chunk, vocab))[2])


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

    The forward walks the ROWS in chunks of ``chunk`` positions: a chunk's
    float32 logits ``[B, chunk, V]`` live for one iteration, and of all of
    them only each row's ``logsumexp`` is kept (``f32[B, S - 1]``). The
    backward is written by hand (a ``jax.custom_vjp`` over ``hidden`` and
    ``unembed_w``): it recomputes logits a piece at a time from the saved
    rows, weight and ``logsumexp`` and walks whichever axis rewrites fewer
    bytes (``chunked_loss_backward_plan``). With a large vocabulary whole on
    the device that is the VOCABULARY: a slice of d(unembed_w) leaves one
    matmul in float32 and is written once, and the loop carries the rows'
    float32 gradient; walking rows carries the whole float32 d(unembed_w)
    through every chunk. A vocabulary spread over devices walks rows, each
    device against its own columns, so a sharded weight stays where it is.
    Numerics match ``next_token_loss`` (fp32 logits and softmax from the
    operands' dtype, same masking and z-loss) up to summation order.
    """
    B, S, H = hidden.shape
    n = S - 1
    chunk = max(min(chunk, n), 1)
    pad = (-n) % chunk
    weights = _target_weights(segment_ids)
    if weights is None:
        weights = jnp.ones((B, n), jnp.float32)
    targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, pad)))
    weights = jnp.pad(weights, ((0, 0), (0, pad)))
    plan = chunked_loss_backward_plan(
        B, S, H, unembed_w.shape[0 if tied else 1], chunk)
    return _chunked_loss(hidden, unembed_w, targets, weights,
                         z_loss_weight, chunk, tied, plan)


def _padded_rows(hidden: jax.Array, positions: int) -> jax.Array:
    """The rows that predict a target, ``[B, positions, H]``: all but a
    sequence's last, zeros behind them."""
    rows = hidden[:, :-1]
    return jnp.pad(rows, ((0, 0), (0, positions - rows.shape[1]), (0, 0)))


def _by_chunk(x: jax.Array, chunk: int) -> jax.Array:
    """[B, positions, ...] -> [chunks, B, chunk, ...]: what a scan walks."""
    B, positions = x.shape[:2]
    return jnp.moveaxis(
        x.reshape(B, positions // chunk, chunk, *x.shape[2:]), 1, 0)


def _from_chunks(x: jax.Array) -> jax.Array:
    """[chunks, B, chunk, ...] -> [B, positions, ...]."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape(x.shape[0], -1, *x.shape[3:])


def _chunk_logits(h: jax.Array, w: jax.Array, tied: bool) -> jax.Array:
    from ..parallel.sharding import constrain
    logits = jnp.einsum("bsh,vh->bsv" if tied else "bsh,hv->bsv", h, w,
                        preferred_element_type=jnp.float32)
    return constrain(logits, "loss_logits")


def _dlogits(z, logz, onehot, scale, z_loss_weight: float):
    """d(loss) / d(logits) of one piece, float32: ``z`` its logits, ``logz``
    and ``scale`` (weight x cotangent / count) a row's, broadcast over it."""
    p = jnp.exp(z - logz)
    if z_loss_weight > 0.0:
        p = p * (1.0 + 2.0 * z_loss_weight * logz)
    return (p - onehot) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _chunked_loss(hidden, unembed_w, targets, weights, z_loss_weight, chunk,
                  tied, plan):
    return _chunked_loss_fwd(hidden, unembed_w, targets, weights,
                             z_loss_weight, chunk, tied, plan)[0]


def _chunked_loss_fwd(hidden, unembed_w, targets, weights, z_loss_weight,
                      chunk, tied, plan):
    from ..parallel.sharding import constrain
    w_lo = unembed_w.astype(hidden.dtype)
    rows = _padded_rows(hidden, targets.shape[1])

    def body(carry, xs):
        total, count = carry
        # On a mesh, pin what moves: the chunk's ROWS go to the weight's
        # vocabulary shards (gathered over fsdp), the weight and the logits
        # stay. Targets and weights with the rows: left on (dp, fsdp), the
        # partitioner moved the logits to THEM at dp > 1.
        h, t, w = (constrain(x, "loss_rows") for x in xs)
        logits = _chunk_logits(h, w_lo, tied)
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t[..., None], axis=-1).squeeze(-1)
        nll = logz - tgt
        if z_loss_weight > 0.0:
            nll = nll + z_loss_weight * jnp.square(logz)
        return (total + jnp.sum(nll * w), count + jnp.sum(w)), logz

    (total, count), logz = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)),
        tuple(_by_chunk(x, chunk) for x in (rows, targets, weights)))
    count = jnp.maximum(count, 1.0)
    residuals = (hidden, unembed_w, targets, weights, _from_chunks(logz),
                 count)
    return (total / count, count), residuals


def _chunked_loss_bwd(z_loss_weight, chunk, tied, plan, residuals, cotangents):
    hidden, unembed_w, targets, weights, logz, count = residuals
    g, _ = cotangents           # the count has no gradient
    with jax.named_scope("chunked_loss_bwd"):
        rows = _padded_rows(hidden, targets.shape[1])
        w_lo = unembed_w.astype(hidden.dtype)
        scale = weights * (g / count)
        if plan.axis == "vocabulary":
            d_rows, d_w = _walk_vocabulary(rows, w_lo, targets, logz, scale,
                                           z_loss_weight, tied, plan)
        else:
            d_rows, d_w = _walk_rows(rows, w_lo, targets, logz, scale,
                                     z_loss_weight, tied, chunk)
        n = hidden.shape[1] - 1
        d_hidden = jnp.pad(d_rows[:, :n], ((0, 0), (0, 1), (0, 0)))
    return (d_hidden.astype(hidden.dtype), d_w.astype(unembed_w.dtype),
            None, None)


_chunked_loss.defvjp(_chunked_loss_fwd, _chunked_loss_bwd)


def _walk_rows(rows, w_lo, targets, logz, scale, z_loss_weight, tied, chunk):
    """The backward a chunk of rows at a time: ``(d_rows [B, positions, H],
    d_w float32)``. The carry is the whole (local) d_w."""
    from ..parallel.sharding import constrain
    columns = jnp.arange(w_lo.shape[0 if tied else 1], dtype=targets.dtype)

    def body(d_w, xs):
        h, t, lz, sc = (constrain(x, "loss_rows") for x in xs)
        dz = _dlogits(_chunk_logits(h, w_lo, tied), lz[..., None],
                      t[..., None] == columns, sc[..., None],
                      z_loss_weight).astype(h.dtype)
        d_w = d_w + jnp.einsum("bsv,bsh->vh" if tied else "bsv,bsh->hv",
                               dz, h, preferred_element_type=jnp.float32)
        d_h = jnp.einsum("bsv,vh->bsh" if tied else "bsv,hv->bsh", dz, w_lo,
                         preferred_element_type=jnp.float32)
        return d_w, d_h.astype(h.dtype)

    d_w, d_rows = jax.lax.scan(
        body, jnp.zeros(w_lo.shape, jnp.float32),
        tuple(_by_chunk(x, chunk) for x in (rows, targets, logz, scale)))
    return _from_chunks(d_rows), d_w


def _walk_vocabulary(rows, w_lo, targets, logz, scale, z_loss_weight, tied,
                     plan):
    """The backward a slice of the vocabulary at a time, over all the rows:
    ``(d_rows float32, d_w float32)``. A slice of d_w is the loop's output;
    the carry is the rows' gradient."""
    from ..parallel.sharding import constrain
    H = rows.shape[-1]
    C, width = plan.slices, plan.width
    rows, targets, logz, scale = (
        constrain(x, "loss_rows") for x in (rows, targets, logz, scale))
    if tied:        # [V, H] -> [slices, width, H]
        by_slice = w_lo.reshape(C, width, H)
        logits_of, d_w_of, d_rows_of = (
            "bnh,wh->bnw", "bnw,bnh->wh", "bnw,wh->bnh")
    else:           # [H, V] -> [slices, H, width]
        by_slice = jnp.moveaxis(w_lo.reshape(H, C, width), 1, 0)
        logits_of, d_w_of, d_rows_of = (
            "bnh,hw->bnw", "bnw,bnh->hw", "bnw,hw->bnh")
    columns = jnp.arange(width, dtype=targets.dtype)     # of slice 0
    per_row = tuple(x[..., None] for x in (targets, logz, scale))

    def body(d_rows, xs):
        w_c, c = xs
        z = constrain(jnp.einsum(logits_of, rows, w_c,
                                 preferred_element_type=jnp.float32),
                      "loss_logits")
        t, lz, sc = per_row
        dz = _dlogits(z, lz, t == columns + c * width, sc,
                      z_loss_weight).astype(rows.dtype)
        d_w_c = jnp.einsum(d_w_of, dz, rows,
                           preferred_element_type=jnp.float32)
        d_rows = d_rows + jnp.einsum(d_rows_of, dz, w_c,
                                     preferred_element_type=jnp.float32)
        return constrain(d_rows, "loss_rows"), d_w_c

    d_rows, d_w = jax.lax.scan(
        body, constrain(jnp.zeros(rows.shape, jnp.float32), "loss_rows"),
        (by_slice, jnp.arange(C, dtype=targets.dtype)))
    # lay the slices back: [slices, width, H] or [slices, H, width] -> the
    # weight's
    if not tied:
        d_w = jnp.moveaxis(d_w, 0, 1)
    return d_rows, d_w.reshape(w_lo.shape)


def perplexity(loss: jax.Array) -> jax.Array:
    return jnp.exp(loss)
