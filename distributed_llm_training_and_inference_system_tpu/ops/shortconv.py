"""Gated short convolution (``lfm2_moe``'s ``conv`` layers, the ``C`` kind):
the depthwise causal conv between the mixer's two gates, and where its
window lives.

A ``C`` mixer (models/layers.py ``shortconv_mixer``) projects the normed
stream to three parts ``[B | C | u]``, gates ``z = B * u``, and convolves
``z`` over time with one ``K``-tap filter a channel:

    y_t = sum_{j=0..K-1} w[j] * z_{t-(K-1)+j}        (zeros before position 0)

No bias, no activation, no matrix state: the layer's WHOLE state is the
``K - 1`` rows ``z_{t-K+1} .. z_{t-1}`` before the next token (K = 3: two
rows of ``hidden_size`` a slot a layer), cached in the cache's dtype.

``recur_window`` / ``recur_step`` / ``recur_chunk`` say where that window
lives, as ops/ssm.py's and ops/kda.py's do for their states (and under the
same names: serve/decode.py ``_RECURRENT``): from zeros over a window that
starts a sequence (a forward with no cache, cold prefill), in the engine's
pool ``[C layer, K-1, slot, H]`` (decode: whole tiles of [slots, H], as
ops/kda.py lays its own), or, for a window of ONE slot behind tokens it has
run already (a chunk of a prompt, the piece a decode step carries), from
that slot's own rows. A state is a 1-tuple here: the conv pool, or a
window's rows, alone.

The scopes (``shortconv_conv`` over a window, ``shortconv_step`` over the
pool) are what a device trace names these operations by; both lie inside
the mixer's ``shortconv_mixer``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .kda import _alive, _tail_after


def shortconv(z: jax.Array, kernel: jax.Array,
              tail: Optional[jax.Array] = None
              ) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal conv of K taps over z [B, S, H]: ``kernel`` [K, H];
    ``tail`` [B, K-1, H] holds the K-1 rows before the window (None: zeros,
    a sequence's start). Accumulated in float32. Returns (y [B, S, H] in
    z's dtype, padded [B, K-1+S, H]: tail and window, from which the caller
    cuts the next tail)."""
    B, S, H = z.shape
    K = kernel.shape[0]
    if tail is None:
        tail = jnp.zeros((B, K - 1, H), z.dtype)
    with jax.named_scope("shortconv_conv"):
        padded = jnp.concatenate([tail.astype(z.dtype), z], axis=1)
        acc = jnp.zeros((B, S, H), jnp.float32)
        for j in range(K):
            acc = acc + (padded[:, j:j + S].astype(jnp.float32)
                         * kernel[j].astype(jnp.float32))
        return acc.astype(z.dtype), padded


def recur_window(cfg, live: Optional[jax.Array] = None,
                 tail: Optional[jax.Array] = None):
    """``recur`` for a window that starts a sequence (a forward with no
    cache, cold prefill): the conv from a zero tail, or, given it, from the
    rows ``tail`` [B, K-1, H] before the window. The state returned is
    (the K-1 rows before position ``length`` [B, K-1, H],): padding
    (``live`` False, which follows the live tokens) never enters it."""
    K = cfg.shortconv_kernel

    def recur(z, p):
        y, padded = shortconv(z, p["conv"]["kernel"], tail)
        # (a window of fewer than K-1 live tokens keeps the tail's last)
        _, length = _alive(live, *z.shape[:2])
        return y, (_tail_after(padded, length, K),)
    return recur


def step_pools(z: jax.Array, p: dict, conv_pool: jax.Array, layer,
               write_ok: Optional[jax.Array]
               ) -> tuple[jax.Array, tuple[jax.Array]]:
    """One decode step of every slot over the pool ``conv_pool``
    [Lc, K-1, slots, H], read and written at ``[layer]`` (an int, or
    traced): what ``recur_step``'s ``recur`` does, with everything it reads
    an argument, so that a program's two step bodies and its ``C`` layers
    can call ONE jitted form of it (serve/decode.py)."""
    B, T, _ = z.shape
    if T != 1:
        raise ValueError(
            "every slot advances one token over the conv pool; a window of "
            f"{T} tokens a slot (speculative verification) is not "
            "supported: a prompt's window goes through recur_chunk, one "
            "slot at a time")
    with jax.named_scope("shortconv_step"):
        kernel = p["conv"]["kernel"].astype(jnp.float32)
        K = kernel.shape[0]
        tail = conv_pool[layer]                          # [K-1, slots, H]
        new = z[:, 0].astype(conv_pool.dtype)
        acc = new.astype(jnp.float32) * kernel[K - 1]
        for j in range(K - 1):
            acc = acc + tail[j].astype(jnp.float32) * kernel[j]
        new_tail = jnp.concatenate([tail[1:], new[None]], axis=0)
        if write_ok is not None:
            new_tail = jnp.where(write_ok.reshape(B)[None, :, None],
                                 new_tail, tail)
        return (acc.astype(z.dtype)[:, None],
                (conv_pool.at[layer].set(new_tail),))


def recur_step(cfg, conv_pool: jax.Array, layer,
               write_ok: Optional[jax.Array] = None, step=step_pools):
    """``recur`` for one decode step of every slot over the pool
    ``conv_pool`` [Lc, K-1, slots, H], read and written at ``[layer]``. A
    slot with ``write_ok`` [slots, 1] False (idle, or past its stop
    position) leaves its window as it is. Returns (the pool,) as the state.
    (``step``: a jitted ``step_pools``, where a program calls it from many
    places.)"""
    def recur(z, p):
        return step(z, p, conv_pool, layer, write_ok)
    return recur


def slot_state(conv_pool: jax.Array, slot: jax.Array, start: jax.Array
               ) -> tuple[jax.Array]:
    """ONE slot's rows of the pool in every ``C`` layer, read once before a
    window's layers run: (windows [Lc, K-1, H],), taken as ZERO where the
    window starts its sequence (``start`` [1] == 0: whatever a former
    occupant of the slot left there is not read). The sum over the slots
    of the pool masked to the one slot, not a slice: ops/kda.py
    ``slot_state`` says why."""
    mine = (jnp.arange(conv_pool.shape[2]) == slot)[:, None]
    tails = jnp.sum(jnp.where(mine, conv_pool, 0), axis=2,
                    dtype=conv_pool.dtype)
    return (jnp.where(start[0] == 0, 0, tails),)


def write_slot_state(conv_pool: jax.Array, slot: jax.Array,
                     tails: jax.Array, live) -> tuple[jax.Array]:
    """The pool with ``slot``'s rows of every ``C`` layer overwritten by a
    window's (windows [Lc, K-1, H]): ONE write, after the window's last
    layer, by a select over the whole pool (25 MB at the short-conv cell's
    shapes; ops/kda.py ``write_slot_state`` says why not a slice).
    ``live`` (bool []) False keeps the rows as the pool holds them (a
    decode step that carries no piece names slot 0)."""
    mine = (jnp.arange(conv_pool.shape[2]) == slot)[:, None] & live
    return (jnp.where(mine, tails.astype(conv_pool.dtype)[:, :, None],
                      conv_pool),)


def arm_slot_state(conv_pool: jax.Array, slot: jax.Array, tails: jax.Array
                   ) -> tuple[jax.Array]:
    """The pool with ``slot``'s rows of every ``C`` layer SET to a cold
    prefill's (windows [Lc, 1, K-1, H], from a zero tail), whatever a
    former occupant left there: a prompt shorter than K-1 tokens arms
    zeros before its own rows."""
    return (conv_pool.at[:, :, slot].set(
        tails[:, 0].astype(conv_pool.dtype)),)


def recur_chunk(cfg, tail: jax.Array, live: Optional[jax.Array] = None):
    """``recur`` for a window of ONE slot's prompt behind tokens the slot
    has run already (the piece a decode step carries, a chunk of a prompt:
    the window is [1, T]): the conv from the slot's cached rows ``tail``
    [K-1, H] (``slot_state``'s rows of this layer). The state it returns is
    (the rows [K-1, H] after the window's last live token,) (``live``
    [1, T], a prefix), which the caller writes back
    (``write_slot_state``)."""
    window = recur_window(cfg, live, tail[None])

    def recur(z, p):
        if z.shape[0] != 1:
            raise ValueError("a chunk is one slot's window: [1, T]")
        y, (new_tail,) = window(z, p)
        return y, (new_tail[0],)
    return recur
