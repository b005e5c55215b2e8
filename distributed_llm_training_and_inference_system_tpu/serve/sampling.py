"""Batched, jit-compatible token sampling.

Parity: the reference samples per request in a Python loop on the host —
temperature, top-k, top-p, multinomial (reference serve/server.py:209-235).
Here the whole batch is sampled in one traced function on device: every
request carries its own (temperature, top_k, top_p, key) and the math is
vectorised — no data-dependent Python control flow (XLA requirement).

temperature == 0 means greedy (argmax). Per-ROW selection stays
jnp.where (rows can't branch); whole-BATCH tier selection is lax.cond.

Cost structure (round 5): the top-k and top-p filters each need the
row's sort order, and a [B, V] sort at V=50304 is VPU-heavy — it runs
INSIDE every iteration of the K-step decode scan. Three tiers keep the
common cases off that path, chosen by ``lax.cond`` on whole-batch
predicates (loop-invariant in the decode scan; XLA conditionals execute
ONE branch at runtime, and the predicates are known at dispatch time):

  all rows greedy          -> argmax only (zero sampling machinery)
  no row filters           -> Gumbel categorical, no sort
  any row filters          -> lax.top_k over FILTER_FAST_CAP candidates
                              (round 6 — the full-vocab argsort measured
                              7.0 ms/step at [8, 50304]); the shared
                              argsort remains as the lax.cond'd exact
                              fallback when the kept set could reach
                              past the candidates

The filtered path is equivalent to filtering per-filter: top-k keeps
``logits >= kth`` (ties included) exactly as before, and top-p's
cumulative cut sees the same kept-entry order — masked entries land in
the tail with ~0 probability either way, so the kept sets, and
therefore the sampled tokens, are unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..models.layers import NEG_INF


_U32 = 0xFFFFFFFF
_THREEFRY_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def seed_key_data(seed: int) -> np.ndarray:
    """uint32[2]: the key data of ``jax.random.PRNGKey(seed)`` under the
    threefry implementation, computed on the host: the seed's high and low
    32-bit words. Without ``jax_enable_x64`` JAX narrows the seed to 32
    bits first, so the high word is 0 (also for a negative or an over-wide
    seed). A key made this way costs the device no program and the caller
    no fetch; ``tests/test_slot_keys.py`` holds it to JAX's own, bit for
    bit."""
    seed = int(seed)
    high = seed >> 32 if jax.config.jax_enable_x64 else 0
    return np.array([high & _U32, seed & _U32], np.uint32)


def fold_in_key_data(key: np.ndarray, n: int) -> np.ndarray:
    """uint32[2]: the key data of ``jax.random.fold_in(key, n)`` under the
    threefry implementation, computed on the host in Python integers:
    Threefry-2x32 (20 rounds) of the count ``[0, n]`` under ``key``, as
    ``jax._src.prng.threefry_fold_in`` computes it on the device. ~7 us,
    against two one-operation programs on the device and the engine
    thread's walk between them (PERF.md 6, PR 32); the decode programs
    fold a position into the same slot key themselves
    (``serve/decode.py``), so the two derivations must agree bit for bit:
    ``tests/test_slot_keys.py`` holds this one to JAX's over a table of
    seeds and lengths."""
    k0, k1 = int(key[0]), int(key[1])
    ks = (k0, k1, k0 ^ k1 ^ 0x1BD11BDA)
    x0, x1 = k0, (int(n) + k1) & _U32
    for i in range(5):
        for r in _THREEFRY_ROTATIONS[i % 2]:
            x0 = (x0 + x1) & _U32
            x1 = ((x1 << r) | (x1 >> (32 - r))) & _U32
            x1 ^= x0
        x0 = (x0 + ks[(i + 1) % 3]) & _U32
        x1 = (x1 + ks[(i + 2) % 3] + i + 1) & _U32
    return np.array([x0, x1], np.uint32)


def _apply_top_k(logits: jax.Array, top_k: jax.Array) -> jax.Array:
    """Mask logits outside each row's top-k. top_k<=0 disables. [B,V].

    top_k <= 0 disabled matches the reference/ecosystem convention
    (reference serve/server.py defaults top_k=-1 and checks top_k>0).
    """
    V = logits.shape[-1]
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]                  # [B,V]
    k = jnp.clip(top_k, 1, V)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=1)  # [B,1]
    keep = (logits >= kth) | (top_k[:, None] <= 0)
    return jnp.where(keep, logits, NEG_INF)


def _apply_top_p(logits: jax.Array, top_p: jax.Array) -> jax.Array:
    """Nucleus filtering per row; top_p>=1 disables. [B,V]."""
    sort_idx = jnp.argsort(logits, axis=-1)[:, ::-1]
    sorted_logits = jnp.take_along_axis(logits, sort_idx, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep tokens until cumulative prob exceeds p; the top token is always
    # kept so top_p=0 degrades to greedy instead of masking everything
    keep_sorted = ((cum - probs) < top_p[:, None]).at[:, 0].set(True)
    keep = jnp.zeros_like(keep_sorted).at[
        jnp.arange(logits.shape[0])[:, None], sort_idx].set(keep_sorted)
    keep = keep | (top_p[:, None] >= 1.0)
    return jnp.where(keep, logits, NEG_INF)


def _filtered_single_sort(scaled: jax.Array, top_k: jax.Array,
                          top_p: jax.Array) -> jax.Array:
    """top-k then top-p filtering from ONE argsort of the scaled logits.

    Equivalent to ``_apply_top_p(_apply_top_k(scaled, top_k), top_p)``:
    top-k's mask only moves non-kept entries to NEG_INF, which preserves
    the descending order of kept entries, so top-p's cumulative scan
    sees the same prefix; masked entries carry ~0 probability wherever
    they sort. One sort instead of two — this path only runs when some
    row actually has a filter (see sample_tokens).
    """
    B, V = scaled.shape
    rows = jnp.arange(B)[:, None]
    sort_idx = jnp.argsort(scaled, axis=-1)[:, ::-1]
    sorted_desc = jnp.take_along_axis(scaled, sort_idx, axis=-1)

    k = jnp.clip(top_k, 1, V)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=1)
    keep_k = (sorted_desc >= kth) | (top_k[:, None] <= 0)   # ties included

    masked_sorted = jnp.where(keep_k, sorted_desc, NEG_INF)
    probs = jax.nn.softmax(masked_sorted, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_p = ((cum - probs) < top_p[:, None]).at[:, 0].set(True)
    keep_p = keep_p | (top_p[:, None] >= 1.0)

    keep = jnp.zeros((B, V), bool).at[rows, sort_idx].set(keep_k & keep_p)
    return jnp.where(keep, scaled, NEG_INF)


FILTER_FAST_CAP = 256
"""Candidate width of the ``lax.top_k`` fast filter tier.

The filtered tier's full-vocab ``jnp.argsort`` measured 7.0 ms/step at
[8, 50304] (round-5 verdict #4) — inside every iteration of the K-step
decode scan. Real requests ask top_k <= 64 and top-p mass concentrates
in a few hundred tokens, so a 256-candidate ``lax.top_k`` (O(V) scan vs
O(V log V) sort) covers the kept set; the argsort path stays as the
exact fallback, selected per batch by ``lax.cond`` whenever the kept
set could extend beyond the candidates (large top_k, boundary value
ties, or a top-p whose mass is not reached within the candidates)."""


def _filtered_fast_or_exact(scaled: jax.Array, top_k: jax.Array,
                            top_p: jax.Array) -> jax.Array:
    """Filtered logits via top-CAP candidates, with the single-sort path
    as a ``lax.cond`` fallback. Produces the SAME kept set as
    ``_filtered_single_sort`` (asserted bitwise on the tie tests): the
    candidate list is re-ordered to the argsort path's exact tie order
    (descending value, ties descending token index) before the top-k /
    top-p cuts, and any batch whose cuts could reach beyond — or tie
    with — the candidate boundary takes the exact path instead.
    """
    B, V = scaled.shape
    cap = FILTER_FAST_CAP
    if V <= cap + 1:             # static: small vocabs just sort
        return _filtered_single_sort(scaled, top_k, top_p)
    rows = jnp.arange(B)[:, None]
    vals, idx = jax.lax.top_k(scaled, cap + 1)
    sentinel = vals[:, cap]                     # largest EXCLUDED value
    cvals, cidx = vals[:, :cap], idx[:, :cap]

    # reconstruct the argsort tie order within the candidates: arrange by
    # token index ascending, stable-sort ascending by value (ties keep
    # ascending index), reverse -> descending value, ties descending index
    perm = jnp.argsort(cidx, axis=-1)
    v1 = jnp.take_along_axis(cvals, perm, axis=-1)
    i1 = jnp.take_along_axis(cidx, perm, axis=-1)
    order = jnp.argsort(v1, axis=-1)[:, ::-1]
    svals = jnp.take_along_axis(v1, order, axis=-1)     # [B, cap]
    sidx = jnp.take_along_axis(i1, order, axis=-1)

    k_active = top_k > 0
    p_active = top_p < 1.0
    k = jnp.clip(top_k, 1, V)
    kth = jnp.take_along_axis(
        svals, jnp.minimum(k - 1, cap - 1)[:, None], axis=1)    # [B, 1]
    keep_k = (svals >= kth) | ~k_active[:, None]

    # probabilities under the SAME masked softmax as the exact path:
    # denominator over the kept candidates when top-k masks the tail,
    # over the full row when top-k is disabled (the tail carries mass)
    m = svals[:, :1]                                    # row max
    exps = jnp.where(keep_k, jnp.exp(svals - m), 0.0)
    z_kept = jnp.sum(exps, axis=1, keepdims=True)
    z_full = jnp.sum(jnp.exp(scaled - m), axis=1, keepdims=True)
    z = jnp.where(k_active[:, None], z_kept, z_full)
    probs = exps / z
    cum = jnp.cumsum(probs, axis=1)
    keep_p = ((cum - probs) < top_p[:, None]).at[:, 0].set(True)
    keep_p = keep_p | (top_p[:, None] >= 1.0)
    keep_c = keep_k & keep_p

    filtered_row = k_active | p_active
    dirty = (
        # top-k cut beyond (or tied with) the candidate boundary: the
        # full-vocab tie set at kth is not visible here
        (k_active & ((k > cap) | (kth[:, 0] <= sentinel)))
        # top-p mass not reached within the candidates
        | (~k_active & p_active
           & ((cum[:, -1] - probs[:, -1]) < top_p))
        # kept set touches a value the excluded tail ties with
        | (filtered_row & jnp.any(keep_c & (svals <= sentinel[:, None]),
                                  axis=1)))
    need_exact = jnp.any(dirty & filtered_row)

    keep = jnp.zeros((B, V), bool).at[rows, sidx].set(keep_c)
    fast = jnp.where(keep | ~filtered_row[:, None], scaled, NEG_INF)
    return jax.lax.cond(
        need_exact,
        lambda _: _filtered_single_sort(scaled, top_k, top_p),
        lambda _: fast,
        None)


@jax.named_scope("sample_tokens")
def sample_tokens(
    logits: jax.Array,       # [B, V] fp32
    keys: jax.Array,         # [B] PRNG keys (uint32[2] each)
    temperature: jax.Array,  # [B] fp32; 0 = greedy
    top_k: jax.Array,        # [B] int32; 0 = disabled
    top_p: jax.Array,        # [B] fp32; 1.0 = disabled
) -> jax.Array:
    """Return sampled token ids [B] int32."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    is_sampled = temperature > 0.0

    def greedy_only(_):
        return greedy

    def sampled(_):
        temp = jnp.maximum(temperature, 1e-6)[:, None]
        scaled = logits / temp

        def unfiltered(_):
            return scaled

        def filtered(_):
            return _filtered_fast_or_exact(scaled, top_k, top_p)

        # the filter sort only runs when a SAMPLED row asks for it —
        # greedy rows' filter knobs are irrelevant to their argmax
        any_filter = jnp.any(is_sampled
                             & ((top_k > 0) | (top_p < 1.0)))
        row = jax.lax.cond(any_filter, filtered, unfiltered, None)
        toks = jax.vmap(
            lambda key, r: jax.random.categorical(key, r))(keys, row)
        return jnp.where(is_sampled, toks.astype(jnp.int32), greedy)

    return jax.lax.cond(jnp.any(is_sampled), sampled, greedy_only, None)


def sample_tokens_with_prob(logits, keys, temperature, top_k, top_p
                            ) -> tuple[jax.Array, jax.Array]:
    """``sample_tokens`` and, beside each token, the probability the
    model gives it: softmax(logits)[token], of the logits as they came
    (not scaled by the temperature, not filtered), in float32. What a
    model that generates by diffusion over blocks ranks its masked rows
    by (``transfer_rows``)."""
    tokens = sample_tokens(logits, keys, temperature, top_k, top_p)
    logits = logits.astype(jnp.float32)
    chosen = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return tokens, jnp.exp(chosen - jax.nn.logsumexp(logits, axis=-1))


def transfer_rows(prob: jax.Array, masked: jax.Array, wanted: jax.Array,
                  strategy: str, threshold: float
                  ) -> tuple[jax.Array, jax.Array]:
    """Which masked rows of each window a denoise step FIXES: (fix
    [B, Bd] bool, beyond [B] int32: rows fixed over the schedule's count).
    ``prob`` [B, Bd] is each row's confidence, ``masked`` [B, Bd] the rows
    still to fix, ``wanted`` [B] the schedule's count for this step (never
    more than are masked: a first window whose leading rows are the
    prompt's has fewer).

    - ``sequential``: the leftmost ``wanted`` masked rows.
    - ``low_confidence_static``: the ``wanted`` masked rows of largest
      confidence (a tie goes to the row further left).
    - ``low_confidence_dynamic``: every masked row whose confidence is
      over ``threshold`` if those are at least ``wanted``, else as
      ``low_confidence_static``."""
    n = jnp.minimum(wanted, jnp.sum(masked, axis=-1))[:, None]
    if strategy == "sequential":
        rank = jnp.cumsum(masked, axis=-1) - 1
    else:
        conf = jnp.where(masked, prob, -jnp.inf)
        col = jnp.arange(conf.shape[-1])
        ahead = (conf[:, None, :] > conf[:, :, None]) | (
            (conf[:, None, :] == conf[:, :, None])
            & (col[None, None, :] < col[None, :, None]))
        rank = jnp.sum(ahead, axis=-1)          # rows ranked before row i
    fix = masked & (rank < n)
    if strategy == "low_confidence_dynamic":
        high = masked & (prob > threshold)
        fix = jnp.where(jnp.sum(high, axis=-1, keepdims=True) >= n, high, fix)
    return fix, (jnp.sum(fix, axis=-1) - n[:, 0]).astype(jnp.int32)
