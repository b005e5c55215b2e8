"""Speculative decoding: host n-gram drafts, device verification.

The reference generates strictly one token per forward pass per request
(reference serve/server.py:199-249). Decode on TPU is HBM-bandwidth-bound on
*weights* — streaming the params through the MXU for 1 token costs nearly
the same as for 8 — so scoring a window of draft tokens in one pass makes
accepted tokens almost free (vLLM/Medusa-style speculation, TPU-shaped:
static window T, no dynamic shapes).

Draft source is **prompt-lookup (n-gram)**: the most recent earlier
occurrence of the context's trailing n-gram proposes the following tokens.
No draft model, no extra weights; it shines on grounded/extractive
workloads (summarisation, code edit, RAG) where the output re-uses prompt
spans.

Correctness does not depend on draft quality: a draft token j is accepted
iff it equals the argmax of the verified logits at its position, so every
emitted greedy stream is a valid greedy chain under the verify-pass logits
(each token is the argmax of logits conditioned on the accepted prefix;
tested in tests/test_speculative.py, bitwise vs plain decode on CPU fp32).
On TPU bf16 the [B,T,H] verify projections may tile/accumulate differently
from the [B,1,H] decode shapes, so a low-bit logit diff can, in principle,
flip an argmax at near-ties — the chain remains self-consistent either
way. Sampled (temperature > 0) requests
in the same batch fall back to one verified token per dispatch — the
engine only routes to the speculative path when a greedy request is
resident. Rejected drafts leave stale KV beyond the accepted position;
that is invisible (reads are length-masked) and overwritten as the slot
advances.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config.schema import ModelConfig
from .decode import DispatchResult, extend_step_forward
from .sampling import sample_tokens

# SpecState tuning constants — deterministic, test-pinned. The EWMA
# weights recent dispatches (a sequence's acceptance drifts as it moves
# from grounded prompt-copying into free generation); the warmup floor
# keeps one lucky/unlucky first window from whipsawing the window; the
# grow/shrink thresholds bracket a ~50% acceptance break-even (not
# measured on the attached chip: no cell runs n-gram drafts; the cell that
# speculates drafts with the model's own prediction module, serve/decode.py
# ``draft_verify_scan``, whose break-even PERF.md has).
SPEC_EWMA_ALPHA = 0.25
SPEC_WARMUP_DISPATCHES = 4
SPEC_GROW_AT = 0.5
SPEC_SHRINK_AT = 0.15
SPEC_MIN_WINDOW = 2


@dataclass
class SpecState:
    """Per-SEQUENCE speculative-decode state: the tuned part of a
    sequence's speed that used to die at every migration / prefill->
    decode handoff boundary (the engine's counters are engine-global;
    a re-placed sequence cold-started its proposer and its window).

    This is a courier citizen: ``to_dict``/``from_dict`` round-trip
    through the migration payload manifest (plain scalars — they ride
    the existing chunked/CRC transport for free) and through the remote
    worker submit wire, so a disaggregated decode replica resumes
    speculating at the source's tuned window instead of re-learning it.

    Fields:
    - ``window``: current adaptive verify window (first position is the
      root token, so ``window - 1`` drafts are proposed per dispatch);
      clamped to [SPEC_MIN_WINDOW, ServeConfig.speculative_tokens].
    - ``ewma``: recent draft-acceptance EWMA driving the adaptation.
    - ``warmup``: spec dispatches observed — the n-gram proposer warmup
      (the window doesn't move until the EWMA has seen a few windows).
    - ``drafts``/``accepted``: lifetime per-sequence acceptance totals
      (migrate with the sequence; the per-replica counters stay local).
    """
    window: int
    ewma: float = 0.0
    warmup: int = 0
    drafts: int = 0
    accepted: int = 0

    def observe(self, accepted: int, drafted: int,
                max_window: int) -> None:
        """Fold one dispatch's acceptance into the EWMA and adapt the
        window (deterministic: same observations -> same window, on any
        replica)."""
        drafted = max(int(drafted), 1)
        accepted = min(max(int(accepted), 0), drafted)
        self.drafts += drafted
        self.accepted += accepted
        rate = accepted / drafted
        if self.warmup == 0:
            self.ewma = rate
        else:
            self.ewma = ((1.0 - SPEC_EWMA_ALPHA) * self.ewma
                         + SPEC_EWMA_ALPHA * rate)
        self.warmup += 1
        if self.warmup >= SPEC_WARMUP_DISPATCHES:
            if self.ewma >= SPEC_GROW_AT:
                self.window = min(self.window + 1, max_window)
            elif self.ewma <= SPEC_SHRINK_AT:
                self.window = max(self.window - 1, SPEC_MIN_WINDOW)

    def to_dict(self) -> dict:
        return {"window": int(self.window), "ewma": float(self.ewma),
                "warmup": int(self.warmup), "drafts": int(self.drafts),
                "accepted": int(self.accepted)}

    @classmethod
    def from_dict(cls, d: dict, max_window: int) -> "SpecState":
        """Rebuild from a migrated dict; malformed/foreign values clamp
        into range rather than poisoning the destination's dispatch
        shapes (the window bounds tokens[] writes)."""
        try:
            window = int(d.get("window", max_window))
        except (TypeError, ValueError):
            window = max_window
        window = max(SPEC_MIN_WINDOW, min(window, max_window))
        try:
            ewma = float(d.get("ewma", 0.0))
        except (TypeError, ValueError):
            ewma = 0.0

        def _i(key):
            try:
                return max(int(d.get(key, 0)), 0)
            except (TypeError, ValueError):
                return 0
        return cls(window=window, ewma=min(max(ewma, 0.0), 1.0),
                   warmup=_i("warmup"), drafts=_i("drafts"),
                   accepted=_i("accepted"))


def propose_ngram_draft(
    context: np.ndarray,     # 1-D int array: prompt + generated so far
    num_draft: int,
    max_ngram: int = 3,
) -> Optional[np.ndarray]:
    """Prompt-lookup proposal: find the most recent *earlier* occurrence of
    the context's trailing n-gram (longest n first) and return the
    ``num_draft`` tokens that followed it. None when nothing matches."""
    L = len(context)
    if L < 2 or num_draft < 1:
        return None
    for n in range(min(max_ngram, L - 1), 0, -1):
        tail = context[L - n:]
        # windows[i] == context[i : i+n]; search the latest i < L - n
        windows = np.lib.stride_tricks.sliding_window_view(context, n)
        hits = np.flatnonzero((windows[: L - n] == tail).all(axis=1))
        if hits.size == 0:
            continue
        start = int(hits[-1]) + n          # first token after the match
        draft = context[start:start + num_draft]
        if draft.size == 0:
            continue
        if draft.size < num_draft:         # pad by repeating the last token
            draft = np.concatenate(
                [draft, np.full(num_draft - draft.size, draft[-1],
                                draft.dtype)])
        return draft.astype(np.int32)
    return None


def speculative_verify(
    params: Any,
    tokens: jax.Array,          # [B, T]: [last_token, draft_1..draft_{T-1}]
    positions: jax.Array,       # [B] position of tokens[:, 0]
    k_pages: jax.Array,         # [L, NP, Nkv, PS, D] (donated)
    v_pages: jax.Array,
    block_tables: jax.Array,    # [B, maxP]
    stop_positions: jax.Array,  # [B] first un-writable position
    slot_keys: jax.Array,       # [B, 2] uint32 key data
    temperature: jax.Array,     # [B]; <= 0 marks the greedy (verifiable) rows
    top_k: jax.Array,
    top_p: jax.Array,
    cfg: ModelConfig,
    attn_impl: str = "auto",
    w4_kernel_ok: bool = True,
    w8_kernel_ok: bool = False,
) -> DispatchResult:
    """One verification pass. Returns a ``DispatchResult`` whose ``sampled``
    is (emitted [B, T], n_emit [B]), with the new pools.

    Row semantics:
    - greedy row: emitted[:n_emit] = argmax chain; n_emit = accepted + 1
      (the bonus token from the first unverified position).
    - sampled row: emitted[0] is sampled from the logits of tokens[:, 0]
      exactly like one plain decode step (same key fold); n_emit = 1.

    The host must advance positions by the number of tokens it actually
    records so the slot's length matches the KV the device wrote.
    """
    B, T = tokens.shape
    offs = jnp.arange(T, dtype=jnp.int32)
    write_ok = (positions[:, None] + offs) < stop_positions[:, None]
    step = extend_step_forward(
        params, tokens, positions, k_pages, v_pages, block_tables, cfg,
        write_ok=write_ok, attn_impl=attn_impl,
        w4_kernel_ok=w4_kernel_ok, w8_kernel_ok=w8_kernel_ok)
    logits = step.logits

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # [B, T]
    is_greedy = temperature <= 0.0
    match = (tokens[:, 1:] == greedy[:, :-1]) & is_greedy[:, None]
    accepted = jnp.cumprod(match.astype(jnp.int32), axis=1)    # [B, T-1]
    n_acc = accepted.sum(axis=1)                               # [B]

    keys = jax.vmap(jax.random.fold_in)(
        jax.vmap(jax.random.wrap_key_data)(slot_keys), positions + 1)
    sampled0 = sample_tokens(logits[:, 0], keys, temperature, top_k, top_p)

    emitted = jnp.where(is_greedy[:, None], greedy,
                        jnp.broadcast_to(sampled0[:, None], (B, T)))
    n_emit = jnp.where(is_greedy, n_acc + 1, 1).astype(jnp.int32)
    return DispatchResult((emitted, n_emit), k_pages=step.k_pages,
                          v_pages=step.v_pages)


def verify_and_decode(
    params: Any,
    tokens: jax.Array,          # [B, T] verify window (last token + drafts)
    positions: jax.Array,       # [B]
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    stop_positions: jax.Array,
    slot_keys: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
    cfg: ModelConfig,
    num_decode_steps: int,
    attn_impl: str = "auto",
    w4_kernel_ok: bool = True,
    w8_kernel_ok: bool = False,
) -> DispatchResult:
    """Fused dispatch: one verification window + ``num_decode_steps`` plain
    decode iterations, all on device.

    Why fused: a verify-only dispatch yields avg ``acceptance*(T-1) + 1``
    tokens per host round trip, which at low acceptance loses to multi-step
    decode's guaranteed K. Chaining R decode steps after the verify makes
    every dispatch yield ``n_acc + 1 + R`` tokens for ``1 + R`` forward
    passes. The verify forward is not free (a T-token window write and
    per-query prefix streaming), so below some acceptance this still
    trails plain multi-step decode — the engine's adaptive check
    (speculative_min_acceptance) exists for exactly that. Where the
    crossover lies is not measured on the attached chip: no cell runs
    n-gram drafts (the self-drafting cell's window of two rows over latent
    pages is ``serve/decode.py draft_verify_scan``, and PERF.md has its
    break-even acceptance).

    Returns a ``DispatchResult`` whose ``sampled`` is (emitted [B, T],
    n_emit [B], decode_seq [R, B]), with the new pools. Host applies
    emitted[:n_emit] then decode_seq rows (and keeps the positions itself:
    the final carry is not returned).
    """
    verified = speculative_verify(
        params, tokens, positions, k_pages, v_pages, block_tables,
        stop_positions, slot_keys, temperature, top_k, top_p, cfg,
        attn_impl=attn_impl, w4_kernel_ok=w4_kernel_ok,
        w8_kernel_ok=w8_kernel_ok)
    emitted, n_emit = verified.sampled
    if num_decode_steps < 1:
        B = tokens.shape[0]
        return verified._replace(
            sampled=(emitted, n_emit, jnp.zeros((0, B), jnp.int32)))
    # device-side carry past the verified window: per-row dynamic position
    last = jnp.take_along_axis(emitted, (n_emit - 1)[:, None],
                               axis=1)[:, 0]
    from .decode import decode_scan
    decoded = decode_scan(
        params, last, positions + n_emit, verified.k_pages,
        verified.v_pages, block_tables, stop_positions, slot_keys,
        temperature, top_k, top_p, cfg, num_decode_steps, attn_impl,
        w4_kernel_ok, w8_kernel_ok)
    return DispatchResult((emitted, n_emit, decoded.sampled),
                          k_pages=decoded.k_pages, v_pages=decoded.v_pages)
