"""Inference engine: disaggregated prefill/decode over a paged KV cache.

Replaces the reference InferenceEngine (reference serve/server.py:127-251),
fixing its two fatal defects (SURVEY §2.4.1/2): requests stay resident in
decode slots until finished (continuous batching), and the KV cache is
actually read — decode is O(1) in prompt length instead of recomputing the
full prefix every token.

TPU-shaped execution model:
- **Prefill** — one compiled program per prompt-length bucket (a length is
  rounded up to the next rung of a ladder: ``prefill_chunk`` times 1, 2,
  4, then two rungs an octave (6, 8, 12, ...), so a handful of programs
  cover all prompts; XLA static shapes, SURVEY §7.3.2). Runs the standard
  training-side ``models.gpt.forward`` and scatters the dense K/V into
  pages.
- **Decode** — ONE compiled program, ever: every slot advances one token per
  call, inactive slots write to the scratch page and are masked. Page
  arrays are donated so XLA updates HBM in place.
- **Sampling** — on device, batched, per-request params (serve/sampling.py).

Admission reserves pages for prompt+max_tokens up front, so decode can
never hit KV OOM mid-flight (simple and correct; preemption/swapping is the
known upgrade path).
"""

from __future__ import annotations

import logging
import math
import threading
import time
from pathlib import Path
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..config.schema import ModelConfig, ServeConfig
from ..models import gpt
from .decode import decode_scan, extend_step_forward
from .kv_cache import PagedKVCache
from .sampling import fold_in_key_data, sample_tokens, seed_key_data
from .scheduler import (ContinuousBatchingScheduler, Request, RequestState,
                        SamplingParams)
from ..analysis.annotations import engine_thread_only
from ..metrics.spans import STARTUP, SpanRecorder

logger = logging.getLogger("llmctl.serve.engine")


class _Program:
    """A jitted engine program that remembers whether it has ever run.

    A failure before a program has ever run is most likely a failure to
    compile it (the TPU compiler refusing a kernel, a program that does
    not fit): it will fail the same way for every later request of that
    shape, and a probe of the device says nothing about it. The engine
    records such a failure in ``failed_programs``; ``recover()`` then
    reports the engine unhealthy until that same program runs — a
    transient first-call error (HBM full, a deleted donated buffer) is
    cleared by the program's next successful call."""

    def __init__(self, name: str, fn: Callable, failed: dict, **jit_kwargs):
        self.name = name
        self._fn = jax.jit(fn, **jit_kwargs)
        self._failed = failed
        self._ran = False

    def lower(self, *args):
        """The program lowered for ``args`` and not run: ``chip_smoke.py``
        compiles the result to read the decode program's memory analysis."""
        return self._fn.lower(*args)

    def compiled_text(self, *args) -> str:
        """The optimised HLO of the program compiled for ``args`` and not
        run; the compile is a start-up span and a ledger entry of its own
        (``program_texts()`` pays it after the window)."""
        with STARTUP.program(f"{self.name} (text)"):
            return self.lower(*args).compile().as_text()

    def __call__(self, *args):
        if self._ran:
            return self._fn(*args)
        # the first call traces, lowers and compiles (or reads the compile
        # cache): ONE llmctl.startup.program span and its ledger entry
        try:
            with STARTUP.program(self.name) as span:
                out = self._fn(*args)
        except Exception as e:
            self._failed[self.name] = f"{type(e).__name__}: {e}"[:400]
            raise
        self._ran = True
        self._failed.pop(self.name, None)
        if STARTUP.ready_t is not None:
            logger.warning(
                "program %r first ran after the server was ready: %.2f s "
                "under traffic (GET /v1/stats \"startup\" has its trace, "
                "lowering, compile and cache-read seconds)",
                self.name, span.seconds)
        return out


class InferenceEngine:
    def __init__(
        self,
        model_cfg: ModelConfig,
        serve_cfg: ServeConfig,
        params=None,
        seed: int = 0,
        eos_token_id: Optional[int] = None,
    ):
        serve_cfg.validate()    # one source of truth for config rules
        if jax.config.jax_default_prng_impl != "threefry2x32":
            raise ValueError(
                f"jax_default_prng_impl={jax.config.jax_default_prng_impl}: "
                "the serving engine keeps a slot's key as threefry2x32 key "
                "data (uint32[2]) and derives it from the seed on the host")
        self.serve_cfg = serve_cfg
        self.eos_token_id = eos_token_id
        dtype = jnp.dtype(serve_cfg.dtype)

        # effective quantization: a pre-quantized artifact can supply the
        # quant kind without the user asking for one. Tracked HERE (not by
        # mutating the caller's ServeConfig — the config object belongs to
        # the caller and may be reused for another engine).
        self.quantization = serve_cfg.quantization
        if params is None:
            # the artifact may override architecture facts (e.g. an
            # HF-imported tied-embedding checkpoint under an untied
            # template) — the effective config comes back with the params
            with STARTUP.phase("llmctl.startup.params"):
                params, model_cfg, self.quantization = self._load_params(
                    model_cfg, serve_cfg, seed, dtype)
        self.cfg = model_cfg
        # what a model with state-space layers turns off: every feature
        # below moves, shares or re-enters K/V PAGES, and a recurrent
        # layer's state is not in them. Each either carries the state or
        # is refused by name; none may run and be silently wrong
        # (ROADMAP C2: state snapshots would unlock them).
        self.ssm_refused: dict[str, int] = {}
        if model_cfg.is_recurrent:
            self._refuse_for_recurrent(serve_cfg)
        if model_cfg.is_latent:
            self._refuse_for_latent(serve_cfg)
        if model_cfg.layer_pattern and (
                serve_cfg.quantization not in ("", "none")
                or serve_cfg.tensor_parallel > 1):
            raise ValueError(
                f"{model_cfg.name}: a model with a layer table serves plain "
                "weights on one chip (quantization and tensor_parallel "
                "walk the uniform stack's [L, in, out] kernels)")

        from ..ops.quantization import _is_runtime_quant
        pre_quantized = any(
            _is_runtime_quant(leaf) for leaf in jax.tree_util.tree_leaves(
                params, is_leaf=_is_runtime_quant))
        if pre_quantized:
            # pre-quantized export artifact (load_exported): the weights
            # never existed in bf16 on this device — exactly the path a
            # 7B-class model needs on a 16 GB chip, where bf16 params +
            # a quantized copy cannot coexist during requantization
            logger.info("serving pre-quantized artifact weights (%s)",
                        self.quantization or "int8")
        elif serve_cfg.quantization == "int8":
            from ..ops.quantization import (quantize_tree_int8,
                                            to_runtime_quant)
            params = dict(params)
            # min_ndim=3: only the stacked [L, in, out] kernels — norm
            # scales / biases are [L, H] and must stay in full precision
            params["blocks"] = to_runtime_quant(
                quantize_tree_int8(params["blocks"], min_ndim=3))
            logger.info("serving with int8 block weights (W8A16)")
        elif serve_cfg.quantization in ("int4", "int4-awq"):
            from ..ops.quantization import (quantize_tree_int4,
                                            to_runtime_quant)
            calib = None
            awq_cfg = None
            if serve_cfg.quantization == "int4-awq":
                # one synthetic calibration pass for the AWQ channel
                # statistic (same approach as `llmctl export --quant
                # int8-awq` without a dataset)
                import jax.random as jrandom
                calib = jrandom.randint(
                    jrandom.PRNGKey(0), (2, min(256, serve_cfg.max_seq_len)),
                    1, model_cfg.vocab_size)
                awq_cfg = model_cfg
            # full-tree call (the AWQ calibration forward needs embed +
            # blocks); only the stacked [L, in, out] kernels quantize
            params = to_runtime_quant(quantize_tree_int4(
                dict(params), model_cfg=awq_cfg, calib_tokens=calib))
            logger.info("serving with int4 block weights (W4A16%s)",
                        "+awq" if calib is not None else "")

        # tensor-parallel serving: one tp-axis mesh; params shard per
        # PARAM_RULES (column/row-parallel kernels), pages per kv head.
        # GSPMD inserts the per-layer collectives — the serve-side
        # equivalent of the training ShardedTrainer. Attention runs the
        # gather impl under tp: the Pallas kernel is a custom call GSPMD
        # can't partition (it would replicate every page to every chip).
        tp = serve_cfg.tensor_parallel
        self.mesh = None
        self._attn_impl = "auto"
        # the W4 Pallas matmul is a custom call GSPMD cannot partition,
        # same as the attention kernel — tp>1 takes the dequant path
        self._w4_kernel_ok = tp <= 1
        # int8 Pallas matmul is OPT-IN (int8 dequant fuses in XLA; the
        # kernel must beat fused-XLA on chip first — schema docstring)
        self._w8_kernel_ok = tp <= 1 and serve_cfg.int8_pallas_matmul
        page_sharding = None
        if tp > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..config.schema import ParallelConfig
            from ..parallel.mesh import build_mesh
            from ..parallel.sharding import shard_params
            if model_cfg.is_moe:
                # the grouped-matmul kernel (ops/moe_gmm.py) is a custom
                # call GSPMD cannot partition, and jax.lax.ragged_dot over
                # tp-sharded expert stacks has never run on the chip
                raise ValueError(
                    f"tensor_parallel={tp} with an MoE model is refused: "
                    "dropless MoE serving has only run on one chip "
                    "(ROADMAP B1: ep=4 or a tp smoke of ragged_dot first)")
            if model_cfg.num_kv_heads % tp or model_cfg.num_heads % tp:
                raise ValueError(
                    f"tensor_parallel={tp} must divide num_heads="
                    f"{model_cfg.num_heads} and num_kv_heads="
                    f"{model_cfg.num_kv_heads}")
            self.mesh = build_mesh(ParallelConfig(tensor_parallel=tp),
                                   jax.devices()[:tp])
            params = shard_params(params, self.mesh)
            page_sharding = NamedSharding(
                self.mesh, P(None, None, "tp", None, None))
            self._attn_impl = "gather"
        self.params = params

        S = serve_cfg.max_batch_size
        # the K/V (or latent) pages and a recurrent model's state pools
        with STARTUP.phase("llmctl.startup.pools"):
            self.kv = PagedKVCache(
                model_cfg, num_slots=S, max_seq_len=serve_cfg.max_seq_len,
                page_size=serve_cfg.kv_block_size,
                num_pages=serve_cfg.kv_num_blocks,
                hbm_budget_gb=serve_cfg.kv_hbm_budget_gb, dtype=dtype,
                page_sharding=page_sharding,
                quantized=serve_cfg.kv_quantization)

        self._req_slot: dict[str, int] = {}
        # pages promised to admitted-but-not-yet-prefilled requests; without
        # this, one admit() round can over-commit: each request individually
        # passes a free-page check but their SUM exceeds what's free.
        # Tracked per request id so a request released BEFORE its prefill
        # (cancel / engine failure) returns its reservation instead of
        # leaking it.
        self._reserved_pages = 0
        self._reserved_by: dict[str, int] = {}
        # prefix-cache pins per request: pages pinned at admission (so LRU
        # eviction can't drop them before prefill), unpinned on release
        self._prefix_pins: dict[str, list[int]] = {}
        self.scheduler = ContinuousBatchingScheduler(
            max_batch_size=S, max_queue=serve_cfg.max_queue,
            max_seq_len=serve_cfg.max_seq_len,
            can_allocate=self._try_reserve,
            on_release=self._on_release,
            can_ever_allocate=lambda r: self.kv.can_ever_allocate(
                r.num_prompt_tokens + r.sampling.max_tokens))
        # guards scheduler/kv bookkeeping shared with the serving thread;
        # NEVER held across device compute (prefill/decode dispatch)
        self.lock = threading.Lock()
        # where the engine thread's time goes (llmctl.engine.* spans) and
        # how long the device had nothing to run; read through stats()
        self.spans = SpanRecorder()
        # fired (from the engine thread) whenever a request leaves its slot
        self.on_finish: Optional[Callable[[Request], None]] = None
        # fired (engine thread) with each batch of newly accepted tokens for
        # a request — the streaming hook (multi-step decode delivers up to
        # K per call)
        self.on_token: Optional[Callable[[Request, list], None]] = None
        # fired (engine thread, NO locks held) for each request that
        # survives its prefill-complete step boundary still RUNNING —
        # before this engine spends any decode dispatch on it. The
        # disaggregated fleet's prefill-role replicas extract the
        # sequence (with its KV) here and hand it to a decode replica.
        self.on_prefill_complete: Optional[Callable[[Request], None]] = None
        # pure-decode expectation (decode-role replica): dispatching a
        # prefill is still ALLOWED — the restore-fallback path needs it
        # when the pool can't hold a handoff payload — but it is counted
        # and logged so a mis-routed fleet is visible, not silent
        self.expect_pure_decode = False
        self.total_unexpected_prefills = 0
        # partial swap-in restores (crash-surviving migration pre-copies:
        # covered pages written back, only the tail re-prefilled)
        self.total_partial_restores = 0
        # fleet-global prefix cache (serve/fleet/): called on the ENGINE
        # thread right before a prefill with (request, uncovered page
        # hashes); returns {"hashes": [bytes], "pages": payload} fetched
        # from the owning replica, or None (miss/abort — plain prefill).
        # None (the default) disables fetching entirely.
        self.prefix_fetch_hook: Optional[Callable] = None
        # pipelined multi-replica prefill (serve/fleet/pipeline.py):
        # called on the ENGINE thread with (request, done_tokens,
        # finished) after each chunk of a STAGE request (one carrying
        # req.pipeline_stage) — by then the chunk's full pages are
        # registered in the prefix cache, so the coordinator can ship
        # them to the next stage while the remaining chunks compute.
        # Fired with no locks held. None disables the notifications
        # (stage requests still complete; the coordinator just falls
        # back to its stage timeout).
        self.pipeline_chunk_hook: Optional[Callable] = None
        # context tokens covered by pages FETCHED from another replica's
        # prefix cache instead of being re-prefilled here
        self.total_prefix_fetched_tokens = 0
        # of those, tokens fetched to extend a crash-salvaged PARTIAL
        # payload's coverage (the tail that would otherwise re-prefill)
        self.total_salvage_tail_fetched_tokens = 0

        # per-slot host state
        self.last_tokens = np.zeros(S, np.int32)
        self.positions = np.zeros(S, np.int32)    # cached length per slot
        self.stop_positions = np.zeros(S, np.int32)  # first un-writable pos
        self.active = np.zeros(S, bool)
        self.temperature = np.full(S, 1.0, np.float32)
        self.top_k = np.zeros(S, np.int32)
        self.top_p = np.ones(S, np.float32)
        # threefry key DATA a slot, written on the host from the request's
        # seed (_seed_slot); the decode programs wrap it and fold each
        # position in
        self._slot_keys = np.zeros((S, 2), np.uint32)
        self._base_seed = seed
        self._admitted_counter = 0
        # admission sequence per slot: preemption victims are chosen
        # newest-first so the oldest resident request always progresses
        # (global progress guarantee under on-demand admission)
        self._slot_seq = np.zeros(S, np.int64)
        self.total_preemptions = 0
        self.total_swap_ins = 0
        # per-slot incremental context (prompt + accepted tokens) for the
        # speculative draft proposer — rebuilding prompt+generated lists
        # per dispatch is O(context) host work in the latency-critical loop
        self._ctx = np.zeros((S, serve_cfg.max_seq_len), np.int32)
        self._ctx_len = np.zeros(S, np.int64)

        self._prefill_cache: dict[int, callable] = {}
        # program name -> the error of its first call (see _Program)
        self.failed_programs: dict[str, str] = {}
        # pipelined decode: the one un-fetched in-flight dispatch record
        # (None = none in flight); see step()
        self._pending = None
        # chunked prefill: request_id -> progress state (one chunk advances
        # per engine step, interleaved with decode)
        self._partial_prefills: dict[str, dict] = {}
        # decode: ONE compiled executable for every dispatch length.
        # With latency-adaptive dispatch (L > 0) the unit is L steps and
        # a full dispatch chains ceil(K/L) units on the device-resident
        # scan carry — no host round trip between units, ONE batched
        # fetch per group — while under queue pressure a dispatch is a
        # single unit, so a prefill window opens after L steps.
        # This REPLACES the round-4 two-program design (a second L-step
        # executable): merely enabling that program cost 18-25%
        # saturation goodput with zero short dispatches firing
        # (battery 9, re-confirmed clean in round 5), and the round-5
        # diagnostic caught 274 XLA compile/retrace events mid-run once
        # short dispatches DID fire — switching executables over the
        # donated page buffers churns layouts/caches. One executable
        # makes the mechanism structurally impossible; splitting a
        # dispatch into units is bitwise-identical output (same per-step
        # program, PRNG folded by position).
        K = max(serve_cfg.decode_steps_per_dispatch, 1)
        # L is a CAP: clamp to K-1 so a misconfigured L >= K still helps
        # instead of silently disabling; K == 1 has nothing to shrink
        L = min(serve_cfg.latency_dispatch_steps, K - 1)
        self._decode_unit_len = L if L > 0 else K
        # ceil division: a full group covers AT LEAST the configured K
        # steps (up to L-1 extra — the same wasted-trailing-iteration
        # trade K itself makes), so round-trip amortisation never
        # silently shrinks and every 0 < L < K keeps a real short path
        # (floor made any L > K/2 one unit == no adaptivity at all).
        # The admission lookahead derives from units * unit_len, so page
        # reservation tracks the actual group length.
        self._decode_units = -(-K // L) if L > 0 else 1
        # jitted from the bound methods, so that a profile shows the
        # programs as jit__decode_impl_n and jit__spec_impl (a
        # functools.partial has no name: jit__unknown)
        self._decode_jit = _Program(
            "_decode_impl_n", self._decode_impl_n,
            self.failed_programs,
            donate_argnums=(1, 2, 11) if model_cfg.is_recurrent else (1, 2))
        self.total_short_dispatches = 0
        self._spec_jit = (_Program("_spec_impl", self._spec_impl,
                                   self.failed_programs,
                                   donate_argnums=(1, 2))
                          if serve_cfg.speculative == "ngram" else None)
        self.total_decode_steps = 0
        # MoE routing counters (live tokens only; idle slots and prefill
        # padding get no expert): choices per expert, (layer, step) expert
        # hits and the (layer, step) pairs they are out of; the decode
        # programs' part of both again, for the decode step's byte floor.
        # Read off the fetch a step makes anyway (_count_moe).
        self.moe_choices = np.zeros(model_cfg.moe.num_experts, np.int64)
        self.moe_all_choices = 0
        # state updates of live slots in decode (slots x steps)
        self.ssm_slot_steps = 0
        self.moe_experts_hit = self.moe_layer_steps = 0
        self.moe_decode_experts_hit = self.moe_decode_layer_steps = 0
        self.total_prefill_tokens = 0      # tokens actually computed
        # rows the prefill programs computed for them: the bucket of a cold
        # or suffix prefill, the chunk program's rows of a chunked one
        self.total_prefill_padded_tokens = 0
        self.total_prefix_cached_tokens = 0  # prompt tokens skipped via cache
        # of the cached tokens, the ones on fleet-requeued orphans (warm-
        # prefix requeue payoff — feeds reprefill_tokens_avoided)
        self.total_requeue_cached_tokens = 0
        # decode always runs over all slots (one compiled program); padded
        # slots are wasted work — tracked so batch-size tuning isn't blind
        self.total_padded_slot_steps = 0
        # how far the paged-attention kernel's page walk engages: pages
        # the slots' lengths cover at each decode dispatch's first step,
        # against the block table's whole width (slots x pages a slot)
        self.total_live_pages = 0
        self.total_table_pages = 0
        # speculative-decode accounting (acceptance rate drives the
        # use-it-or-not decision per deployment)
        self.total_spec_dispatches = 0
        self.total_spec_drafts = 0
        self.total_spec_accepted = 0
        # per-slot courier-migratable speculative state (SpecState:
        # acceptance EWMA, adaptive window, proposer warmup) — armed
        # with the request, extracted into migration/handoff payloads,
        # restored on the destination so a re-placed sequence keeps its
        # tuned window instead of cold-starting the proposer
        self._spec_state: list = [None] * S
        # slots armed FROM a migrated SpecState (vs a cold proposer) —
        # the fleet-disagg resume assertion reads this
        self.total_spec_resumes = 0

    # -- setup ---------------------------------------------------------------

    def _refuse_for_recurrent(self, serve_cfg: ServeConfig) -> None:
        """Refuse, by name, the opt-in features a recurrent layer's state
        cannot follow, and turn prefix reuse by page hash off (it is ON by
        default): a page hit would skip tokens whose state-space state
        nobody kept. Said once in the log and in ``stats()["ssm"]``."""
        asked = {
            "chunked_prefill_tokens": serve_cfg.chunked_prefill_tokens > 0,
            "speculative": serve_cfg.speculative != "off",
            "preemption: swap": serve_cfg.preemption == "swap",
        }
        for feature, on in asked.items():
            if on:
                raise ValueError(
                    f"{self.cfg.name} has state-space layers: {feature} is "
                    "refused (it re-enters or moves K/V pages, and the "
                    "layers' recurrent state is not in them; ROADMAP C2)")
        if serve_cfg.prefix_caching:
            self.ssm_refused["prefix_caching"] = 0
            logger.warning(
                "%s has state-space layers: prefix reuse by page hash is "
                "off (no page hash is registered or looked up; a repeated "
                "prompt is prefilled again)", self.cfg.name)

    def _refuse_for_latent(self, serve_cfg: ServeConfig) -> None:
        """Refuse, by name, what a latent page pool does not carry yet.
        Prefix reuse by page hash stays ON: a latent page is a function of
        the token prefix exactly as a K/V page is. (Quantised latent pages
        are refused where the pool is made, serve/kv_cache.py.)"""
        asked = {
            "speculative": serve_cfg.speculative != "off",
            "preemption: swap": serve_cfg.preemption == "swap",
        }
        for feature, on in asked.items():
            if on:
                raise ValueError(
                    f"{self.cfg.name} keeps latent pages: {feature} is "
                    "refused (no verification program has run over latent "
                    "pages, and the swap payload is a K and a V pool; "
                    "ROADMAP B4, B7)")

    # the longest prompt a latent-attention model prefills COLD when no
    # chunk length is configured: its cold program attends in the expanded
    # form through XLA, whose float32 scores are heads x S x S (a 12k-token
    # document: 19 GB); longer prompts go chunk by chunk over the pages
    LATENT_COLD_TOKENS = 1024

    @property
    def _chunk_tokens(self) -> int:
        """Prompts longer than this are prefilled in chunks of it over the
        pages (0 = never): ``chunked_prefill_tokens``, and for a model with
        latent attention at least ``LATENT_COLD_TOKENS`` whatever is
        configured, so the cold ladder holds no rung it cannot run."""
        C = self.serve_cfg.chunked_prefill_tokens
        if self.cfg.is_latent and C <= 0:
            return self.LATENT_COLD_TOKENS
        return C

    @property
    def _prefix_caching(self) -> bool:
        return self.serve_cfg.prefix_caching and not self.cfg.is_recurrent

    @property
    def prefix_fetch_hook(self) -> Optional[Callable]:
        return self._prefix_fetch_hook

    @prefix_fetch_hook.setter
    def prefix_fetch_hook(self, hook: Optional[Callable]) -> None:
        if hook is not None and self.cfg.is_recurrent:
            raise ValueError(
                f"{self.cfg.name} has state-space layers: fleet prefix "
                "fetch is refused (fetched pages carry no recurrent state)")
        if hook is not None and self.cfg.is_latent:
            raise ValueError(
                f"{self.cfg.name} keeps latent pages: fleet prefix fetch "
                "is refused (the page payload is a K and a V pool; "
                "ROADMAP B4)")
        self._prefix_fetch_hook = hook

    @staticmethod
    def _load_params(model_cfg, serve_cfg, seed, dtype):
        """Restore from the artifact checkpoint dir, else random init (the
        reference errors without an artifact; random init keeps bench/smoke
        paths self-contained).

        Returns (params, effective model_cfg, effective quantization).
        The caller's ServeConfig is never mutated — a pre-quantized
        artifact's quant kind is reported through the return value and
        tracked on the engine."""
        art = serve_cfg.artifact
        if art and Path(art).is_file():
            # `llmctl export` artifact (safetensors/npz), possibly
            # pre-quantized: quantized leaves go straight to device as
            # (int8, scale) runtime tensors — bf16 never materialises
            from ..io.export import load_exported
            from ..ops.quantization import to_runtime_quant
            tree, meta = load_exported(art)
            art_quant = meta.get("quant") or ""
            want = serve_cfg.quantization
            want = "" if want in ("", "none") else want
            if art_quant and want and art_quant != want:
                raise ValueError(
                    f"artifact {art} is {art_quant}-quantized but serve "
                    f"config asks for {want!r}; requantization from a "
                    "quantized artifact would compound error — re-export")
            if art_quant == "int8-awq":
                raise ValueError(
                    "int8-awq exports are an interchange format; the serve "
                    "runtime consumes int8 / int4 / int4-awq artifacts "
                    "(the awq channel scaling is already folded for int4)")
            # architecture facts recorded at export (or provable from the
            # tree's structure) override the serving template — an artifact
            # from a tied-embedding model must not silently serve under an
            # untied config (and vice versa: the missing/extra lm_head
            # would corrupt the output projection, not error)
            import dataclasses

            from ..config.schema import _parse_bool
            tied_meta = meta.get("tie_word_embeddings")
            if tied_meta is not None:
                tied = _parse_bool("artifact tie_word_embeddings", tied_meta)
                if tied != model_cfg.tie_word_embeddings:
                    logger.warning(
                        "artifact records tie_word_embeddings=%s; "
                        "overriding serving template %r", tied,
                        model_cfg.name)
                    model_cfg = dataclasses.replace(
                        model_cfg, tie_word_embeddings=tied)
            has_head = isinstance(tree, dict) and "lm_head" in tree
            if has_head == model_cfg.tie_word_embeddings:
                # structural proof beats both metadata and template
                logger.warning(
                    "artifact %s lm_head — overriding "
                    "tie_word_embeddings=%s on template %r",
                    "has an" if has_head else "has no", not has_head,
                    model_cfg.name)
                model_cfg = dataclasses.replace(
                    model_cfg, tie_word_embeddings=not has_head)
            params = to_runtime_quant(tree)

            def cast(x):
                # dtype probe on the HOST array — jnp.asarray here would
                # device-transfer every float leaf twice in exactly the
                # memory-constrained 7B path this branch exists for
                x = np.asarray(x)
                if jnp.issubdtype(x.dtype, jnp.floating):
                    return jnp.asarray(x, dtype)
                return jnp.asarray(x)

            # device_put everything up front (incl. the int8 payloads —
            # leaving them as numpy would re-transfer per compiled program)
            from ..ops.quantization import _is_runtime_quant
            def put(x):
                if _is_runtime_quant(x):
                    children, aux = x.tree_flatten()
                    return type(x).tree_unflatten(
                        aux, [jnp.asarray(c) for c in children])
                return cast(x)

            params = jax.tree_util.tree_map(put, params,
                                            is_leaf=_is_runtime_quant)
            if meta.get("model") and meta["model"] != model_cfg.name:
                logger.warning("artifact was exported from model %r, "
                               "serving as %r", meta["model"], model_cfg.name)
            logger.info("loaded exported artifact %s (quant=%s)", art,
                        art_quant or "none")
            return params, model_cfg, (art_quant or want)
        if art and Path(art).exists():
            from ..io.checkpoint import (CheckpointManager,
                                         apply_ckpt_model_overrides,
                                         params_from_flat)
            ckpt = CheckpointManager(art)
            if ckpt.latest_step() is not None:
                state, extra = ckpt.restore()
                params = params_from_flat(state)
                model_cfg = apply_ckpt_model_overrides(model_cfg, extra)
                logger.info("loaded params from %s step %s", art,
                            ckpt.latest_step())
                return (jax.tree_util.tree_map(
                    lambda a: jnp.asarray(a, dtype), params), model_cfg,
                    serve_cfg.quantization)
        logger.warning("no artifact checkpoint found (%r): using random init",
                       art)
        return (gpt.init(model_cfg, jax.random.PRNGKey(seed),
                         dtype=dtype), model_cfg, serve_cfg.quantization)

    # -- prefill -------------------------------------------------------------

    @property
    def _decode_lookahead(self) -> int:
        """Tokens one device dispatch may write per slot: the page-growth
        horizon for on-demand admission.

        The fused speculative dispatch writes the whole verify window
        (T rows from the root position) AND its decode scan (K-1 steps
        from root + n_emit, n_emit <= T), so its worst-case span is
        T + K - 1 tokens — NOT max(T, K). Under-reserving here silently
        redirected the overflow rows to scratch page 0 (the block-table
        padding entry) where concurrent slots' overflow interleaves, and
        the next capacity pass then grew the chain over those positions
        with FRESH (zero) pages: quality rot in every deep-acceptance
        dispatch, and byte divergence the moment a migration misaligned
        a co-resident's overflow pattern (caught by the int4+spec
        migration identity tests)."""
        k = self._decode_units * self._decode_unit_len
        if self.serve_cfg.speculative == "ngram":
            K = max(self.serve_cfg.decode_steps_per_dispatch, 1)
            k = max(k, self.serve_cfg.speculative_tokens + K - 1)
        return k

    def _admission_tail(self, req: Request) -> int:
        """Tokens beyond the prefill context that admission must cover.

        reserve: the full generation budget (prompt+max_tokens pages held
        for the request's whole life — round-2 policy).
        ondemand: one dispatch of decode lookahead; later pages are
        allocated as decode advances (_ensure_decode_capacity), with
        preemption on pool exhaustion."""
        if self.serve_cfg.admission == "reserve":
            return req.remaining_tokens
        return min(self._decode_lookahead, req.remaining_tokens)

    def _try_reserve(self, req: Request) -> bool:
        """Admission hook (runs under self.lock inside admit()): reserve the
        request's admission KV footprint (_admission_tail) so concurrent
        admissions can't collectively over-commit the page pool. With prefix
        caching, cached context pages are pinned here (they stop being
        evictable) and only the remainder is reserved."""
        ctx = req.context_tokens   # prompt, + generated after a preemption
        n = len(ctx)
        # asked again for a request whose pages were promised already (the
        # scheduler's token budget stopped the admission after this hook
        # said yes): give the earlier promise back first, or it is counted
        # twice and its pins are never dropped
        self._reserved_pages -= self._reserved_by.pop(req.request_id, 0)
        stale = self._prefix_pins.pop(req.request_id, None)
        if stale:
            self.kv.unpin_pages(stale)
        req.prefix_cached_tokens = 0
        if req.swapped_kv is not None:
            # swap-in admission: the request brings its own pages — no
            # prefix pinning (it would double-count against the restore
            # allocation); reserve the restore footprint + lookahead
            need = max(self.kv.pages_needed(n + self._admission_tail(req)),
                       req.swapped_kv["pages"]["num_pages"])
            if need > self.kv.free_pages - self._reserved_pages:
                return False
            self._reserved_pages += need
            self._reserved_by[req.request_id] = need
            return True
        pins: list[int] = []
        usable = 0
        if "prefix_caching" in self.ssm_refused:
            self.ssm_refused["prefix_caching"] += 1   # admissions not looked up
        if self._prefix_caching:
            if req.prefix_hashes is None:      # once per request, not per retry
                from .kv_cache import prefix_page_hashes
                req.prefix_hashes = prefix_page_hashes(
                    ctx, self.kv.page_size)
            # keep >=1 suffix token: the last prompt token must be
            # re-processed to produce the first sampled token's logits
            usable = min(len(req.prefix_hashes),
                         max((n - 1) // self.kv.page_size, 0))
            pins = self.kv.lookup_prefix(req.prefix_hashes[:usable])
            # On TPU the multi-query Pallas kernel streams each cached page
            # once for all suffix queries, so ANY hit saves compute. The
            # gather fallback (CPU / tensor-parallel) re-streams the whole
            # prefix once PER SUFFIX TOKEN — there a small hit on a long
            # tail costs more than a cold dense prefill, so it is dropped.
            pallas_suffix = (self._attn_impl == "auto"
                             and jax.default_backend() == "tpu"
                             and (self.cfg.head_dim % 128 == 0
                                  or self.cfg.is_latent))
            computed = n - len(pins) * self.kv.page_size
            if pins and not pallas_suffix and computed > max(
                    len(pins) * self.kv.page_size,
                    self.serve_cfg.prefill_chunk):
                pins = []
        # pin BEFORE the capacity check: pinned pages leave the evictable
        # pool, so free_pages below no longer counts them — otherwise a
        # pool full of ref==0 cached prefixes admits requests whose fresh
        # allocation later OOMs in _prefill (over-commit)
        if pins:
            self.kv.pin_pages(pins)
        need = self.kv.pages_needed(n + self._admission_tail(req)) - len(pins)
        if need > self.kv.free_pages - self._reserved_pages:
            if pins:
                self.kv.unpin_pages(pins)
            return False
        if pins:
            self._prefix_pins[req.request_id] = pins
            # what the scheduler's per-step token budget need not charge
            req.prefix_cached_tokens = len(pins) * self.kv.page_size
        # hit-rate stats once per successful admission (not per retry)
        self.kv.prefix_queries += usable
        self.kv.prefix_hits += len(pins)
        if pins and req.fleet_requeued:
            # a crash/drain orphan whose prompt pages are already warm
            # here: these tokens are NOT re-prefilled — the fleet's
            # reprefill_tokens_avoided metric sums this across replicas
            self.total_requeue_cached_tokens += len(pins) * self.kv.page_size
        self._reserved_pages += need
        self._reserved_by[req.request_id] = need
        return True

    def _bucket(self, n: int) -> int:
        """Rows of the cold-prefill program for an ``n``-token prompt: the
        smallest rung at or above ``n`` of the ladder c, 2c, 4c, then two
        rungs an octave (6c, 8c, 12c, 16c, ...), where c is
        ``prefill_chunk`` rounded up to a page; capped at ``max_seq_len``
        rounded up to a page. Padding stays under one c up to 2c, under
        half the rung up to 4c and under a third of it above, and the
        program count grows with the logarithm of ``max_seq_len`` (13 at
        32k for c = 256). There is no 3c: every resident program costs a
        replica 0.7-1.0 s at each start (trace, lowering, the executable's
        read from the compile cache; ~13 s to compile on a cold one), which
        the 3c rung does not earn back (PERF.md 6, PR 30)."""
        PS = self.kv.page_size
        c = math.ceil(max(self.serve_cfg.prefill_chunk, PS) / PS) * PS
        k = math.ceil(max(n, 1) / c)
        if k > 2:
            octave = 1 << (k - 1).bit_length()      # power of two >= k
            k = octave * 3 // 4 if 4 < k <= octave * 3 // 4 else octave
        return min(k * c, math.ceil(self.serve_cfg.max_seq_len / PS) * PS)

    def _suffix_bucket(self, m: int) -> int:
        """Bucket for the un-cached prompt tail: page-granular, power-of-two
        page counts (bounded program count). Bucketing the tail by
        prefill_chunk like the dense path would pad a 64-token suffix to
        512 query rows — measured 5x slower than a cold dense prefill."""
        pages = max(math.ceil(m / self.kv.page_size), 1)
        pages = 1 << (pages - 1).bit_length()
        return min(pages * self.kv.page_size, self._bucket(m))

    def _prefill_fn(self, bucket: int):
        if bucket not in self._prefill_cache:
            cfg = self.cfg
            n_pages = bucket // self.kv.page_size
            dtype = self.kv.dtype

            def prefill_latent(params, tokens, length, k_pages, v_pages,
                               entries, key, temp, top_k, top_p):
                """Cold prefill of a latent-attention model: the window
                attends over its own tokens in the expanded form, and the
                rows a cache keeps are written to the latent pool's pages
                whole (the bucket's padding lands in scratch page 0 or
                behind the slot's length, where nothing reads it)."""
                live = (jnp.arange(bucket, dtype=jnp.int32)[None]
                        < length[:, None]).astype(jnp.int32)
                logits, rows, moe_stats = gpt.forward(
                    params, tokens, cfg, unembed_positions=length - 1,
                    segment_ids=live, return_latent=True,
                    return_moe_stats=True)
                pad = k_pages.shape[-1] - rows.shape[-1]
                rows = jnp.pad(rows[:, 0], ((0, 0), (0, 0), (0, pad)))
                k_pages = k_pages.at[:, entries].set(rows.reshape(
                    cfg.kv_layers, n_pages, 1, self.kv.page_size,
                    -1).astype(k_pages.dtype))
                token = sample_tokens(logits[:, 0], key[None], temp[None],
                                      top_k[None], top_p[None])[0]
                return (self._with_moe_stats(token, moe_stats), k_pages,
                        v_pages)

            def prefill(params, tokens, length, k_pages, v_pages, entries,
                        key, temp, top_k, top_p, state=None, slot=None):
                zeros = gpt.init_kv_cache(cfg, 1, bucket, dtype=dtype)
                moe = {}
                if cfg.is_moe or cfg.is_recurrent:
                    # the bucket's padding is not live: it gets no expert
                    # and is not counted, and it is kept out of a
                    # state-space layer's state (segment id 0; the cached
                    # attention route masks by length and ignores it)
                    moe = {"return_moe_stats": cfg.is_moe, "segment_ids": (
                        jnp.arange(bucket, dtype=jnp.int32)[None]
                        < length[:, None]).astype(jnp.int32)}
                logits, (kd, vd), *rest = gpt.forward(
                    params, tokens, cfg, kv_cache=zeros,
                    cache_offset=jnp.zeros((1,), jnp.int32),
                    unembed_positions=length - 1,
                    return_ssm_state=cfg.is_recurrent, **moe)
                moe_stats = rest[:int(cfg.is_moe)]
                if cfg.is_recurrent:
                    # ARM the slot: both pools' rows are overwritten with
                    # the state after the prompt's last live token,
                    # computed from a ZERO state, whatever a former
                    # occupant (or its trailing decode steps) left there
                    tails, states = rest[-1]
                    state = {
                        "conv": state["conv"].at[:, slot].set(
                            tails[:, 0].astype(state["conv"].dtype)),
                        "ssm": state["ssm"].at[:, slot].set(
                            states[:, 0].astype(state["ssm"].dtype))}
                # dense [L, bucket, Nkv, D] -> paged [L, n_pages, Nkv, PS, D]
                kd = kd[:, 0].reshape(
                    cfg.kv_layers, n_pages, self.kv.page_size,
                    cfg.num_kv_heads, cfg.head_dim).transpose(0, 1, 3, 2, 4)
                vd = vd[:, 0].reshape(
                    cfg.kv_layers, n_pages, self.kv.page_size,
                    cfg.num_kv_heads, cfg.head_dim).transpose(0, 1, 3, 2, 4)

                def scatter(pages, dense):
                    from ..ops.paged_attention import (
                        Int4Pages, QuantPages, quantize_kv_token,
                        quantize_kv_token_int4)
                    if isinstance(pages, Int4Pages):
                        # same per-token absmax granularity as int8,
                        # then the whole-page pack along the slot axis
                        # ([L, nP, Nkv, PS, D] -> [.., PS/2, D] bytes)
                        from ..ops.quantization import pack_int4_rows
                        qv, sc = quantize_kv_token_int4(dense)
                        return Int4Pages(
                            pages.values.at[:, entries].set(
                                pack_int4_rows(qv, axis=-2)),
                            pages.scale.at[:, entries].set(sc))
                    if isinstance(pages, QuantPages):
                        # dense [L, nP, Nkv, PS, D]: absmax over D gives
                        # the per-token scale [L, nP, Nkv, PS] — exactly
                        # the per-page scale-tile layout, no reshape
                        qv, sc = quantize_kv_token(dense)
                        return QuantPages(
                            pages.values.at[:, entries].set(qv),
                            pages.scale.at[:, entries].set(sc))
                    return pages.at[:, entries].set(dense)

                k_pages = scatter(k_pages, kd)
                v_pages = scatter(v_pages, vd)
                token = sample_tokens(logits[:, 0], key[None], temp[None],
                                      top_k[None], top_p[None])[0]
                if moe_stats:
                    token = self._with_moe_stats(token, moe_stats[0])
                if cfg.is_recurrent:
                    return token, k_pages, v_pages, state
                return token, k_pages, v_pages

            self._prefill_cache[bucket] = _Program(
                f"prefill {bucket}",
                prefill_latent if cfg.is_latent else prefill,
                self.failed_programs,
                donate_argnums=(3, 4, 10) if cfg.is_recurrent else (3, 4))
        return self._prefill_cache[bucket]

    def _extend_prefill_fn(self, bucket: int):
        """Suffix prefill over a cached paged prefix: only the un-cached
        tail of the prompt is computed (decode.extend_step_forward), writing
        straight through the slot's block table. One program per suffix
        bucket, same bucketing as the dense path."""
        key_ = ("extend", bucket)
        if key_ not in self._prefill_cache:
            cfg = self.cfg

            def extend_prefill(params, tokens, start, m, k_pages, v_pages,
                               table, key, temp, top_k, top_p):
                write_ok = (jnp.arange(bucket, dtype=jnp.int32)[None]
                            < m[:, None])
                logits, k_pages, v_pages, *moe_stats = extend_step_forward(
                    params, tokens, start, k_pages, v_pages, table, cfg,
                    write_ok=write_ok, attn_impl=self._attn_impl,
                    w4_kernel_ok=self._w4_kernel_ok,
                    w8_kernel_ok=self._w8_kernel_ok,
                    return_moe_stats=True)
                last = jnp.take_along_axis(
                    logits, (m - 1)[:, None, None], axis=1)[:, 0]   # [1, V]
                token = sample_tokens(last, key[None], temp[None],
                                      top_k[None], top_p[None])[0]
                if moe_stats:
                    token = self._with_moe_stats(token, moe_stats[0])
                return token, k_pages, v_pages

            self._prefill_cache[key_] = _Program(
                f"suffix prefill {bucket}", extend_prefill,
                self.failed_programs, donate_argnums=(4, 5))
        return self._prefill_cache[key_]

    def _extend_chunk_fn(self, bucket: int):
        """Intermediate chunked-prefill program: writes a chunk's K/V into
        the pages and returns ONLY the pages — the unembed/logits chain is
        dead-code-eliminated by XLA, so mid-prompt chunks skip the [T, V]
        head entirely."""
        key_ = ("chunk", bucket)
        if key_ not in self._prefill_cache:
            cfg = self.cfg

            def extend_chunk(params, tokens, start, m, k_pages, v_pages,
                             table):
                write_ok = (jnp.arange(bucket, dtype=jnp.int32)[None]
                            < m[:, None])
                # (returns no token, so an MoE model's mid-prompt chunks
                # have no fetch to carry their routing counts: not counted)
                _, k_pages, v_pages = extend_step_forward(
                    params, tokens, start, k_pages, v_pages, table, cfg,
                    write_ok=write_ok, attn_impl=self._attn_impl,
                    w4_kernel_ok=self._w4_kernel_ok,
                    w8_kernel_ok=self._w8_kernel_ok)
                return k_pages, v_pages

            self._prefill_cache[key_] = _Program(
                f"prefill chunk {bucket}", extend_chunk,
                self.failed_programs, donate_argnums=(4, 5))
        return self._prefill_cache[key_]

    @staticmethod
    def _with_moe_stats(token, moe_stats):
        """A prefill program's first token and its routing counts as ONE
        int32 vector [token, choices (E), experts hit]: the counts reach
        the host on the fetch of the token, with no sync of their own."""
        return jnp.concatenate([token.reshape(1).astype(jnp.int32),
                                moe_stats])

    def _count_moe(self, moe_stats: np.ndarray, steps: int,
                   decode: bool) -> None:
        """Add one program's [choices (E), experts hit] (summed over its
        layers and ``steps`` steps) to the engine's counters."""
        E = self.cfg.moe.num_experts
        hit, layer_steps = int(moe_stats[E]), steps * self.cfg.moe_layers
        self.moe_choices += moe_stats[:E]
        # the live choices over ALL the router's experts: the held ones
        # alone where every expert is held
        self.moe_all_choices += int(moe_stats[-1] if len(moe_stats) > E + 1
                                    else moe_stats[:E].sum())
        self.moe_experts_hit += hit
        self.moe_layer_steps += layer_steps
        if decode:
            self.moe_decode_experts_hit += hit
            self.moe_decode_layer_steps += layer_steps

    @engine_thread_only
    def _maybe_fetch_prefix(self, req: Request) -> None:
        """Fleet-global prefix fetch (engine thread, called right before
        a prefill, NO lock held across the network round trip): when the
        local prefix cache leaves full pages of the context uncovered and
        the router attached a ``prefix_owner`` hint, fetch those pages
        from the owner over the courier, import them into the local
        cache, and pin them for this request — the prefill then computes
        only the uncovered tail. Every failure (no hook, miss, abort,
        malformed payload, dry pool) leaves the request exactly as it
        was: plain prefill, correct tokens, extra compute."""
        hook = self.prefix_fetch_hook
        if (hook is None or not self._prefix_caching
                or req.swapped_kv is not None
                or getattr(req, "prefix_owner", None) is None
                or not req.prefix_hashes):
            return
        rid = req.request_id
        n = len(req.context_tokens)
        PS = self.kv.page_size
        # >=1 suffix token stays: the last context token must be
        # re-processed to produce the next token's logits
        usable = min(len(req.prefix_hashes), max((n - 1) // PS, 0))
        if usable == 0:
            return
        with self.lock:
            pins = list(self._prefix_pins.get(rid, ()))
            # re-check coverage NOW (not at admission): a sibling's fetch
            # or prefill since then may already have published the pages
            chain = self.kv.lookup_prefix(req.prefix_hashes[:usable])
            if len(chain) > len(pins):
                extra = chain[len(pins):]
                self.kv.pin_pages(extra)
                pins += extra
                self._prefix_pins[rid] = pins
        uncovered = req.prefix_hashes[len(pins):usable]
        if not uncovered:
            return
        got = hook(req, uncovered)      # network round trip, no lock
        if not got:
            return
        hashes, pages = got.get("hashes") or [], got.get("pages")
        # chain consistency: the owner must answer with a PREFIX of what
        # was asked — anything else (stale inventory, hash-collision-
        # shaped confusion) is discarded rather than imported
        k = 0
        while k < min(len(hashes), len(uncovered)) \
                and hashes[k] == uncovered[k]:
            k += 1
        if k == 0 or not isinstance(pages, dict):
            return
        with self.lock:
            try:
                inserted = self.kv.insert_prefix_pages(uncovered[:k], pages)
            except (ValueError, KeyError, TypeError) as e:
                # malformed fetch payload: plain prefill, never garbage KV
                logger.warning(
                    "fetched prefix payload for %s rejected (%s); "
                    "re-prefilling", rid, e)
                return
            # pin the now-longer cached chain for this request so nothing
            # imported can be evicted before its prefill runs (same lock
            # hold as the insert — the lookup->pin atomicity contract)
            chain = self.kv.lookup_prefix(req.prefix_hashes[:usable])
            if len(chain) > len(pins):
                extra = chain[len(pins):]
                self.kv.pin_pages(extra)
                self._prefix_pins[rid] = pins + extra
            if inserted:
                tokens = len(inserted) * PS
                self.total_prefix_fetched_tokens += tokens
                # prefill FLOPs the FLEET did not respend — feeds the
                # fleet's reprefill_tokens_avoided metric exactly like
                # warm-prefix requeues
                self.total_requeue_cached_tokens += tokens
                logger.info(
                    "prefix fetch for %s: imported %d page(s) (%d "
                    "tokens) from replica %s", rid, len(inserted),
                    tokens, getattr(req, "prefix_owner", None))

    @engine_thread_only
    def _maybe_fetch_salvage_tail(self, req: Request) -> None:
        """Crash-salvaged PARTIAL payloads (migration pre-copies) used to
        re-prefill their whole uncovered tail even when a sibling's
        prefix cache held those very pages. When the router hinted an
        owner, fetch the chain pages BEYOND the payload's coverage over
        the courier and splice them onto the payload — the tail prefill
        then shrinks to what nobody has. Every failure mode (no hook, no
        hint, miss, abort, schema mismatch) leaves the payload exactly
        as it was: the plain partial-restore path, correct tokens, extra
        compute. Engine thread, no lock held across the network."""
        hook = self.prefix_fetch_hook
        kvp = req.swapped_kv
        if (hook is None or not self._prefix_caching
                or not isinstance(kvp, dict) or not kvp.get("partial")
                or getattr(req, "prefix_owner", None) is None
                or not req.prefix_hashes):
            return
        from .kv_cache import concat_page_payloads, slice_page_payload
        PS = self.kv.page_size
        n = len(req.context_tokens)
        covered = int(kvp.get("positions", 0)) // PS
        pages = kvp.get("pages")
        if not isinstance(pages, dict) \
                or int(pages.get("num_pages", -1)) != covered:
            return       # unexpected payload shape: leave it alone
        # >=1 suffix token must still be computed (the last context token
        # produces the next token's logits) — same bound as the plain
        # prefix-fetch path
        usable = min(len(req.prefix_hashes), max((n - 1) // PS, 0))
        if covered >= usable:
            return
        missing = req.prefix_hashes[covered:usable]
        got = hook(req, missing)
        if not got:
            return
        hashes, fetched = got.get("hashes") or [], got.get("pages")
        # chain consistency: accept only a PREFIX of what was asked
        k = 0
        while k < min(len(hashes), len(missing)) \
                and hashes[k] == missing[k]:
            k += 1
        if k == 0 or not isinstance(fetched, dict):
            return
        try:
            merged = concat_page_payloads(pages,
                                          slice_page_payload(fetched, k))
        except (ValueError, KeyError, TypeError) as e:
            logger.warning(
                "salvage-tail fetch payload for %s rejected (%s); "
                "re-prefilling the tail", req.request_id, e)
            return
        kvp["pages"] = merged
        kvp["positions"] = (covered + k) * PS
        self.total_salvage_tail_fetched_tokens += k * PS
        self.total_prefix_fetched_tokens += k * PS
        logger.info(
            "salvage-tail fetch for %s: extended partial coverage "
            "%d -> %d page(s) from replica %s", req.request_id, covered,
            covered + k, getattr(req, "prefix_owner", None))

    @engine_thread_only
    def _seed_slot(self, slot: int, seed: int) -> np.ndarray:
        """The slot's sampling key from its request's seed: the key's DATA
        (uint32[2]), made on the host (``sampling.seed_key_data``: no
        program on the device, nothing fetched from it), written into
        ``_slot_keys`` for the decode programs, which fold each position
        in themselves, and returned for ``_sampling_args``, which folds
        the prompt's length in for the first token."""
        key = seed_key_data(seed)
        self._slot_keys[slot] = key
        return key

    @staticmethod
    def _sampling_args(slot_key: np.ndarray, n: int,
                       s: SamplingParams) -> tuple:
        """A prefill program's sampling arguments: the first token's key
        (the slot's key with the context length ``n`` folded in, as the
        decode programs fold each later position in), temperature, top-k
        and top-p, every one a numpy value made on the host. The jitted
        call uploads them, where ``fold_in`` on a device key, ``jnp.float32``
        of a number or ``jnp.asarray`` of a list with a dtype each runs a
        one-operation program on the device first, and the engine thread
        walks from each such program to the next while the device stands
        idle (PERF.md 6, PR 32). The one place every caller of those
        programs gets them from."""
        return (fold_in_key_data(slot_key, n), np.float32(s.temperature),
                np.int32(s.top_k), np.float32(s.top_p))

    @engine_thread_only
    def _start_chunked_prefill(self, req: Request) -> None:
        """Allocate the slot's pages and enqueue the context for chunk-at-a-
        time prefill (one chunk per engine step, interleaved with decode)."""
        self._maybe_fetch_prefix(req)
        slot = req.slot
        ctx = req.context_tokens
        n = len(ctx)
        rid = req.request_id
        if self.expect_pure_decode:
            self.total_unexpected_prefills += 1
            logger.warning(
                "pure-decode engine starting a chunked prefill for %s "
                "(restore fallback or fleet mis-routing)", rid)
        with self.lock:
            pins = self._prefix_pins.get(rid, [])
            self.kv.allocate(slot, n + self._admission_tail(req),
                             prefix_pages=pins)
            self._reserved_pages -= self._reserved_by.pop(rid, 0)
            self._req_slot[rid] = slot
            table_row = self.kv.block_tables[slot].copy()
        s = req.sampling
        if req.assigned_seed is None:
            req.assigned_seed = s.seed if s.seed is not None else (
                self._base_seed + self._admitted_counter)
        self._admitted_counter += 1
        self._slot_seq[slot] = self._admitted_counter
        slot_key = self._seed_slot(slot, req.assigned_seed)
        cached = len(pins) * self.kv.page_size
        self.total_prefix_cached_tokens += cached
        if req.prefill_dispatch_time is None:
            req.prefill_dispatch_time = time.monotonic()
        self.spans.annotate(cached=cached)
        self._partial_prefills[rid] = {
            "req": req, "ctx": ctx, "done": cached, "pins": len(pins),
            "table_row": table_row, "slot_key": slot_key}

    @engine_thread_only
    def _advance_chunked_prefills(self) -> list:
        """Advance in-flight chunked prefills, at most ``prefill_budget_
        tokens`` of prompt per engine step TOTAL (at least one chunk so a
        single prefill can never starve). Without the cap, N concurrent
        chunked prefills would each advance a chunk per step and the
        resident streams' inter-token gap would be N*chunk, not one budget
        (round-2 code-review finding). Round-robin rotation keeps
        concurrent prefills progressing fairly. Returns
        [(req, device_token)] for the ones that completed this step."""
        completed = []
        C = self._chunk_tokens
        budget = max(self.serve_cfg.prefill_budget_tokens, C)
        spent = live = 0
        rids = list(self._partial_prefills)
        # resume point is a request_id, not an index: entries complete or
        # cancel between steps, so an index into last step's snapshot can
        # skip or double-advance a request (ADVICE r2)
        resume_rid = getattr(self, "_chunk_rr", None)
        rr = rids.index(resume_rid) if resume_rid in rids else 0
        self._chunk_rr = None
        for rid in rids[rr:] + rids[:rr]:
            st = self._partial_prefills[rid]
            req: Request = st["req"]
            if req.cancel_requested:        # dispatches nothing: no charge
                with self.lock:
                    self.scheduler.abort_prefill(rid)   # frees slot + pages
                del self._partial_prefills[rid]
                continue
            ctx = st["ctx"]
            n, done = len(ctx), st["done"]
            stage = req.pipeline_stage
            # stage requests reach here even with chunking disabled
            # (C == 0): fall back to the prefill bucketing granularity
            # so the per-chunk page-publish cadence still exists
            this = min(n - done,
                       C if C > 0 else max(self.serve_cfg.prefill_chunk, 1))
            # charge what the program actually computes — the padded
            # suffix bucket — not the raw token count (a 33-token final
            # chunk dispatches a 64-row program) and not the constant C
            # (a 1-token chunk must not burn a whole chunk of budget)
            cost = self._suffix_bucket(this)
            if spent > 0 and spent + cost > budget:
                self._chunk_rr = rid   # resume at this request next step
                break
            spent += cost
            live += this
            bucket = cost
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :this] = ctx[done:done + this]
            common = (self.params, tokens, np.array([done], np.int32),
                      np.array([this], np.int32),
                      self.kv.k_pages, self.kv.v_pages,
                      st["table_row"][None])
            if done + this < n or stage is not None:
                # intermediate chunk — and EVERY chunk of a pipeline
                # stage request, whose product is pages, not logits:
                # even its final chunk runs the sampling-free program
                self.kv.k_pages, self.kv.v_pages = \
                    self._extend_chunk_fn(bucket)(*common)
                st["done"] = done + this
                if stage is not None:
                    self._publish_stage_pages(st)
                    if done + this >= n:
                        # stage complete: pages published, slot freed
                        # without arming decode (the registered pages
                        # outlive the slot, evictable until pinned)
                        with self.lock:
                            self.scheduler.finish_prefill_only(rid)
                        del self._partial_prefills[rid]
            else:
                token, self.kv.k_pages, self.kv.v_pages = \
                    self._extend_prefill_fn(bucket)(
                        *common, *self._sampling_args(st["slot_key"], n,
                                                      req.sampling))
                self.spans.dispatched()
                if self._prefix_caching and req.prefix_hashes:
                    with self.lock:
                        table = self.kv.block_tables[req.slot]
                        self.kv.register_pages(
                            [(req.prefix_hashes[i], int(table[i]))
                             for i in range(st["pins"],
                                            n // self.kv.page_size)])
                completed.append((req, token))
                del self._partial_prefills[rid]
            self.total_prefill_tokens += this
            self.total_prefill_padded_tokens += bucket
            if stage is not None and self.pipeline_chunk_hook is not None:
                # no locks held: the coordinator side only enqueues
                self.pipeline_chunk_hook(req, st["done"], st["done"] >= n)
        self.spans.annotate(tokens=live, bucket=spent)
        return completed

    @engine_thread_only
    def _publish_stage_pages(self, st: dict) -> None:
        """Register a pipeline stage request's freshly-completed FULL
        pages in the prefix cache as soon as they exist — not at prefill
        end like ordinary requests: the pipeline coordinator ships
        published pages to the next stage while the remaining chunks
        compute, which is the transfer-hides-behind-compute half of the
        pipelined prefill (serve/fleet/pipeline.py)."""
        req: Request = st["req"]
        if not self._prefix_caching or not req.prefix_hashes:
            return
        full = min(st["done"] // self.kv.page_size, len(req.prefix_hashes))
        pub = st.setdefault("published", st["pins"])
        if full <= pub:
            return
        with self.lock:
            table = self.kv.block_tables[req.slot]
            self.kv.register_pages([(req.prefix_hashes[i], int(table[i]))
                                    for i in range(pub, full)])
        st["published"] = full

    @engine_thread_only
    def _prefill(self, req: Request):
        """Dispatch one prompt's prefill; returns (req, device token).

        ONE program on the device and nothing fetched from it: the pages,
        the slot's key and the arguments are host work (numpy), so the
        dispatch queues behind whatever the device is running, a pipelined
        decode dispatch included (``tests/test_prefill_dispatch.py``).
        The first-token fetch is DEFERRED (_finish_prefill) so a burst of
        admitted prompts pays one host round trip total, not one per
        prompt — dispatches pipeline on-device."""
        self._maybe_fetch_prefix(req)
        slot = req.slot
        ctx = req.context_tokens   # prompt, + generated after a preemption
        n = len(ctx)
        rid = req.request_id
        PS = self.kv.page_size
        if self.expect_pure_decode:
            self.total_unexpected_prefills += 1
            logger.warning(
                "pure-decode engine dispatching a prefill for %s "
                "(restore fallback or fleet mis-routing)", rid)
        # crash-salvaged migration pre-copy: the payload's FULL pages are
        # host memory covering a prefix of the context — written back
        # below, so only the uncovered tail re-prefills. When the router
        # hinted a prefix owner, the tail first routes through the fetch
        # path and the payload grows by whatever the owner still caches.
        self._maybe_fetch_salvage_tail(req)
        partial = (req.swapped_kv
                   if req.swapped_kv is not None
                   and req.swapped_kv.get("partial") else None)
        with self.lock:   # page bookkeeping is shared with cancel/release
            pins = self._prefix_pins.get(rid, [])
            if partial is not None and pins:
                # a partial payload and local prefix-cache pins both
                # cover a prefix of the chain — pick ONE source. The
                # payload is written into the slot's own pages from
                # chain index 0, so restoring it over pinned SHARED
                # cache pages would corrupt the cache for every other
                # holder; and when the cache already covers at least as
                # much, the payload adds nothing.
                if len(pins) * PS >= int(partial.get("positions", 0)):
                    req.swapped_kv = None
                    partial = None
                else:
                    self.kv.unpin_pages(pins)
                    self._prefix_pins.pop(rid, None)
                    pins = []
            self.kv.allocate(slot, n + self._admission_tail(req),
                             prefix_pages=pins)
            self._reserved_pages -= self._reserved_by.pop(rid, 0)
            self._req_slot[rid] = slot
            cached = len(pins) * PS       # context tokens served from cache
            if partial is not None:
                try:
                    self.kv.write_slot_pages(slot, partial["pages"])
                    cached = int(partial["positions"])
                    self.total_partial_restores += 1
                    if req.fleet_requeued:
                        # prefill FLOPs the fleet did NOT respend thanks
                        # to the salvaged pre-copy — feeds the fleet's
                        # reprefill_tokens_avoided metric
                        self.total_requeue_cached_tokens += cached
                except (ValueError, KeyError, TypeError) as e:
                    # malformed salvage payload: fall back to a FULL
                    # prefill over the already-allocated chain — slower,
                    # never wrong, never a dead engine thread
                    logger.warning(
                        "partial restore payload for %s rejected (%s); "
                        "re-prefilling the whole context", rid, e)
                req.swapped_kv = None
            if cached == 0:
                # table entries for the bucket: beyond-length -> scratch 0
                bucket = self._bucket(n)
                entries = np.zeros(bucket // PS, np.int32)
                used = self.kv.pages_needed(n)
                entries[:used] = self.kv.block_tables[slot, :used]
            table_row = self.kv.block_tables[slot].copy()

        s = req.sampling
        if req.assigned_seed is None:
            req.assigned_seed = s.seed if s.seed is not None else (
                self._base_seed + self._admitted_counter)
        self._admitted_counter += 1
        self._slot_seq[slot] = self._admitted_counter  # preemption priority
        sampling = self._sampling_args(
            self._seed_slot(slot, req.assigned_seed), n, s)
        # first prefill only: a preemption RESUME must not restamp these —
        # TTFT is arrival->FIRST token, and the resume bucket is a suffix
        # program the dense calibration table doesn't cover
        first_prefill = req.prefill_dispatch_time is None
        if first_prefill:
            req.prefill_dispatch_time = time.monotonic()

        if cached == 0:
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :n] = ctx
            if first_prefill:
                req.prefill_bucket = bucket
            out = self._prefill_fn(bucket)(
                self.params, tokens, np.array([n], np.int32),
                self.kv.k_pages, self.kv.v_pages, entries, *sampling,
                *((self.kv.state, np.int32(slot))
                  if self.cfg.is_recurrent else ()))
            if self.cfg.is_recurrent:
                *out, self.kv.state = out
            token, self.kv.k_pages, self.kv.v_pages = out
            computed = n
        else:
            computed = n - cached
            bucket = self._suffix_bucket(computed)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :computed] = ctx[cached:]
            # NO prefill_bucket here: this is the suffix-extend program,
            # whose bucket ints collide with dense calibration keys —
            # attach_device_times must skip prefix-hit requests rather
            # than bill them a full dense prefill
            token, self.kv.k_pages, self.kv.v_pages = \
                self._extend_prefill_fn(bucket)(
                    self.params, tokens, np.array([cached], np.int32),
                    np.array([computed], np.int32),
                    self.kv.k_pages, self.kv.v_pages, table_row[None],
                    *sampling)
            self.total_prefix_cached_tokens += cached
        self.spans.dispatched()
        self.spans.annotate(bucket=bucket, cached=cached)

        # publish this prompt's freshly-written full pages for future hits
        if self._prefix_caching and req.prefix_hashes:
            with self.lock:
                table = self.kv.block_tables[slot]
                self.kv.register_pages(
                    [(req.prefix_hashes[i], int(table[i]))
                     for i in range(len(pins), n // PS)])

        self.total_prefill_tokens += computed
        self.total_prefill_padded_tokens += bucket
        return req, token

    @engine_thread_only
    def _arm_slot(self, req: Request, last_token: int, n_written: int,
                  ctx: list) -> None:
        """Make a slot live for decode — the ONE place the per-slot decode
        invariants are set (prefill completion AND swap-in restore; a
        field added here reaches both paths). ``n_written`` is the number
        of KV entries present; ``ctx`` the full token context including
        ``last_token`` (whose KV is written on its decode step)."""
        slot = req.slot
        s = req.sampling
        from .scheduler import RequestState
        req.state = RequestState.RUNNING
        self.last_tokens[slot] = last_token
        self._ctx[slot, :len(ctx)] = ctx
        self._ctx_len[slot] = len(ctx)
        self.positions[slot] = n_written
        # first position this slot may NOT write: absolute generation cap
        # (prompt + max_tokens); multi-step decode masks writes at/past
        # this bound to scratch page 0. Under on-demand admission the
        # PHYSICAL page chain may be shorter — _ensure_decode_capacity
        # grows it one dispatch ahead of the write frontier.
        self.stop_positions[slot] = req.num_prompt_tokens + s.max_tokens
        self.active[slot] = True
        self.temperature[slot] = s.temperature
        self.top_k[slot] = s.top_k
        self.top_p[slot] = s.top_p
        # speculative state: resume from a migrated SpecState when the
        # request carries one (handoff / drain migration / preemption
        # resume — the payload's copy lands on req.spec_state before
        # this runs), else start cold at the full configured window
        if self.serve_cfg.speculative == "ngram":
            from .speculative import SpecState
            T = max(self.serve_cfg.speculative_tokens, 2)
            carried = getattr(req, "spec_state", None)
            if isinstance(carried, dict):
                self._spec_state[slot] = SpecState.from_dict(
                    carried, max_window=T)
                if self._spec_jit is not None:
                    self.total_spec_resumes += 1
            else:
                self._spec_state[slot] = SpecState(window=T)
        else:
            self._spec_state[slot] = None

    @engine_thread_only
    def _finish_prefill(self, req: Request, token) -> None:
        """Resolve a dispatched prefill: fetch its first token and make the
        slot live for decode."""
        with self.spans.phase("llmctl.engine.prefill.wait",
                              request_id=req.request_id):
            if self.cfg.is_moe:
                fetched = np.asarray(token)
                token = int(fetched[0])
                self._count_moe(fetched[1:], steps=1, decode=False)
            else:
                token = int(token)
        self.spans.fetched()
        with self.spans.phase("llmctl.engine.apply"):
            ctx = req.context_tokens   # BEFORE recording the new token
            n = len(ctx)
            req.record_token(token)
            if self.on_token is not None:
                with self.spans.phase("llmctl.engine.deliver"):
                    self.on_token(req, [token])
            self._arm_slot(req, token, n, ctx + [token])

    # -- decode --------------------------------------------------------------

    def _decode_impl_n(self, params, k_pages, v_pages, tokens, positions,
                       tables, stops, slot_keys, temp, top_k, top_p,
                       state=None):
        # _decode_unit_len steps: fixed at construction, so ONE program
        # the final scan carry (tokens, positions) comes back as DEVICE
        # arrays so a pipelined follow-up dispatch can chain on them
        # without a host round trip (step() pipelining below)
        # an MoE model's program also returns its routing counts
        # (decode_scan's moe_stats), fetched with the tokens
        # a model with state-space layers also takes and returns their
        # state pools, LAST (``state``: donated, advanced in place)
        (toks, pos, k_pages, v_pages, *rest), toks_seq = decode_scan(
            params, tokens, positions, k_pages, v_pages, tables, stops,
            slot_keys, temp, top_k, top_p, self.cfg, self._decode_unit_len,
            attn_impl=self._attn_impl, w4_kernel_ok=self._w4_kernel_ok,
            w8_kernel_ok=self._w8_kernel_ok, return_moe_stats=True,
            ssm_state=state)
        return (toks_seq, toks, pos, k_pages, v_pages, *rest)

    def _short_dispatch_ok(self) -> bool:
        """Should the next decode dispatch run the SHORT program? (caller
        holds self.lock.) True only when shortening can actually help: a
        request waits in the queue, a slot is free, and the queue head's
        admission reservation would fit the free pool right now (a
        pages-starved head can't be admitted at any boundary, so paying
        K/L x the host round trips would buy nothing). The page probe
        ignores prefix-cache pins — pessimistic, so the failure mode is
        keeping the long program, never wasted RTT."""
        if self._decode_units <= 1:
            return False
        # occupancy gate: only at a mostly-empty batch. Near saturation a
        # queued admissible head exists almost every boundary, and paying
        # K/L x the dispatch overhead for EVERY resident taxes goodput
        # far more than the queued request gains (measured: c8 goodput
        # 144 -> 113.5 tok/s with the queue-only guard, battery 5) — the
        # latency win is real only when few streams share the overhead.
        S = self.serve_cfg.max_batch_size
        # threshold capped at S-1 so a FULL batch never shortens (S=1:
        # threshold 0 — the sole slot busy means nothing can be admitted)
        occupancy_cap = min(max(S // 4, 1), S - 1)
        if (self.scheduler.queue_depth == 0
                or self.scheduler.active_count > occupancy_cap):
            return False
        head = self.scheduler.waiting[0]
        need = self.kv.pages_needed(
            len(head.context_tokens) + self._admission_tail(head))
        return need <= self.kv.free_pages - self._reserved_pages

    @engine_thread_only
    def _decode_device(self, use_short: bool = False) -> np.ndarray:
        """Dispatch one decode GROUP and fetch its tokens.

        A group is ``self._decode_units`` chained unit dispatches (ONE
        when ``use_short`` — the latency-adaptive path: the device
        finishes after unit_len steps, so the next admit/prefill window
        opens that much sooner). Units chain on the device-resident scan
        carry, so the group costs one device->host fetch regardless of
        unit count — the host-round-trip amortisation of the old K-step
        program is preserved (see decode.decode_multi_step)."""
        if use_short:
            self.total_short_dispatches += 1
        group = self._submit_group(1 if use_short else self._decode_units)
        return self._fetch_group(group)

    def _shared_decode_args(self) -> tuple:
        """Device-convert the dispatch args that are invariant across a
        group's units (tables, stops, sampling state) ONCE per group —
        per-unit jnp.asarray would re-upload [B, maxP] block tables
        units-fold on exactly the remote-link path this design exists
        to amortise."""
        return (jnp.asarray(self.kv.block_tables),
                jnp.asarray(self.stop_positions),
                jnp.asarray(self._slot_keys), jnp.asarray(self.temperature),
                jnp.asarray(self.top_k), jnp.asarray(self.top_p))

    @engine_thread_only
    def _submit_decode(self, chain_from=None, shared=None) -> dict:
        """Dispatch ONE decode unit WITHOUT fetching results.

        ``chain_from``: a previous dispatch record (unit or group) — its
        final scan carry (tokens, positions) feeds this dispatch as
        device arrays, so back-to-back dispatches queue on the device
        with no host round trip between them. Everything else (tables,
        stops, sampling state) is host state, valid because step() only
        chains when no slot was re-armed in between.

        Returns a pending record carrying the un-fetched device arrays
        plus the per-slot request-id snapshot apply-time masking needs."""
        if chain_from is not None:
            tokens, positions = (chain_from["next_tokens"],
                                 chain_from["next_positions"])
        else:
            tokens = jnp.asarray(self.last_tokens)
            positions = jnp.asarray(self.positions)
        if shared is None:
            shared = self._shared_decode_args()
        (sampled_seq, next_toks, next_pos, self.kv.k_pages, self.kv.v_pages,
         *moe_stats) = self._decode_jit(
                self.params, self.kv.k_pages, self.kv.v_pages,
                tokens, positions, *shared,
                *((self.kv.state,) if self.cfg.is_recurrent else ()))
        if self.cfg.is_recurrent:
            *moe_stats, self.kv.state = moe_stats
        return {
            "sampled": sampled_seq, "moe_stats": moe_stats,
            "next_tokens": next_toks,
            "next_positions": next_pos,
            "req_ids": [r.request_id if r is not None else None
                        for r in self.scheduler.slots],
            "active": self.active.copy(),
        }

    @engine_thread_only
    def _submit_group(self, n_units: int, chain_from=None) -> dict:
        """Chain ``n_units`` unit dispatches; return a group record.

        The group exposes the same keys a unit does (last unit's carry,
        first unit's slot snapshot — identical across units, nothing
        re-arms between submissions), so groups chain onto groups in the
        pipelined path exactly like units chain onto units."""
        units = []
        pend = chain_from
        # a chained dispatch starts where the one in flight ends, which the
        # host's positions have not seen yet; an idle slot sits at position
        # 0 and the kernel still fetches the one page its table names
        lag = (len(chain_from["units"]) * self._decode_unit_len
               if chain_from is not None else 0)
        live_pages = int(np.minimum(
            (self.positions + lag * self.active) // self.kv.page_size + 1,
            self.kv.max_pages_per_slot).sum())
        self.total_live_pages += live_pages
        self.total_table_pages += self.kv.block_tables.size
        ids = {}
        if self.cfg.is_recurrent:
            # state updates this dispatch asks of the state-space layers
            # (live slots x steps; stats()["ssm"]["slot_steps"] counts them
            # at the fetch)
            ids["ssm_slot_steps"] = (int(self.active.sum()) * n_units
                                     * self._decode_unit_len)
        if self.cfg.is_latent:
            # what one walked page costs over the layers (llmctl trace
            # summarize: "latent attention walked N pages of B bytes")
            ids["latent_page_bytes"] = (self.kv.bytes_per_token
                                        * self.kv.page_size)
        with self.spans.phase("llmctl.engine.decode.submit", units=n_units,
                              active=int(self.active.sum()),
                              live_pages=live_pages,
                              table_pages=self.kv.block_tables.size, **ids):
            shared = self._shared_decode_args()
            for _ in range(n_units):
                pend = self._submit_decode(chain_from=pend, shared=shared)
                units.append(pend)
        self.spans.dispatched()
        return {
            "units": units,
            "next_tokens": units[-1]["next_tokens"],
            "next_positions": units[-1]["next_positions"],
            "req_ids": units[0]["req_ids"],
            "active": units[0]["active"],
        }

    @engine_thread_only
    def _fetch_group(self, group: dict) -> np.ndarray:
        """One batched device->host fetch of a group's sampled tokens:
        [n_units * unit_len, B]. jax.device_get issues the per-unit
        transfers together, so the link round trip is paid once per
        group, not per unit."""
        with self.spans.phase(
                "llmctl.engine.decode.wait",
                steps=len(group["units"]) * self._decode_unit_len):
            arrs, moe_stats = jax.device_get(
                ([u["sampled"] for u in group["units"]],
                 [u["moe_stats"] for u in group["units"]]))
        self.spans.fetched()
        for unit_stats in moe_stats:
            for st in unit_stats:       # none for a dense model
                self._count_moe(st, steps=self._decode_unit_len, decode=True)
        out = np.concatenate([np.asarray(a) for a in arrs], axis=0)
        self.total_decode_steps += out.shape[0]
        self.total_padded_slot_steps += out.shape[0] * int(
            self.serve_cfg.max_batch_size - group["active"].sum())
        if self.cfg.is_recurrent:
            self.ssm_slot_steps += out.shape[0] * int(group["active"].sum())
        return out

    @engine_thread_only
    def _drain_pending(self) -> None:
        """Fetch + apply the in-flight pipelined dispatch group (if any)
        so the engine's host state catches up with the device before a
        non-chainable action (prefill of a re-armed slot, short dispatch,
        speculation, shutdown)."""
        prev, self._pending = self._pending, None
        if prev is None:
            return
        sampled = self._fetch_group(prev)
        with self.spans.phase("llmctl.engine.apply"), self.lock:
            self._apply_decode(sampled, snapshot=prev)
            self.scheduler.step_finished(self.eos_token_id)

    # -- speculative decode --------------------------------------------------

    @engine_thread_only
    def spec_state_of(self, slot: int) -> Optional[dict]:
        """The slot's SpecState as a plain-scalar dict (rides the
        migration/handoff payload manifest and the worker wire) — None
        when speculation is off or the slot carries no state. Callers:
        migration.stop_and_copy (payload "spec" entry) and _preempt
        (request-side fallback for payload-less requeues)."""
        if not 0 <= slot < len(self._spec_state):
            return None
        st = self._spec_state[slot]
        return st.to_dict() if st is not None else None

    def _spec_impl(self, params, k_pages, v_pages, tokens, positions,
                   tables, stops, slot_keys, temp, top_k, top_p):
        from .speculative import verify_and_decode
        # verify (1 forward over the window) + K-1 plain decode steps: the
        # same forward-pass count as multi-step decode, yielding n_accepted
        # extra tokens. NOT free in practice: the verify window measures
        # ~9 decode-steps of extra cost (BASELINE.md round 2), so low
        # acceptance is a net loss — the adaptive check in step() falls
        # back to plain decode when acceptance stays under
        # speculative_min_acceptance.
        return verify_and_decode(
            params, tokens, positions, k_pages, v_pages, tables, stops,
            slot_keys, temp, top_k, top_p, self.cfg,
            num_decode_steps=max(
                self.serve_cfg.decode_steps_per_dispatch - 1, 0),
            attn_impl=self._attn_impl, w4_kernel_ok=self._w4_kernel_ok,
            w8_kernel_ok=self._w8_kernel_ok)

    @engine_thread_only
    def _spec_device(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One fused speculative dispatch: propose drafts on host (prompt-
        lookup over each slot's prompt+generated context), then verify +
        K-1 decode steps on device. Returns (emitted [B, T], n_emit [B],
        decode_seq [K-1, B])."""
        with self.spans.phase("llmctl.engine.decode.submit", units=1,
                              active=int(self.active.sum())):
            T = max(self.serve_cfg.speculative_tokens, 2)
            B = self.serve_cfg.max_batch_size
            tokens = np.zeros((B, T), np.int32)
            tokens[:, 0] = self.last_tokens
            # draftless rows repeat the last token — acceptance is self-
            # verifying (draft == argmax), so a lucky repeat is correct greedy
            # output, not an error
            tokens[:, 1:] = self.last_tokens[:, None]
            from .speculative import propose_ngram_draft
            n_drafted = 0
            for slot, req in enumerate(self.scheduler.slots):
                if req is None or not self.active[slot] \
                        or self.temperature[slot] > 0:
                    continue
                # per-slot ADAPTIVE window (SpecState): only w-1 drafts are
                # proposed and counted for this row; positions [w, T) keep
                # the repeat-last fallback (the compiled program's T is
                # static — the window bounds proposal work and the
                # acceptance statistics, not the dispatch shape). Every
                # greedy row counts its window's drafts (ngram or the
                # repeat fallback) — counting only ngram rows would let
                # fallback acceptances push spec_acceptance above 1.0.
                st = self._spec_state[slot]
                w = min(st.window, T) if st is not None else T
                n_drafted += w - 1
                # bounded lookback keeps proposal O(window), not O(context)
                ctx = self._ctx[slot, max(self._ctx_len[slot] - 1024, 0):
                                self._ctx_len[slot]]
                # draft_fn is injectable (benchmarks dial acceptance exactly
                # via oracle/corrupted drafts — experiments/spec_crossover.py);
                # production default is the prompt-lookup proposer
                draft_fn = getattr(self, "draft_fn", None)
                if draft_fn is not None:
                    draft = draft_fn(ctx, w - 1,
                                     self.serve_cfg.speculative_ngram)
                else:
                    draft = propose_ngram_draft(
                        ctx, w - 1, self.serve_cfg.speculative_ngram)
                if draft is not None:
                    tokens[slot, 1:w] = draft
            emitted, n_emit, decode_seq, self.kv.k_pages, self.kv.v_pages = \
                self._spec_jit(
                    self.params, self.kv.k_pages, self.kv.v_pages,
                    jnp.asarray(tokens), jnp.asarray(self.positions),
                    jnp.asarray(self.kv.block_tables),
                    jnp.asarray(self.stop_positions),
                    jnp.asarray(self._slot_keys), jnp.asarray(self.temperature),
                    jnp.asarray(self.top_k), jnp.asarray(self.top_p))
        self.spans.dispatched()
        with self.spans.phase("llmctl.engine.decode.wait"):
            emitted, n_emit = np.asarray(emitted), np.asarray(n_emit)
            decode_seq = np.asarray(decode_seq)
        self.spans.fetched()
        self.total_spec_dispatches += 1
        self.total_spec_drafts += n_drafted
        self.total_decode_steps += 1 + decode_seq.shape[0]
        self.total_padded_slot_steps += (1 + decode_seq.shape[0]) * int(
            B - self.active.sum())
        return emitted, n_emit, decode_seq

    @engine_thread_only
    def _apply_speculative(self, emitted: np.ndarray, n_emit: np.ndarray,
                           decode_seq: np.ndarray) -> None:
        """Host bookkeeping for one fused dispatch (under self.lock):
        n_emit verified tokens, then the trailing decode-scan rows.
        Positions advance in lockstep with what is recorded so slot length
        always matches the KV state."""
        for slot, req in enumerate(self.scheduler.slots):
            if req is None or not self.active[slot]:
                continue
            stream = [int(emitted[slot, k])
                      for k in range(int(n_emit[slot]))]
            stream += [int(t) for t in decode_seq[:, slot]]
            accepted = []
            for tok in stream:
                self.positions[slot] += 1
                req.record_token(tok)
                accepted.append(tok)
                self.last_tokens[slot] = tok
                if (req.cancel_requested
                        or req.should_stop(self.eos_token_id) is not None):
                    break
            end = self._ctx_len[slot] + len(accepted)
            self._ctx[slot, self._ctx_len[slot]:end] = accepted
            self._ctx_len[slot] = end
            if self.temperature[slot] <= 0:
                # device-side acceptance (n_emit - 1 drafts verified), not
                # recorded count: a stop condition can truncate recording
                # after the device already verified the draft. Capped at
                # the slot's PROPOSED window — repeat-fallback positions
                # beyond it can still verify (correct greedy output), but
                # crediting them would push acceptance above 1.0.
                st = self._spec_state[slot]
                T = max(self.serve_cfg.speculative_tokens, 2)
                w = min(st.window, T) if st is not None else T
                acc = min(max(int(n_emit[slot]) - 1, 0), w - 1)
                self.total_spec_accepted += acc
                if st is not None:
                    # EWMA + adaptive window (SpecState.observe) — the
                    # state that migrates with the sequence
                    st.observe(acc, w - 1, max_window=T)
            if accepted and self.on_token is not None:
                with self.spans.phase("llmctl.engine.deliver"):
                    self.on_token(req, accepted)

    @engine_thread_only
    def _apply_decode(self, sampled_seq: np.ndarray,
                      snapshot: Optional[dict] = None) -> None:
        """Host bookkeeping for K decode steps (called under self.lock).

        Continuing slots accept all K tokens (positions advance in lockstep
        with the device scan carry); slots that hit a stop condition
        mid-scan stop accepting — their trailing device iterations wrote
        reserved pages that are released with the slot.

        ``snapshot``: the dispatch's pending record when applying a
        PIPELINED dispatch one step late — slots whose request changed
        since submission (finished + released while this dispatch was in
        flight) are skipped: their rows decoded past the old request's
        life into freed pages, which is harmless (the device executes any
        subsequent prefill AFTER this program, so reallocated pages are
        overwritten in order) but must not be credited to anyone."""
        for slot, req in enumerate(self.scheduler.slots):
            if req is None or not self.active[slot]:
                continue
            if snapshot is not None and (
                    req.request_id != snapshot["req_ids"][slot]
                    or not snapshot["active"][slot]):
                continue
            accepted = []
            for k in range(sampled_seq.shape[0]):
                self.positions[slot] += 1
                tok = int(sampled_seq[k, slot])
                req.record_token(tok)
                accepted.append(tok)
                self.last_tokens[slot] = tok
                if (req.cancel_requested
                        or req.should_stop(self.eos_token_id) is not None):
                    break
            end = self._ctx_len[slot] + len(accepted)
            self._ctx[slot, self._ctx_len[slot]:end] = accepted
            self._ctx_len[slot] = end
            if accepted and self.on_token is not None:
                with self.spans.phase("llmctl.engine.deliver"):
                    self.on_token(req, accepted)

    # -- lifecycle -----------------------------------------------------------

    def release(self) -> None:
        """Free this engine's device memory: weights, KV pool, and every
        compiled program. The engine is unusable afterwards.

        Benchmark sweeps build engines back-to-back in one process (each
        sweep point needs its own compile-before-timing warmup); without an
        explicit release the dead engine's weights + pool + executables
        survive until GC, and the next engine's pool allocation can
        RESOURCE_EXHAUST the chip — observed on the 4th engine of a
        round-3 serve-load sweep.

        Only THIS engine's references are dropped (the jitted wrappers own
        their executables, so they die with the attributes). The
        engine<->scheduler host cycle is collectable once the caller drops
        its own reference — a caller needing immediate reclamation should
        `gc.collect()` after that, and may additionally
        `jax.clear_caches()` if (and only if) no other live jitted code in
        the process would mind losing its compilation cache."""
        self.params = None
        self.kv = None
        self._pending = None
        self._decode_jit = None
        self._spec_jit = None
        self._prefill_cache.clear()
        self._partial_prefills.clear()

    def _swap_bytes_in_queue(self) -> int:
        """Host bytes currently held by swapped-out waiting requests.
        Computed lazily (the queue is bounded and preemption is rare)
        rather than via incremental counters that cancel paths could
        leave stale."""
        total = 0
        for r in self.scheduler.waiting:
            if r.swapped_kv is not None:
                for part in (r.swapped_kv["pages"]["k"],
                             r.swapped_kv["pages"]["v"]):
                    if isinstance(part, dict):
                        total += sum(a.nbytes for a in part.values())
                    else:
                        total += part.nbytes
        return total

    @engine_thread_only
    def _restore_swapped(self, req: Request) -> bool:
        """Swap-in (preemption=swap readmission): allocate pages, write the
        saved K/V back, and make the slot live for decode — NO prefill
        compute. Returns False when the pool can't hold the restore; the
        caller clears swapped_kv and falls back to recompute-prefill."""
        slot = req.slot
        rid = req.request_id
        saved = req.swapped_kv
        with self.lock:
            try:
                ok = self.kv.restore_slot(slot, saved["pages"])
            except (ValueError, KeyError, TypeError) as e:
                # malformed payload (courier bug / schema drift): treat
                # exactly like a pool-full restore — the caller clears
                # swapped_kv and re-prefills from tokens. Wrong tokens
                # are the one unacceptable outcome; extra compute is not.
                logger.warning(
                    "swap-in payload for %s rejected (%s); falling back "
                    "to re-prefill", rid, e)
                ok = False
            if not ok:
                return False
            self._reserved_pages -= self._reserved_by.pop(rid, 0)
            self._req_slot[rid] = slot
        self._admitted_counter += 1
        self._slot_seq[slot] = self._admitted_counter
        self._seed_slot(slot, req.assigned_seed)
        # migrated speculative state rides the payload manifest (the
        # courier-aware half: a handed-off/migrated sequence resumes
        # with its tuned window, not a cold proposer); _arm_slot reads
        # it off the request
        if isinstance(saved.get("spec"), dict):
            req.spec_state = saved["spec"]
        self._arm_slot(req, saved["last_token"], saved["positions"],
                       req.context_tokens)
        req.swapped_kv = None
        self.total_swap_ins += 1
        return True

    @engine_thread_only
    def _preempt(self, slot: int) -> None:
        """Evict ``slot``'s RUNNING request (newest-first victim policy) so
        an older stream can grow its page chain. Recompute-style: the
        request re-enters the waiting queue head and re-prefills
        prompt+generated on readmission — from prefix-cached pages when
        caching is on (its fully-written pages are published here, so a
        prompt re-prefill is usually just the last partial page).

        Caller holds self.lock."""
        req = self.scheduler.slots[slot]
        rid = req.request_id
        written = int(self.positions[slot])   # KV entries actually present
        if self.serve_cfg.preemption == "swap" and \
                self._swap_bytes_in_queue() < \
                self.serve_cfg.swap_space_gb * 1e9:
            # swap-out: pages to host memory; readmission writes them
            # back instead of re-prefilling (zero recompute). Over the
            # host budget, fall back to recompute (the swap dict stays
            # unset, so readmission takes the prefill path)
            req.swapped_kv = {
                "pages": self.kv.extract_slot(slot),
                "positions": written,
                "last_token": int(self.last_tokens[slot]),
            }
            spec = self.spec_state_of(slot)
            if spec is not None:
                req.swapped_kv["spec"] = spec
        if self._prefix_caching:
            from .kv_cache import prefix_page_hashes
            ctx = req.context_tokens
            full = written // self.kv.page_size
            hashes = prefix_page_hashes(ctx[:full * self.kv.page_size],
                                        self.kv.page_size)
            table = self.kv.block_tables[slot]
            # register BEFORE release: released pages that carry a hash
            # stay evictable (content kept) instead of returning to _free
            self.kv.register_pages(
                [(hashes[j], int(table[j])) for j in range(full)])
        # carry the tuned speculative state with the request: the resume
        # (local readmission, drain migration, handoff — all funnel
        # through here) re-arms from it instead of a cold proposer
        spec = self.spec_state_of(slot)
        if spec is not None:
            req.spec_state = spec
        pins = self._prefix_pins.pop(rid, None)
        self.kv.release(slot)
        if pins:
            self.kv.unpin_pages(pins)
        self._req_slot.pop(rid, None)
        self.active[slot] = False
        self.positions[slot] = 0
        self.stop_positions[slot] = 0
        self._ctx_len[slot] = 0
        self._spec_state[slot] = None
        self.scheduler.preempt_slot(slot)
        self.total_preemptions += 1
        logger.info("preempted %s (slot %d, %d tokens generated) to free "
                    "KV pages", rid, slot, len(req.generated_tokens))

    @engine_thread_only
    def _ensure_decode_capacity(self) -> None:
        """Grow every active slot's page chain to cover the next dispatch's
        writes (on-demand admission). Oldest slots grow first; when the
        pool is dry the newest resident request is preempted and the grow
        retried — the oldest stream can always advance, so the system
        drains even at 100% KV pressure.

        Caller holds self.lock."""
        if self.serve_cfg.admission != "ondemand":
            return
        # lag: un-applied pipelined dispatch GROUP in flight — the
        # device is already a full group (units * unit_len >= K; the
        # ceil split can exceed K) past the host's positions, so the
        # NEXT (chained) dispatch writes up to positions + lag + k
        lag = (self._decode_units * self._decode_unit_len
               if self._pending is not None else 0)
        k = self._decode_lookahead + lag
        order = sorted(np.flatnonzero(self.active),
                       key=lambda i: self._slot_seq[i])
        for i in order:
            i = int(i)
            if not self.active[i]:      # already preempted as a victim
                continue
            target = min(int(self.positions[i]) + k,
                         int(self.stop_positions[i]))
            while not self.kv.extend_slot(i, target):
                victims = [int(j) for j in np.flatnonzero(self.active)
                           if int(j) != i]
                if not victims:
                    # alone and still can't grow: this request's own
                    # footprint exceeds the pool — admission's
                    # can_ever_allocate bounds prompt+max_tokens, so only
                    # reachable with a pool smaller than one request
                    self._preempt(i)
                    break
                self._preempt(max(victims, key=lambda j: self._slot_seq[j]))

    def _on_release(self, req: Request) -> None:
        # admitted-but-never-prefilled (cancel/failure before _prefill):
        # return the admission reservation so capacity can't leak
        self._reserved_pages -= self._reserved_by.pop(req.request_id, 0)
        pins = self._prefix_pins.pop(req.request_id, None)
        if pins:
            self.kv.unpin_pages(pins)
        slot = self._req_slot.pop(req.request_id, None)
        if slot is not None:
            self.kv.release(slot)
            self.active[slot] = False
            self.positions[slot] = 0
            self.stop_positions[slot] = 0
            self._spec_state[slot] = None
        if self.on_finish is not None:
            # an HTTP handler's cancel gets here too: there the span is a
            # bare annotation (SpanRecorder.phase)
            with self.spans.phase("llmctl.engine.deliver"):
                self.on_finish(req)

    @engine_thread_only
    def step(self) -> int:
        """One engine iteration: admit+prefill, then one decode step for all
        running slots. Returns the number of active requests.

        Device compute (prefill forward, decode step) runs OUTSIDE the lock
        so HTTP handlers are never blocked behind a forward pass; only the
        cheap scheduler/page bookkeeping is serialized.
        """
        static = self.serve_cfg.scheduler == "static"
        spans = self.spans
        with spans.phase("llmctl.engine.admit"), self.lock:
            if static:
                # static batches form only when fully drained — there are no
                # resident streams to protect, so no prefill budget applies
                admitted = ([] if self.scheduler.active_count > 0
                            else self.scheduler.admit())
            else:
                admitted = self.scheduler.admit(
                    self.serve_cfg.prefill_budget_tokens)
            spans.set_busy(self.scheduler.active_count > 0)
            if admitted:
                spans.annotate(admitted=len(admitted))
        C = self._chunk_tokens
        pending = []
        for req in admitted:
            if req.swapped_kv is not None \
                    and not req.swapped_kv.get("partial"):
                # preemption=swap readmission: write the saved KV back
                # (no prefill); on pool pressure fall back to recompute.
                # PARTIAL payloads (crash-salvaged migration pre-copies)
                # are not decode-resumable — they take the _prefill path,
                # which writes the covered pages and computes the tail.
                with spans.phase("llmctl.engine.capacity"):
                    restored = self._restore_swapped(req)
                if restored:
                    continue
                req.swapped_kv = None
            # route on the full re-prefill CONTEXT: a preempted request
            # resumes with prompt+generated, which can exceed the chunk
            # threshold even when the original prompt didn't — and the
            # high-KV-pressure regime that preempts is exactly where a
            # dense multi-thousand-token dispatch would stall residents
            # pipeline STAGE requests always take the chunked path: their
            # value is the per-chunk page-publish cadence the forward
            # shipper overlaps transfers against, chunk threshold or not
            if (C > 0 and len(req.context_tokens) > C
                    and req.swapped_kv is None) \
                    or (req.pipeline_stage is not None
                        and req.swapped_kv is None):
                start = self._start_chunked_prefill
            else:
                start = self._prefill
            with spans.phase("llmctl.engine.prefill.host",
                             request_id=req.request_id,
                             tokens=len(req.context_tokens)):
                dispatched = start(req)
            if dispatched is not None:
                pending.append(dispatched)
        # advance every in-flight chunked prefill by one chunk; completed
        # ones join this step's finish batch
        if self._partial_prefills:
            with spans.phase("llmctl.engine.prefill.host"):
                pending += self._advance_chunked_prefills()
        for req, token in pending:
            self._finish_prefill(req, token)
        if pending:
            with spans.phase("llmctl.engine.apply"), self.lock:
                # prompt-is-whole-request edge: finished on the first token
                self.scheduler.step_finished(self.eos_token_id)
            if self.on_prefill_complete is not None:
                # prefill-complete boundary hook (disaggregated serving):
                # fires with no locks held for requests that survived the
                # boundary still RUNNING — the fleet replica may extract
                # the sequence WITH its KV before this engine spends a
                # single decode dispatch on it
                for req, _tok in pending:
                    if req.state is RequestState.RUNNING:
                        with spans.phase("llmctl.engine.deliver",
                                         request_id=req.request_id):
                            self.on_prefill_complete(req)
        with spans.phase("llmctl.engine.capacity"), self.lock:
            # on-demand admission: make sure every active slot has pages
            # for one dispatch of writes, preempting newest-first if the
            # pool is dry — BEFORE the dispatch reads the block tables
            preempted = self.total_preemptions
            self._ensure_decode_capacity()
            if self.total_preemptions > preempted:
                spans.annotate(preempted=self.total_preemptions - preempted)
            # latency-adaptive dispatch decision (needs the lock: it
            # inspects the queue head's admissibility)
            use_short = self._short_dispatch_ok()
        if any(self.active):
            # speculative path only when a greedy stream is resident: for
            # sampled rows a verify dispatch yields 1 token vs K from
            # multi-step decode, so an all-sampled batch stays on decode.
            # Adaptive kill switch: once 64 dispatches have measured a
            # draft-acceptance rate under the configured floor, speculation
            # is a pure loss (the verify window isn't free) — fall back to
            # plain multi-step decode permanently.
            if (self._spec_jit is not None and self.total_spec_dispatches >= 64
                    and self.total_spec_accepted
                    < self.serve_cfg.speculative_min_acceptance
                    * self.total_spec_drafts):
                logger.warning(
                    "speculative decode disabled: acceptance %.3f < %.3f "
                    "after %d dispatches",
                    self.total_spec_accepted / max(self.total_spec_drafts, 1),
                    self.serve_cfg.speculative_min_acceptance,
                    self.total_spec_dispatches)
                self._spec_jit = None
            if (self._spec_jit is not None
                    and bool((self.temperature[self.active] <= 0).any())):
                # a pending pipelined dispatch (set while the batch was
                # all-sampled) leaves host tokens/positions K steps stale —
                # the spec dispatch builds its drafts and window from host
                # state, so it must catch up first
                self._drain_pending()
                emitted, n_emit, decode_seq = self._spec_device()
                with spans.phase("llmctl.engine.apply"), self.lock:
                    self._apply_speculative(emitted, n_emit, decode_seq)
                    self.scheduler.step_finished(self.eos_token_id)
            elif (self.serve_cfg.pipelined_decode and not static
                  and not use_short and not admitted and not pending
                  and not self._partial_prefills
                  and 2 * int(self.active.sum())
                  >= self.serve_cfg.max_batch_size):
                # occupancy gate (>= half the slots resident): at light
                # load a chained pair queues up to 2K device steps ahead
                # of any arrival's prefill window — the same TTFT hazard
                # the latency-adaptive short dispatch exists to shrink —
                # while the goodput win only materialises when the batch
                # is busy enough for the RTT to be the bottleneck
                # PIPELINED decode: keep one un-fetched dispatch in flight.
                # Submit the next dispatch chained on the previous one's
                # device-resident scan carry, THEN fetch/apply the previous
                # one — the per-dispatch host round trip (dispatch + sync;
                # not re-measured on a directly attached chip) overlaps
                # device execution instead of serialising with it. Chains break
                # whenever a slot is (re)armed — any prefill this step, the
                # short program, speculation — because the chained inputs
                # (tokens/positions) would be stale for that slot; mere
                # FINISHES don't break the chain (the stale row decodes
                # into its freed pages, which the device overwrites in
                # program order before any reuse, and apply() masks it out
                # via the request-id snapshot).
                prev = self._pending
                self._pending = self._submit_group(
                    self._decode_units, chain_from=prev)
                if prev is not None:
                    sampled = self._fetch_group(prev)
                    with spans.phase("llmctl.engine.apply"), self.lock:
                        self._apply_decode(sampled, snapshot=prev)
                        self.scheduler.step_finished(self.eos_token_id)
            else:
                self._drain_pending()
                # the drain may have finished every resident request —
                # don't burn a dispatch on an all-inactive batch
                if any(self.active):
                    sampled = self._decode_device(use_short)
                    with spans.phase("llmctl.engine.apply"), self.lock:
                        self._apply_decode(sampled)
                        self.scheduler.step_finished(self.eos_token_id)
        with self.lock:
            active = self.scheduler.active_count
        spans.set_busy(active > 0)
        return active

    def fail_all(self, error: str) -> None:
        """Fail every queued and resident request (engine-thread crash path);
        waiters fire via on_finish instead of hanging to the HTTP timeout."""
        with self.lock:
            failed = self.scheduler.fail_all(error)
            # in-flight pipelined dispatch references the failed slots'
            # state; its results must never be applied
            self._pending = None
            self.spans.reset_in_flight()
            # fail_all released every slot (incl. PREFILLING); advancing a
            # stale chunked prefill would write into freed pages
            self._partial_prefills.clear()
        if self.on_finish is not None:
            for r in failed:
                # slot holders were already notified via _on_release; the
                # waiter registry pop is idempotent so double-notify is safe
                self.on_finish(r)

    def recover(self) -> bool:
        """Restore engine invariants after a failed step and probe the device.

        The jitted prefill/decode programs donate the KV page buffers; an
        exception after dispatch leaves ``self.kv.k_pages/v_pages`` pointing
        at deleted arrays, so every later step would raise "Array has been
        deleted" forever. Reallocate them (all requests were already failed
        by fail_all, so no live KV is lost) and run a tiny device op to
        check the backend is usable again. Returns True when healthy — not
        while a program that failed before its first run has yet to run:
        the probe below cannot see that failure, and a program that did
        not compile fails the next request of its shape too
        (``failed_programs``)."""
        try:
            reallocated = False
            for name in ("k_pages", "v_pages"):
                buf = getattr(self.kv, name)
                if any(leaf.is_deleted()
                       for leaf in jax.tree_util.tree_leaves(buf)):
                    setattr(self.kv, name,
                            self.kv._new_pages(buf.shape, self.kv.dtype))
                    reallocated = True
            if self.kv.state is not None and any(
                    leaf.is_deleted() for leaf in self.kv.state.values()):
                self.kv.state = self.kv.new_state()
            if reallocated:
                # zeroed buffers invalidate every cached prefix page — a
                # future hash hit would attend over all-zero K/V
                self.kv.flush_prefix_cache()
            probe = jnp.zeros((8,), jnp.float32) + 1.0
            if not bool(np.asarray(probe).sum() == 8.0):
                return False
            if self.failed_programs:
                logger.error("engine programs failed to compile and will "
                             "fail again: %s", self.failed_programs)
                return False
            return True
        except Exception:
            logger.exception("engine recovery probe failed")
            return False

    def measure_device_times(self, buckets: Sequence[int] = (),
                             iters: int = 8) -> dict:
        """Calibrate ON-DEVICE phase times: per-bucket prefill ms and
        per-token decode ms, with the host->device link RTT amortised out
        (``iters`` dispatches pipelined behind ONE fence). Writes go to
        scratch page 0 (zero table entries), so live KV is untouched.

        This is the measurement behind ``ttft_device_ms``: wall TTFT
        includes the host<->device round trip of the dispatch; the
        device-time figure = host queue wait + this prefill time
        (VERDICT r2 weak #2: the <200 ms claim must rest on a measured
        device-time number, not RTT arithmetic)."""
        if self.cfg.is_recurrent:
            raise ValueError(
                f"{self.cfg.name} has state-space layers: "
                "measure_device_times is refused (its probes write scratch "
                "pages, and would arm and advance live slots' state)")
        out: dict = {"prefill_ms": {}, "iters": iters}
        kp, vp = self.kv.k_pages, self.kv.v_pages
        # probes DONATE the page buffers: keep self.kv pointed at the
        # live arrays after every dispatch so an exception mid-
        # calibration can't leave the engine holding deleted buffers
        # dense-prefill programs only: the cache also holds
        # ("extend", b)/("chunk", b) tuple keys, which are different
        # programs (and unsortable against ints)
        for bucket in buckets or sorted(
                k for k in self._prefill_cache if isinstance(k, int)):
            fn = self._prefill_fn(bucket)
            tokens = jnp.ones((1, bucket), jnp.int32)
            entries = jnp.zeros((bucket // self.kv.page_size,), jnp.int32)
            length = jnp.asarray([bucket], jnp.int32)
            sampling = self._sampling_args(
                seed_key_data(0), bucket, SamplingParams(temperature=0.0))
            token, kp, vp = fn(self.params, tokens, length, kp, vp, entries,
                               *sampling)                    # warm/compile
            self.kv.k_pages, self.kv.v_pages = kp, vp
            np.asarray(token)
            t0 = time.perf_counter()
            for _ in range(iters):
                token, kp, vp = fn(self.params, tokens, length, kp, vp,
                                   entries, *sampling)
                self.kv.k_pages, self.kv.v_pages = kp, vp
            np.asarray(token)                                 # one fence
            out["prefill_ms"][bucket] = (time.perf_counter() - t0) \
                / iters * 1e3
        # decode: K steps per dispatch, all slots
        K = self._decode_unit_len      # steps per compiled decode dispatch
        zeros_i = jnp.zeros(self.serve_cfg.max_batch_size, jnp.int32)
        # an all-zero block table sends every probe write to the reserved
        # scratch page — the LIVE tables would route position-0 writes
        # into resident requests' first pages
        scratch_tables = jnp.zeros_like(jnp.asarray(self.kv.block_tables))
        dargs = (scratch_tables, zeros_i,
                 jnp.asarray(self._slot_keys),
                 jnp.ones(self.serve_cfg.max_batch_size, jnp.float32),
                 jnp.zeros(self.serve_cfg.max_batch_size, jnp.int32),
                 jnp.ones(self.serve_cfg.max_batch_size, jnp.float32))
        sampled, _, _, kp, vp, *_ = self._decode_jit(
            self.params, kp, vp, zeros_i, zeros_i, *dargs)
        self.kv.k_pages, self.kv.v_pages = kp, vp
        np.asarray(sampled)
        t0 = time.perf_counter()
        for _ in range(iters):
            sampled, _, _, kp, vp, *_ = self._decode_jit(
                self.params, kp, vp, zeros_i, zeros_i, *dargs)
            self.kv.k_pages, self.kv.v_pages = kp, vp
        np.asarray(sampled)
        out["decode_ms_per_token"] = (time.perf_counter() - t0) \
            / (iters * K) * 1e3
        return out

    def run_until_idle(self, max_steps: int = 100_000) -> None:
        self.spans.bind_thread()
        for _ in range(max_steps):
            if self.step() == 0 and self.scheduler.queue_depth == 0:
                return
        raise RuntimeError("run_until_idle: did not drain")

    # -- convenience ---------------------------------------------------------

    def generate(self, prompts: Sequence[Sequence[int]],
                 sampling: Optional[SamplingParams] = None) -> list[Request]:
        """Offline batch generation (bench + tests)."""
        reqs = []
        for i, p in enumerate(prompts):
            r = Request(request_id=f"gen-{i}-{time.monotonic_ns()}",
                        prompt_tokens=list(p),
                        sampling=sampling or SamplingParams())
            if not self.scheduler.add_request(r):
                raise RuntimeError(f"queue full / invalid request: {r.error}")
            reqs.append(r)
        self.run_until_idle()
        return reqs

    def stats(self) -> dict:
        from ..ops.quantization import tree_weight_bytes
        steps = max(self.total_decode_steps, 1)
        return {
            "weight_bytes": tree_weight_bytes(self.params),
            "quantization": self.quantization,
            **self.scheduler.stats(),
            "kv": {**self.kv.stats(),
                   "live_pages": self.total_live_pages,
                   "table_pages": self.total_table_pages},
            "admission": self.serve_cfg.admission,
            "preemptions": self.total_preemptions,
            "preemption_mode": self.serve_cfg.preemption,
            "swap_ins": self.total_swap_ins,
            "swapped_host_bytes": self._swap_bytes_in_queue(),
            "decode_steps": self.total_decode_steps,
            "short_dispatches": self.total_short_dispatches,
            "prefill_tokens": self.total_prefill_tokens,
            "prefill_padded_tokens": self.total_prefill_padded_tokens,
            "prefix_cached_tokens": self.total_prefix_cached_tokens,
            "requeue_cached_tokens": self.total_requeue_cached_tokens,
            "prefix_fetched_tokens": self.total_prefix_fetched_tokens,
            "salvage_tail_fetched_tokens":
                self.total_salvage_tail_fetched_tokens,
            "unexpected_prefills": self.total_unexpected_prefills,
            "partial_restores": self.total_partial_restores,
            "padded_slot_steps": self.total_padded_slot_steps,
            "decode_slot_utilization": round(
                1.0 - self.total_padded_slot_steps
                / (steps * self.serve_cfg.max_batch_size), 4),
            "spec_dispatches": self.total_spec_dispatches,
            "spec_drafts": self.total_spec_drafts,
            "spec_accepted": self.total_spec_accepted,
            "spec_resumes": self.total_spec_resumes,
            "spec_acceptance": round(
                self.total_spec_accepted / max(self.total_spec_drafts, 1), 4),
            "compiled_programs": self.compiled_programs(),
            **({"ssm": {
                "state_bytes": self.kv.state_bytes(),
                "slot_steps": self.ssm_slot_steps,
                # every prefill of such a model is cold: its tokens and
                # the rows its programs computed all go through the scan
                "prefill_tokens": self.total_prefill_tokens,
                "prefill_padded_tokens": self.total_prefill_padded_tokens,
                "refused": dict(self.ssm_refused),
            }} if self.cfg.is_recurrent else {}),
            **({"moe": {
                "choices": self.moe_choices.tolist(),
                # live choices on the experts HELD here, beside those over
                # all the router's experts (equal where all are held)
                "held_choices": int(self.moe_choices.sum()),
                "all_choices": self.moe_all_choices,
                "experts_hit": self.moe_experts_hit,
                "layer_steps": self.moe_layer_steps,
                "decode_experts_hit": self.moe_decode_experts_hit,
                "decode_layer_steps": self.moe_decode_layer_steps,
            }} if self.cfg.is_moe else {}),
            # cumulative, with their own clock: "clock_s", "phases"
            # ({span: {"s": self seconds, "n": calls}}), "starved_s"
            **self.spans.snapshot(),
            # the PROCESS's start-up, on the same clock: llmctl.startup.*
            # phases and one "programs" entry a program's first call
            # (metrics/spans.py StartupRecorder)
            "startup": STARTUP.snapshot(),
        }

    def program_texts(self) -> dict:
        """{program name: optimised HLO text} of the resident decode and
        cold-prefill programs, lowered for shapes like their live
        arguments' and compiled (from the persistent compile cache where it
        is on), NOT run. A device trace names an XLA operation by its HLO
        instruction (``fusion.123``) and says nothing of the named scope it
        was traced under; each instruction's ``op_name`` in this text does
        (``.../ssm_decode/mul``): how a reader of a trace tells the
        state-space update from the matmuls (benchmark/runners/hybrid.py)."""
        def shapes(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        state = (shapes(self.kv.state),) if self.cfg.is_recurrent else ()
        common = shapes((self.params, self.kv.k_pages, self.kv.v_pages))
        i32 = jnp.int32
        texts = {}
        if self._decode_jit is not None:
            texts[self._decode_jit.name] = self._decode_jit.compiled_text(
                *common, *shapes((jnp.asarray(self.last_tokens),
                                  jnp.asarray(self.positions),
                                  *self._shared_decode_args())),
                *state)
        sampling = shapes(self._sampling_args(seed_key_data(0), 0,
                                              SamplingParams()))
        for bucket in [k for k in list(self._prefill_cache)
                       if isinstance(k, int)]:
            program = self._prefill_cache[bucket]
            texts[program.name] = program.compiled_text(
                common[0], jax.ShapeDtypeStruct((1, bucket), i32),
                jax.ShapeDtypeStruct((1,), i32), common[1], common[2],
                jax.ShapeDtypeStruct((bucket // self.kv.page_size,), i32),
                *sampling, *state,
                *((jax.ShapeDtypeStruct((), i32),) if state else ()))
        return texts

    def compiled_programs(self) -> dict:
        """Resident compiled-program inventory by kind: prefill buckets,
        pipelining and speculation all multiply resident executables, so
        the count is first-class observable state: a user seeing an
        unexplained throughput or start-up delta can check whether the
        program population changed before suspecting the schedule. What
        each program cost (trace, lowering, compile or cache read, and
        when) is ``stats()["startup"]["programs"]``."""
        # snapshot: the engine thread inserts new buckets lock-free while
        # a stats request iterates — list() prevents "dict changed size"
        keys = list(self._prefill_cache)
        prefill_dense = sum(1 for k in keys if isinstance(k, int))
        prefill_extend = sum(1 for k in keys
                             if isinstance(k, tuple) and k[0] == "extend")
        prefill_chunk = sum(1 for k in keys
                            if isinstance(k, tuple) and k[0] == "chunk")
        decode = int(self._decode_jit is not None)   # 0 after release()
        spec = int(self._spec_jit is not None)
        return {
            "prefill_dense_buckets": prefill_dense,
            "prefill_extend_buckets": prefill_extend,
            "prefill_chunk_buckets": prefill_chunk,
            "decode": decode,
            # the second (short) decode executable was REMOVED in round
            # 5 — adaptive dispatch chains units of ONE program; the key
            # stays for dashboard compatibility and is always 0
            "decode_short": 0,
            "speculative": spec,
            "total": (prefill_dense + prefill_extend + prefill_chunk
                      + decode + spec),
        }
