"""OpenAI-compatible inference server on aiohttp.

API parity with the reference (reference serve/server.py:286-311):
``POST /v1/completions``, ``GET /v1/models``, ``GET /health`` — plus
``GET /metrics`` (Prometheus text) and ``GET /v1/stats``, closing the
reference's unwired-observability gap (SURVEY §5.5).

Concurrency model: the reference runs generation inside the asyncio event
loop, blocking every HTTP request during each forward pass
(reference server.py:372-386). Here the engine runs in a dedicated thread;
device compute never holds the shared lock (engine.step acquires it only
around scheduler/page bookkeeping), so handlers stay responsive during
forward passes. Completion is signalled per request via an asyncio.Event
set with call_soon_threadsafe from the engine thread — no polling.
"""

from __future__ import annotations

import asyncio
import json
import logging
import threading
import time
import uuid
from typing import Optional

from aiohttp import web

from ..config.schema import ModelConfig, ServeConfig
from ..metrics.spans import STARTUP
from .engine import InferenceEngine
from .scheduler import Request, RequestState, SamplingParams
from .tokenizer import load_tokenizer

logger = logging.getLogger("llmctl.serve.server")


class BadRequest(ValueError):
    """Completion-body validation failure -> HTTP 400 upstream."""


def parse_completion_body(body: dict, tokenizer, vocab_size: int
                          ) -> tuple[list, SamplingParams, bool]:
    """Validate an OpenAI-style /v1/completions body into
    (prompt_tokens, sampling, stream). Shared by the single-server and
    fleet HTTP fronts so the two cannot drift on what they accept.
    Raises BadRequest with a client-facing message."""
    prompt = body.get("prompt", "")
    if isinstance(prompt, list):           # OpenAI also accepts token ids
        # strict: int(t) would silently truncate floats / coerce bools,
        # generating from a different prompt than the client sent
        if any(isinstance(t, bool) or not isinstance(t, int)
               for t in prompt):
            raise BadRequest("prompt token ids must be integers")
        prompt_tokens = list(prompt)
        bad = [t for t in prompt_tokens if not 0 <= t < vocab_size]
        if bad:
            # OOB ids would clamp silently in the embedding gather and
            # produce wrong completions — reject instead
            raise BadRequest(f"prompt token id {bad[0]} outside "
                             f"[0, {vocab_size})")
    else:
        prompt_tokens = tokenizer.encode(str(prompt))
    if not prompt_tokens:
        raise BadRequest("empty prompt")

    seed = body.get("seed")
    if seed is not None and (isinstance(seed, bool)
                             or not isinstance(seed, int)):
        # an unvalidated seed would raise inside the engine thread
        raise BadRequest(f"seed must be an integer, got {seed!r}")
    ignore_eos = body.get("ignore_eos", False)
    if not isinstance(ignore_eos, bool):
        raise BadRequest(f"ignore_eos must be true or false, "
                         f"got {ignore_eos!r}")
    try:
        sampling = SamplingParams(
            temperature=float(body.get("temperature", 1.0)),
            top_k=int(body.get("top_k", 0)),
            top_p=float(body.get("top_p", 1.0)),
            max_tokens=int(body.get("max_tokens", 64)),
            seed=seed,
            ignore_eos=ignore_eos,
        )
    except (TypeError, ValueError) as e:
        raise BadRequest(f"invalid sampling parameter: {e}") from None
    if sampling.max_tokens < 1:
        raise BadRequest(
            f"max_tokens must be >= 1, got {sampling.max_tokens}")
    return prompt_tokens, sampling, bool(body.get("stream", False))


class InferenceServer:
    def __init__(self, model_cfg: ModelConfig, serve_cfg: ServeConfig,
                 params=None, observer=None):
        self.model_cfg = model_cfg
        self.serve_cfg = serve_cfg
        self.tokenizer = load_tokenizer(serve_cfg.artifact or None,
                                        model_cfg.vocab_size)
        self.engine = InferenceEngine(
            model_cfg, serve_cfg, params=params,
            eos_token_id=getattr(self.tokenizer, "eos_token_id", None))
        self.observer = observer or (lambda event, payload: None)
        self._lock = self.engine.lock
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._recent_latencies: list[float] = []
        self._recent_ttfts: list[float] = []
        self._engine_error: Optional[str] = None
        self._engine_error_count = 0
        self._waiters: dict[str, tuple[asyncio.AbstractEventLoop, asyncio.Event]] = {}
        # streaming requests: request_id -> (loop, asyncio.Queue of token
        # batches; None = finished)
        self._streams: dict[str, tuple] = {}
        self.engine.on_finish = self._notify_finished
        self.engine.on_token = self._notify_tokens
        self.app = self._build_app()

    def _notify_finished(self, req) -> None:
        """Engine-thread callback: wake the handler awaiting this request."""
        waiter = self._waiters.pop(req.request_id, None)
        if waiter is not None:
            loop, event = waiter
            loop.call_soon_threadsafe(event.set)
        stream = self._streams.pop(req.request_id, None)
        if stream is not None:
            loop, q = stream
            loop.call_soon_threadsafe(q.put_nowait, None)   # end-of-stream

    def _notify_tokens(self, req, tokens: list) -> None:
        """Engine-thread callback: push a freshly decoded token batch to the
        request's SSE stream (multi-step decode delivers up to K at once)."""
        stream = self._streams.get(req.request_id)
        if stream is not None:
            loop, q = stream
            loop.call_soon_threadsafe(q.put_nowait, list(tokens))

    # -- engine thread -------------------------------------------------------

    def _engine_loop(self) -> None:
        logger.info("engine thread started")
        spans = self.engine.spans
        spans.bind_thread()
        while not self._stop.is_set():
            with self._lock:
                busy = (self.engine.scheduler.queue_depth > 0
                        or self.engine.scheduler.active_count > 0)
            if not busy:
                with spans.phase("llmctl.engine.idle"):
                    self._wake.wait(timeout=0.05)
                    self._wake.clear()
                continue
            # step() does its own fine-grained locking; compute runs unlocked
            try:
                self.engine.step()
                # a successful step clears the degraded flag so a transient
                # error doesn't leave /health at 503 forever (the cumulative
                # count stays visible for operators) — unless a program
                # failed to compile: other shapes still serve, but the next
                # request of that shape fails again, so /health keeps
                # saying so
                if not self.engine.failed_programs:
                    self._engine_error = None
            except Exception as e:  # device/runtime error: fail loudly, not
                # silently — in-flight requests get FAILED (waiters fire),
                # /health reports the outage, and the loop keeps serving.
                logger.exception("engine step failed")
                self._engine_error = f"{type(e).__name__}: {e}"
                self._engine_error_count += 1
                try:
                    self.engine.fail_all(self._engine_error)
                except Exception:
                    logger.exception("fail_all after engine error failed")
                # Reallocate donated-then-deleted KV buffers and probe the
                # device. On success, clear the degraded flag here — fail_all
                # drained every request, so an idle server would otherwise
                # hold /health at 503 until external traffic arrived despite
                # the 503 (load balancers gating on /health would never send
                # the request that clears it).
                if self.engine.recover():
                    self._engine_error = None
                else:
                    logger.error("engine recovery failed; /health degraded")
        logger.info("engine thread stopped")

    def start_engine(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(target=self._engine_loop,
                                            daemon=True, name="llmctl-engine")
            self._thread.start()

    def stop_engine(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    # -- request handling ----------------------------------------------------

    async def _await_request(self, req: Request, event: asyncio.Event,
                             timeout: float = 600.0) -> None:
        try:
            await asyncio.wait_for(event.wait(), timeout=timeout)
        except asyncio.TimeoutError:
            raise asyncio.TimeoutError(
                f"request {req.request_id} timed out") from None

    async def handle_completions(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)

        try:
            prompt_tokens, sampling, stream = parse_completion_body(
                body, self.tokenizer, self.model_cfg.vocab_size)
        except BadRequest as e:
            return web.json_response({"error": str(e)}, status=400)
        # generation by diffusion over blocks: beside ``token_ids``, the
        # denoise step at which each token was fixed (what lets a reference
        # follow the served trajectory)
        want_steps = body.get("return_unmask_steps", False)
        if not isinstance(want_steps, bool) or (
                want_steps and not self.model_cfg.is_diffusion):
            return web.json_response({"error": (
                "return_unmask_steps must be true or false, and true only "
                f"for a model that generates by diffusion over blocks "
                f"({self.model_cfg.name} does not)")}, status=400)
        # self-drafting (``speculative: mtp``): beside ``token_ids``, the
        # draft the prediction module had made for each emitted position
        # and the main stack verified (-1: none), and whether it stood
        want_drafts = body.get("return_draft_tokens", False)
        if not isinstance(want_drafts, bool) or (
                want_drafts and self.serve_cfg.speculative != "mtp"):
            return web.json_response({"error": (
                "return_draft_tokens must be true or false, and true only "
                "for a server that drafts with the model's prediction "
                "module (speculative: mtp)")}, status=400)
        req = Request(request_id=f"cmpl-{uuid.uuid4().hex[:24]}",
                      prompt_tokens=prompt_tokens, sampling=sampling)
        loop = asyncio.get_running_loop()
        event = asyncio.Event()
        self._waiters[req.request_id] = (loop, event)
        token_q: Optional[asyncio.Queue] = None
        if stream:
            token_q = asyncio.Queue()
            self._streams[req.request_id] = (loop, token_q)
        with self._lock:
            accepted = self.engine.scheduler.add_request(req)
        if not accepted:
            self._waiters.pop(req.request_id, None)
            self._streams.pop(req.request_id, None)
            if req.error:
                return web.json_response({"error": req.error}, status=400)
            return web.json_response(
                {"error": "server overloaded"}, status=503)
        self._wake.set()

        if stream:
            return await self._stream_response(request, req, token_q,
                                               want_drafts)

        try:
            await self._await_request(req, event)
        except asyncio.TimeoutError:
            self._waiters.pop(req.request_id, None)
            with self._lock:
                self.engine.scheduler.cancel(req.request_id)
            return web.json_response({"error": "timeout"}, status=504)

        if req.state is RequestState.FAILED:
            return web.json_response({"error": req.error or "failed"},
                                     status=500)

        latency_ms = (req.finish_time - req.arrival_time) * 1000.0
        n_gen = len(req.generated_tokens)
        self._record_request_metrics(req)
        return web.json_response({
            "id": req.request_id,
            "object": "text_completion",
            "created": int(time.time()),
            "model": self.model_cfg.name,
            "choices": [{
                "index": 0,
                "text": self.tokenizer.decode(req.generated_tokens),
                "token_ids": req.generated_tokens,
                **({"unmask_steps": req.unmask_steps[:n_gen]}
                   if want_steps else {}),
                **(self._draft_fields(req) if want_drafts else {}),
                "finish_reason": req.finish_reason,
            }],
            "usage": {
                "prompt_tokens": req.num_prompt_tokens,
                "completion_tokens": n_gen,
                "total_tokens": req.num_prompt_tokens + n_gen,
            },
            "metrics": {"ttft_ms": req.ttft_ms, "latency_ms": latency_ms},
        })

    @staticmethod
    def _draft_fields(req: Request) -> dict:
        """``return_draft_tokens``: the draft verified at each generated
        token's position (-1: none) and whether it stood."""
        n_gen = len(req.generated_tokens)
        return {"draft_tokens": req.draft_tokens[:n_gen],
                "draft_stood": req.draft_stood[:n_gen]}

    async def _stream_response(self, http_req: web.Request, req: Request,
                               token_q: asyncio.Queue,
                               want_drafts: bool = False
                               ) -> web.StreamResponse:
        """Server-sent events (OpenAI `stream: true` wire format): one
        `data: {...}` chunk per decoded token batch, `data: [DONE]` at the
        end. Multi-step decode delivers tokens in bursts of up to K; a
        model that generates by diffusion over blocks, in bursts of the
        blocks a dispatch finished (whole blocks of ``block_length``
        tokens, but for a reply's first and last)."""
        # CORS headers must land BEFORE prepare() — the middleware's
        # post-handler pass is too late for a prepared stream (headers are
        # already on the wire)
        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            **self._cors_headers(http_req),
        })
        await resp.prepare(http_req)

        def chunk(text, finish_reason=None, token_ids=(), **more):
            # (``token_ids``: the batch's ids beside its text, which drops
            # what the tokenizer cannot render: a caller that sends the
            # reply back as part of its next turn needs the ids)
            return ("data: " + json.dumps({
                "id": req.request_id, "object": "text_completion",
                "model": self.model_cfg.name,
                "choices": [{"index": 0, "text": text,
                             "token_ids": [int(t) for t in token_ids],
                             "finish_reason": finish_reason, **more}],
            }) + "\n\n").encode()

        # incremental decode against the accumulated token list: batch-
        # independent decode renders merge-sensitive seams (split UTF-8
        # chars, BPE joins) differently than the final full decode
        from .tokenizer import IncrementalDecoder
        decoder = IncrementalDecoder(self.tokenizer)
        try:
            deadline = time.monotonic() + 600.0
            while True:
                try:
                    # short poll instead of one long wait: a client that
                    # disconnected between tokens used to leave this
                    # coroutine parked on the queue (and the _streams
                    # entry + the request's decode slot alive) until the
                    # request finished on its own — the disconnect only
                    # surfaced at the next write. Waking every 250 ms
                    # lets the transport check below catch it promptly.
                    batch = await asyncio.wait_for(token_q.get(),
                                                   timeout=0.25)
                except asyncio.TimeoutError:
                    if time.monotonic() > deadline:
                        # engine stalled: free the slot + KV pages like
                        # the non-streaming timeout path does
                        with self._lock:
                            self.engine.scheduler.cancel(req.request_id)
                        break
                    tr = http_req.transport
                    if tr is None or tr.is_closing():
                        # client is gone mid-stream: drop the stream
                        # entry NOW and (default on) abort the orphaned
                        # request so it stops burning a decode slot for
                        # nobody
                        self._streams.pop(req.request_id, None)
                        self._waiters.pop(req.request_id, None)
                        if self.serve_cfg.stream_abort_on_disconnect:
                            with self._lock:
                                self.engine.scheduler.cancel(
                                    req.request_id)
                        logger.info(
                            "stream %s: client disconnected; request "
                            "%s", req.request_id,
                            "aborted"
                            if self.serve_cfg.stream_abort_on_disconnect
                            else "left to finish unobserved")
                        return resp
                    continue
                if batch is None:               # request left its slot
                    break
                await resp.write(chunk(decoder.feed(batch), token_ids=batch))
            # (the whole reply's drafts ride the last chunk)
            final = chunk(decoder.finish(), req.finish_reason or "error",
                          **(self._draft_fields(req) if want_drafts else {}))
            await resp.write(final)
            await resp.write(b"data: [DONE]\n\n")
        except (ConnectionResetError, asyncio.CancelledError):
            # client went away: free the slot + pages
            with self._lock:
                self.engine.scheduler.cancel(req.request_id)
            raise
        finally:
            self._streams.pop(req.request_id, None)
            self._waiters.pop(req.request_id, None)
        self._record_request_metrics(req)
        await resp.write_eof()
        return resp

    def _record_request_metrics(self, req: Request) -> None:
        """Shared /health percentile + observer accounting for finished
        requests (streaming and blocking paths must not drift)."""
        if req.finish_time is None:
            return
        latency_ms = (req.finish_time - req.arrival_time) * 1000.0
        self._recent_latencies = (
            self._recent_latencies + [latency_ms])[-1000:]
        if req.ttft_ms is not None:
            self._recent_ttfts = (self._recent_ttfts + [req.ttft_ms])[-1000:]
        # engine.stats() is the one locked accessor for admission
        # telemetry — the engine thread mutates the waiting deque and
        # swapped_kv under this lock, so reading them lock-free here
        # would race (and private-field reads would drift from /health)
        with self._lock:
            st = self.engine.stats()
        self.observer("inference_request", {
            "latency_ms": latency_ms, "ttft_ms": req.ttft_ms,
            "prompt_tokens": req.num_prompt_tokens,
            "tokens": len(req.generated_tokens),
            "queue_depth": st["queue_depth"],
            # running totals, exported as they stand: the scheduler's
            # histogram and the engine's spans, not this request's own
            "queue_wait_ms": st["queue_wait_ms"],
            "phases": st["phases"],
            "starved_by_phase": st["starved_by_phase"],
            "slot_steps": st["slot_steps"],
            "startup_phases": st["startup"]["phases"],
            "moe_choices": st.get("moe", {}).get("choices", ()),
            "preemptions": st["preemptions"],
            "swap_ins": st["swap_ins"],
            "prefill_ride_tokens": st["prefill_ride_tokens"],
            "state_carry_tokens": st["state_carry_tokens"],
            **{k: st[k] for k in ("mtp_drafts", "mtp_accepted",
                                  "mtp_slot_steps", "mtp_tokens")},
            "swapped_host_bytes": st["swapped_host_bytes"],
        })

    async def handle_models(self, request: web.Request) -> web.Response:
        return web.json_response({
            "object": "list",
            "data": [{"id": self.model_cfg.name, "object": "model",
                      "owned_by": "llmctl",
                      "max_model_len": self.serve_cfg.max_seq_len}],
        })

    async def handle_health(self, request: web.Request) -> web.Response:
        with self._lock:
            stats = self.engine.stats()
        def pct(xs, q):
            if not xs:
                return None
            s = sorted(xs)
            return s[min(int(q * len(s)), len(s) - 1)]

        healthy = self._engine_error is None
        return web.json_response({
            "status": "healthy" if healthy else "degraded",
            "model": self.model_cfg.name,
            "engine": stats,
            "p50_latency_ms": pct(self._recent_latencies, 0.50),
            "ttft_ms": {"p50": pct(self._recent_ttfts, 0.50),
                        "p99": pct(self._recent_ttfts, 0.99)},
            "last_engine_error": self._engine_error,
            "engine_error_count": self._engine_error_count,
        }, status=200 if healthy else 503)

    async def handle_stats(self, request: web.Request) -> web.Response:
        with self._lock:
            return web.json_response(self.engine.stats())

    async def handle_metrics(self, request: web.Request) -> web.Response:
        try:
            from prometheus_client import generate_latest
            payload = generate_latest()
        except Exception:
            payload = b""
        return web.Response(body=payload, content_type="text/plain")

    def _cors_headers(self, request) -> dict:
        """CORS headers for this request, or {} when the origin is not
        allowed. Allow-Credentials is only asserted for an EXPLICIT origin
        list: reflecting any origin AND asserting credentials would be
        strictly more permissive than the reference's allow-all middleware
        (a literal '*' ACAO makes browsers refuse credentialed reads)."""
        origins = self.serve_cfg.cors_origins
        if not origins:
            return {}
        origin = request.headers.get("Origin", "")
        explicit = origins != "*"
        # responses differ by Origin (ACAO present/absent/reflected) and,
        # for preflights, by the reflected Allow-Headers — a shared cache
        # must key on both or it can serve one origin's CORS grant (or a
        # denied response's absence of one) to a different origin. The
        # Vary header therefore goes on EVERY response in explicit mode,
        # including denials and requests with no Origin at all.
        # ("*" mode still reflects Allow-Headers, so it varies too)
        vary = {"Vary": "Origin, Access-Control-Request-Headers"}
        if explicit and origin not in {
                o.strip() for o in origins.split(",") if o.strip()}:
            return vary
        headers = {
            "Access-Control-Allow-Origin":
                (origin if explicit else "*") or "*",
            "Access-Control-Allow-Methods": "GET, POST, OPTIONS",
            "Access-Control-Allow-Headers":
                request.headers.get(
                    "Access-Control-Request-Headers", "*") or "*",
            **vary,
        }
        if explicit:
            headers["Access-Control-Allow-Credentials"] = "true"
        return headers

    def _build_app(self) -> web.Application:
        # CORS parity with the reference's allow-all CORSMiddleware
        # (reference serve/server.py:276-282): browser clients can call the
        # API cross-origin. aiohttp has no built-in CORS, so a middleware
        # stamps the headers (SSE streams stamp theirs pre-prepare in
        # _stream_response). Configurable via ServeConfig.cors_origins
        # ("" disables; "*" = any origin, the reference's default).
        origins = self.serve_cfg.cors_origins

        @web.middleware
        async def cors_middleware(request, handler):
            if request.method == "OPTIONS":
                return web.Response(status=204,
                                    headers=self._cors_headers(request))
            resp = await handler(request)
            # prepared responses (SSE streams) stamped their own headers
            # in _stream_response — headers are already on the wire here
            if not resp.prepared:
                for k, v in self._cors_headers(request).items():
                    resp.headers.setdefault(k, v)
            return resp

        app = web.Application(middlewares=[cors_middleware] if origins
                              else [])
        app.router.add_post("/v1/completions", self.handle_completions)
        app.router.add_get("/v1/models", self.handle_models)
        app.router.add_get("/v1/stats", self.handle_stats)
        app.router.add_get("/health", self.handle_health)
        app.router.add_get("/metrics", self.handle_metrics)
        return app

    # -- lifecycle -----------------------------------------------------------

    async def start_async(self) -> web.AppRunner:
        self.start_engine()
        runner = web.AppRunner(self.app)
        await runner.setup()
        site = web.TCPSite(runner, self.serve_cfg.host, self.serve_cfg.port)
        await site.start()
        logger.info("serving %s on %s:%d", self.model_cfg.name,
                    self.serve_cfg.host, self.serve_cfg.port)
        # ready: a program that first runs from here on compiles under
        # traffic and says so (serve/engine.py _Program)
        STARTUP.ready()
        logger.info(STARTUP.summary())
        return runner

    def run_forever(self) -> None:
        async def _main():
            runner = await self.start_async()
            try:
                while True:
                    await asyncio.sleep(3600)
            finally:
                await runner.cleanup()
                self.stop_engine()
        asyncio.run(_main())


def create_inference_server(model_cfg: ModelConfig, serve_cfg: ServeConfig,
                            params=None, observer=None) -> InferenceServer:
    return InferenceServer(model_cfg, serve_cfg, params=params,
                           observer=observer)


def create_server(model_cfg: ModelConfig, serve_cfg: ServeConfig,
                  fleet_cfg=None, params=None, observer=None):
    """Single entry point for the serve CLI: one replica -> the classic
    InferenceServer; ``fleet_cfg.replicas > 1`` -> the fleet front
    (router + supervisor over N threaded engine replicas,
    serve/fleet/http.py). Both expose the same /v1 surface."""
    if fleet_cfg is not None and fleet_cfg.replicas > 1:
        from .fleet.http import FleetServer
        return FleetServer(model_cfg, serve_cfg, fleet_cfg, params=params,
                           observer=observer)
    return InferenceServer(model_cfg, serve_cfg, params=params,
                           observer=observer)


STARTUP.imported()      # an entry module: llmctl.startup.import ends here
