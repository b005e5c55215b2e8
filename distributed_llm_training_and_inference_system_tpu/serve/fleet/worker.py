"""`llmctl fleet worker`: one fleet replica as its own OS process.

The other half of serve/fleet/remote.py. A worker runs ONE engine
replica (any role) plus the host-local :class:`CourierReceiver`, behind
a small aiohttp front:

- ``POST /fleet/courier/chunk``  — inbound KV chunks (push-based
  courier; reassembled, CRC-verified, attached by ticket)
- ``POST /worker/submit``        — a serialized request; a courier
  ticket riding along is attached locally before admission (the remote
  restorer — no sender round-trip)
- ``GET  /worker/probe``         — health + load + counters
- ``POST /worker/outbox/take``   — drain finished results, crash/drain
  orphans, and completed migrations/handoffs back to the parent
  (payload-carrying entries reference a ticket parked in the local
  receiver, never bytes)
- ``POST /worker/ship``          — push a parked payload straight to
  another worker's courier endpoint (worker-to-worker movement; the
  control plane never relays KV bytes)
- ``POST /worker/drain|undrain|role|migrate|cancel`` — operator verbs

The worker supervises its own engine: a crashed engine thread is
rebuilt locally under doubling backoff while its orphans (and any
salvaged partial pre-copies, parked as tickets) flow to the outbox for
the parent to re-place. The parent only declares the worker dead when
the PROCESS stops answering — SIGKILL, black-holed endpoint — at which
point its in-flight work re-prefills on survivors.

A prefill-role worker hands freshly-prefilled sequences to the fleet by
parking the extracted KV under a ticket and publishing a ``handoff``
outbox entry; the parent routes it to a decode replica and issues the
worker-to-worker ship. Decode never waits on a supervisor poll longer
than the parent's outbox poll interval.
"""

from __future__ import annotations

import asyncio
import json
import logging
import threading
import time
import uuid
from collections import deque
from typing import Optional

from ...config.schema import FleetConfig, ModelConfig, ServeConfig
from ..scheduler import Request, RequestState, SamplingParams
from . import replica as replica_mod
from .faults import FaultInjector, FaultPlan
from .remote import request_from_wire, request_to_wire
from .replica import EngineReplica
from .transport import (KV_STORE_OWNER, CourierChunk, CourierReceiver,
                        HTTPCourierTransport, TransportError,
                        TransportStats)
from ...analysis.annotations import (aiohttp_handler, engine_thread_only, supervisor_thread)

logger = logging.getLogger("llmctl.serve.fleet.worker")


class FleetWorker:
    """One engine replica + courier receiver + outbox, ready to be
    fronted by :meth:`build_app` (aiohttp) or driven directly in tests."""

    def __init__(self, replica_id: int, model_cfg: ModelConfig,
                 serve_cfg: ServeConfig,
                 fleet_cfg: Optional[FleetConfig] = None,
                 role: str = replica_mod.ROLE_MIXED, params=None,
                 seed: int = 0, fault_plan: Optional[FaultPlan] = None,
                 warmup: bool = True):
        self.fleet_cfg = fleet_cfg or FleetConfig()
        self.injector = FaultInjector(fault_plan) if fault_plan else None
        self.receiver = CourierReceiver(
            ttl_ms=self.fleet_cfg.courier_ticket_ttl_ms)
        self.courier_stats = TransportStats()
        # before the replica: its warmup generate fires _on_finish
        self._outbox: deque = deque()
        self._lock = threading.Lock()
        self.replica = EngineReplica(
            replica_id, model_cfg, serve_cfg, params=params, seed=seed,
            injector=self.injector, on_finish=self._on_finish,
            fleet_cfg=self.fleet_cfg, role=role)
        self.params = self.replica.engine.params
        self.replica.courier_receiver = self.receiver
        # disaggregation: a prefill-role worker cannot see the fleet, so
        # the handoff destination is always "the parent decides" — the
        # extracted payload parks locally under a ticket and the parent
        # places + ships it
        self.replica.handoff_dest = lambda req, rid: -1
        self.replica.on_handoff = self._on_handoff
        # fleet-global prefix cache: this worker fetches missing prefix
        # pages itself (the owner hint + endpoint ride the submit wire);
        # set once run_forever knows the bound address — a worker driven
        # directly in tests can set it by hand
        self.self_endpoint: Optional[str] = None
        self.replica.prefix_fetcher = self._fetch_prefix
        # networked KV fabric (serve/fleet/store_service.py): with a
        # configured store endpoint this worker demotes its evicted /
        # drain-flushed prefix pages to the SHARED service and honors
        # KV_STORE_OWNER fetch hints against it — the same store every
        # front resolves, so a returning conversation landing here
        # restores pages another replica (or another worker) demoted.
        self.store_client = None
        store_eps = self.fleet_cfg.kv_store_endpoint_list() \
            if hasattr(self.fleet_cfg, "kv_store_endpoint_list") \
            else ([str(getattr(self.fleet_cfg, "kv_store_endpoint", "")
                       or "")] if getattr(self.fleet_cfg,
                                          "kv_store_endpoint", "")
                  else [])
        if store_eps:
            from .store_service import StoreClient
            self.store_client = StoreClient(self.fleet_cfg,
                                            injector=self.injector)
            self.replica.set_kv_store(self.store_client)
        # fleet SSE streaming: a streaming request's token batches ship
        # to the parent as cursor-tagged outbox entries (tokens are tiny
        # — no courier involved). The outbox deque preserves order, so a
        # request's stream entries always precede its own finished /
        # orphan / migrated entry.
        self.replica.on_token = self._on_token
        if warmup:
            # compile outside the serving path, then zero the prefill
            # counters the fleet's zero-re-prefill assertions read
            eng = self.replica.engine
            eng.generate([[1, 2, 3]], SamplingParams(
                temperature=0.0, max_tokens=4))
            eng.total_prefill_tokens = 0
            eng.total_prefill_padded_tokens = 0
            if hasattr(eng, "total_unexpected_prefills"):
                eng.total_unexpected_prefills = 0
        with self._lock:
            self._outbox.clear()    # drop warmup completions
        self._restarts = 0
        self._next_restart = 0.0
        self._backoff_s = self.fleet_cfg.restart_backoff_s
        self._stop = threading.Event()
        self._janitor: Optional[threading.Thread] = None

    # -- engine-side hooks ---------------------------------------------------

    @engine_thread_only
    def _on_finish(self, replica_id: int, req: Request) -> None:
        entry = {
            "kind": "finished",
            "request_id": req.request_id,
            "generated_tokens": [int(t) for t in req.generated_tokens],
            "finish_reason": req.finish_reason,
            "state": ("failed" if req.state is RequestState.FAILED
                      else "completed"),
            "error": req.error,
            "ttft_ms": req.ttft_ms,
        }
        with self._lock:
            self._outbox.append(entry)

    @engine_thread_only
    def _on_token(self, replica_id: int, req: Request,
                  tokens: list) -> None:
        """Engine-thread streaming hook: publish one token batch with its
        sequence cursor. ``start`` is derived from the request's own
        committed token count, so after any local engine rebuild +
        re-prefill the cursors stay aligned with the fleet-wide sequence
        numbering (seq = index into generated_tokens). ``seed`` rides
        along so the parent can fold streamed tokens into its copy and
        requeue a SIGKILL'd stream from the last delivered token."""
        entry = {"kind": "stream", "request_id": req.request_id,
                 "start": len(req.generated_tokens) - len(tokens),
                 "tokens": [int(t) for t in tokens],
                 "seed": req.assigned_seed}
        with self._lock:
            self._outbox.append(entry)

    @engine_thread_only
    def _on_handoff(self, replica_id: int, req: Request,
                    dest) -> None:
        """Prefill-complete extraction (engine thread): park the payload
        under a ticket and publish a handoff entry — fast, no sockets on
        the engine thread."""
        ticket = f"courier-{uuid.uuid4().hex[:16]}"
        payload, req.swapped_kv = req.swapped_kv, None
        self.receiver.put_payload(ticket, payload)
        with self._lock:
            self._outbox.append({"kind": "handoff", "ticket": ticket,
                                 "partial": False, "dest": None,
                                 "request": request_to_wire(req)})

    # -- local supervision ---------------------------------------------------

    @supervisor_thread
    def _flush_orphans(self) -> None:
        for req in self.replica.take_orphans():
            payload = req.swapped_kv
            ticket = None
            partial = False
            if isinstance(payload, dict) \
                    and "courier_ticket" not in payload:
                ticket = f"courier-{uuid.uuid4().hex[:16]}"
                partial = bool(payload.get("partial"))
                self.receiver.put_payload(ticket, payload)
                req.swapped_kv = None
            with self._lock:
                self._outbox.append({"kind": "orphan", "ticket": ticket,
                                     "partial": partial,
                                     "request": request_to_wire(req)})

    @supervisor_thread
    def _flush_migrated(self) -> None:
        for req, t in self.replica.take_migrated():
            payload, req.swapped_kv = req.swapped_kv, None
            ticket = None
            partial = False
            if isinstance(payload, dict):
                ticket = f"courier-{uuid.uuid4().hex[:16]}"
                partial = bool(payload.get("partial"))
                self.receiver.put_payload(ticket, payload)
            with self._lock:
                self._outbox.append({"kind": "migrated", "ticket": ticket,
                                     "partial": partial, "dest": t.dest,
                                     "reason": t.reason,
                                     "request": request_to_wire(req)})

    @supervisor_thread
    def supervise_once(self, now: Optional[float] = None) -> None:
        """One local-janitor pass: collect orphans/migrations into the
        outbox and rebuild a crashed engine under doubling backoff."""
        now = time.monotonic() if now is None else now
        r = self.replica
        self._flush_migrated()
        state = r.state
        if state in (replica_mod.CRASHED, replica_mod.STOPPED):
            self._flush_orphans()
            if self._next_restart == 0.0:
                self._next_restart = now + self._backoff_s
                self._backoff_s = min(
                    max(self._backoff_s, 1e-3) * 2,
                    self.fleet_cfg.restart_backoff_max_s)
            elif now >= self._next_restart:
                try:
                    r.stop()
                    r.restart(params=self.params)
                    self._restarts += 1
                    self._next_restart = 0.0
                    logger.info("worker replica %d engine rebuilt "
                                "(restart #%d)", r.replica_id,
                                self._restarts)
                except Exception:
                    logger.exception("worker engine rebuild failed")
                    self._next_restart = now + self._backoff_s
        else:
            self._flush_orphans()       # drain victims etc.

    @supervisor_thread
    def _janitor_loop(self) -> None:
        interval = min(self.fleet_cfg.probe_interval_s, 0.05)
        while not self._stop.wait(interval):
            try:
                self.supervise_once()
            except Exception:
                logger.exception("worker janitor pass failed")

    def start(self) -> None:
        self.replica.start()
        if self._janitor is None or not self._janitor.is_alive():
            self._stop.clear()
            self._janitor = threading.Thread(
                target=self._janitor_loop, daemon=True,
                name=f"llmctl-fleet-worker-{self.replica.replica_id}")
            self._janitor.start()

    def shutdown(self) -> None:
        self._stop.set()
        if self._janitor is not None:
            self._janitor.join(timeout=5.0)
            self._janitor = None
        self.replica.stop()
        try:
            self.replica.engine.release()
        except Exception:
            pass

    # -- RPC bodies (also driven directly by tests) --------------------------

    @aiohttp_handler
    def submit_wire(self, body: dict) -> dict:
        req = request_from_wire(body, receiver=self.receiver)
        ok = self.replica.submit(req)
        out = {"ok": bool(ok)}
        if not ok and req.error:
            out["reject_error"] = req.error
        return out

    @aiohttp_handler
    def probe_dict(self) -> dict:
        r = self.replica
        try:
            base = r.probe()
        except RuntimeError as e:
            # the ENGINE crashed; the process (us) is fine and the
            # janitor is rebuilding it. Report honestly — the parent
            # keeps routing elsewhere until we're back.
            base = {"replica": r.replica_id, "state": replica_mod.CRASHED,
                    "role": r.role, "queue_depth": 0, "active": 0,
                    "outstanding_tokens": 0, "error": str(e)}
        hits, queries, cached = r.prefix_cache_stats()
        eng = r.engine
        base.update({
            "resident_requests": r.resident_requests()
            if base["state"] == replica_mod.HEALTHY else [],
            # SLO preemption signal: worst queueing age of an
            # interactive request (ms) — the parent's autoscaler
            # compares it to interactive_ttft_target_ms
            "queued_interactive_wait_ms":
            r.queued_priority_wait_ms("interactive")
            if base["state"] == replica_mod.HEALTHY else 0.0,
            "migrations_in_flight": r.migrations_in_flight(),
            "migrations": r.migrations_out,
            "migrated_tokens": r.migrated_tokens,
            "reprefill_avoided_tokens": r.reprefill_avoided_tokens,
            "migrations_by_reason": dict(r.migrations_by_reason),
            "handoffs": r.handoffs_out,
            "handoff_tokens": r.handoff_tokens,
            "handoffs_local": r.handoffs_local,
            "prefix_hits": hits, "prefix_queries": queries,
            "requeue_cached_tokens": cached,
            # fleet-global prefix cache: the compact inventory (hex) the
            # parent's router turns into fetch hints, plus this
            # replica's fetch-side counters
            "prefix_pages": [h.hex() for h in r.prefix_inventory()],
            "prefix_fetch": r.prefix_fetch_stats(),
            # courier-aware speculation: per-replica acceptance counters
            # (running totals; the parent's supervisor snapshot and the
            # llmctl_fleet_spec_* Prometheus pump delta them)
            "spec": r.spec_stats(),
            "engine_restarts": self._restarts,
            "total_prefill_tokens": getattr(eng, "total_prefill_tokens",
                                            0),
            "total_unexpected_prefills": getattr(
                eng, "total_unexpected_prefills", 0),
            "outbox_depth": len(self._outbox),
        })
        return base

    @aiohttp_handler
    def take_outbox(self) -> dict:
        with self._lock:
            entries = list(self._outbox)
            self._outbox.clear()
        return {"entries": entries, "probe": self.probe_dict()}

    @aiohttp_handler
    def ship(self, body: dict) -> dict:
        """Push a parked payload to another worker's courier endpoint.
        Pops the ticket — an aborted push means the payload is gone and
        the parent falls back to re-prefill (the courier contract)."""
        ticket = str(body.get("ticket", ""))
        dest_endpoint = str(body.get("dest_endpoint", "")).rstrip("/")
        if not ticket or not dest_endpoint:
            return {"ok": False,
                    "error": "body must be {ticket, dest_endpoint}"}
        payload = self.receiver.take_payload(ticket)
        if payload is None:
            return {"ok": False,
                    "error": f"unknown or expired ticket {ticket!r}"}
        transport = HTTPCourierTransport(
            self.fleet_cfg, injector=self.injector,
            stats=self.courier_stats, endpoint=dest_endpoint)
        try:
            transport.transfer(payload,
                               src=self.replica.replica_id,
                               dest=body.get("dest"), ticket=ticket)
        except TransportError as e:
            return {"ok": False, "error": str(e)}
        return {"ok": True, "ticket": ticket}

    @aiohttp_handler
    def status_dict(self) -> dict:
        out = self.probe_dict()
        out["courier"] = {**self.courier_stats.snapshot(),
                          **self.receiver.stats()}
        sc = self.store_client
        if sc is not None:
            # local counters only — status must stay responsive while
            # the store service is down (no remote round-trip here)
            out["kv_store"] = {"endpoint": sc.endpoint,
                               "endpoints": sc.endpoints,
                               "remote_hits": sc.total_remote_hits,
                               "remote_misses": sc.total_remote_misses,
                               "retries": sc.total_retries,
                               "failovers": sc.total_failovers,
                               "hedges": sc.total_hedges}
        return out

    # -- fleet-global prefix cache -------------------------------------------

    @engine_thread_only
    def _fetch_prefix(self, fetcher_id: int, owner,
                      owner_endpoint: Optional[str],
                      hashes: list) -> Optional[dict]:
        """Fetch half, worker flavor: command the OWNER's front (worker
        or parent fleet server — both serve /fleet/courier/fetch) to
        extract + push the pages to this worker's own courier endpoint,
        then claim them locally by ticket. None = miss; raises
        TransportError-shaped failures as plain exceptions the replica
        counts as aborts."""
        ep = (owner_endpoint or "").rstrip("/")
        if owner == KV_STORE_OWNER:
            # the networked store service: pull-mode — the response
            # carries the held frames and THIS worker replays them
            # through its own receiver (full CRC/verify path)
            client = self.store_client
            if client is None or (ep and ep not in client.endpoints):
                if not ep:
                    return None
                from .store_service import StoreClient
                client = StoreClient(self.fleet_cfg, endpoint=ep,
                                     injector=self.injector)
                if self.store_client is None:
                    self.store_client = client
            return client.fetch(hashes, self.receiver)
        me = self.self_endpoint
        if not ep or not me:
            return None
        ticket = f"courier-{uuid.uuid4().hex[:16]}"
        body = {"replica": owner,
                "hashes": [h.hex() if isinstance(h, bytes) else str(h)
                           for h in hashes],
                "ticket": ticket, "dest": self.replica.replica_id,
                "dest_endpoint": me}
        import urllib.request
        wire = urllib.request.Request(
            f"{ep}/fleet/courier/fetch",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(
                wire,
                timeout=self.fleet_cfg.prefix_fetch_timeout_s) as resp:
            out = json.loads(resp.read().decode())
        if not out.get("ok"):
            return None
        return self.receiver.take_payload(ticket)

    @aiohttp_handler
    def prefix_fetch(self, body: dict) -> dict:
        """Owner side of ``POST /fleet/courier/fetch`` (alias
        ``/worker/prefix``): extract the requested prefix pages on the
        engine thread and push them, chunked, to the fetcher's courier
        endpoint. A miss (nothing cached, evicted since advertised) is
        an ok=False answer, not an error — the fetcher re-prefills."""
        try:
            hashes = [bytes.fromhex(h) for h in body.get("hashes", [])]
        except (TypeError, ValueError):
            return {"ok": False, "error": "malformed hashes"}
        ticket = str(body.get("ticket") or "")
        dest_ep = str(body.get("dest_endpoint") or "").rstrip("/")
        if not hashes or not ticket or not dest_ep:
            return {"ok": False, "error":
                    "body must be {hashes, ticket, dest_endpoint}"}
        payload = self.replica.request_prefix_extract(
            hashes, timeout_s=self.fleet_cfg.prefix_fetch_timeout_s)
        if not payload:
            return {"ok": False, "error": "prefix pages not cached"}
        transport = HTTPCourierTransport(
            self.fleet_cfg, injector=self.injector,
            stats=self.courier_stats, endpoint=dest_ep)
        try:
            transport.transfer(payload,
                               src=self.replica.replica_id,
                               dest=body.get("dest"), ticket=ticket)
        except TransportError as e:
            return {"ok": False, "error": str(e)}
        return {"ok": True, "ticket": ticket,
                "covered": int(payload["pages"]["num_pages"])}

    # -- aiohttp front -------------------------------------------------------

    def build_app(self):
        from aiohttp import web

        worker = self

        def json_body(handler):
            async def wrapped(request):
                try:
                    body = await request.json()
                except json.JSONDecodeError:
                    return web.json_response({"error": "invalid JSON"},
                                             status=400)
                return await handler(request, body)
            return wrapped

        async def courier_chunk(request, body):
            try:
                chunk = CourierChunk.from_wire(body)
            except Exception:
                return web.json_response(
                    {"error": "body must be a courier chunk frame "
                              "{ticket, seq, total, crc32, data(b64)}"},
                    status=400)
            return web.json_response(worker.receiver.add_chunk(chunk))

        async def submit(request, body):
            try:
                return web.json_response(worker.submit_wire(body))
            except (KeyError, TypeError, ValueError) as e:
                return web.json_response(
                    {"ok": False, "error": f"malformed request: {e}"},
                    status=400)

        async def probe(request):
            return web.json_response(worker.probe_dict())

        async def outbox_take(request, body):
            return web.json_response(worker.take_outbox())

        async def ship(request, body):
            # the chunked push blocks (retries, backoff): keep it off
            # the event loop so probes stay responsive mid-transfer
            loop = asyncio.get_running_loop()
            out = await loop.run_in_executor(None, worker.ship, body)
            return web.json_response(out)

        async def prefix(request, body):
            # extract waits on the engine thread and the push retries:
            # both belong off the event loop (inbound chunks from OTHER
            # transfers must keep landing mid-fetch)
            loop = asyncio.get_running_loop()
            out = await loop.run_in_executor(None, worker.prefix_fetch,
                                             body)
            return web.json_response(out)

        async def drain(request, body):
            worker.replica.request_drain()
            return web.json_response({"ok": True})

        async def undrain(request, body):
            worker.replica.undrain()
            return web.json_response({"ok": True})

        async def role(request, body):
            role = str(body.get("role", "")).lower()
            if role not in (replica_mod.ROLE_PREFILL,
                            replica_mod.ROLE_DECODE,
                            replica_mod.ROLE_MIXED):
                return web.json_response(
                    {"ok": False, "error": f"unknown role {role!r}"},
                    status=400)
            worker.replica.set_role(role)
            return web.json_response({"ok": True, "role": role})

        async def migrate(request, body):
            ok = worker.replica.request_migrate(
                str(body.get("request_id", "")), dest=body.get("dest"),
                reason=str(body.get("reason", "operator")))
            return web.json_response({"ok": bool(ok)})

        async def cancel(request, body):
            ok = worker.replica.cancel(str(body.get("request_id", "")))
            return web.json_response({"ok": bool(ok)})

        async def status(request):
            return web.json_response(worker.status_dict())

        async def health(request):
            state = worker.replica.state
            return web.json_response(
                {"status": "healthy"
                 if state == replica_mod.HEALTHY else state},
                status=200 if state == replica_mod.HEALTHY else 503)

        app = web.Application()
        app.router.add_post("/fleet/courier/chunk",
                            json_body(courier_chunk))
        app.router.add_post("/worker/submit", json_body(submit))
        app.router.add_get("/worker/probe", probe)
        app.router.add_post("/worker/outbox/take", json_body(outbox_take))
        app.router.add_post("/worker/ship", json_body(ship))
        # fleet-global prefix fetch, owner side: /worker/prefix is the
        # worker-flavored name, /fleet/courier/fetch the uniform one the
        # fetchers actually POST (the parent fleet front serves the same
        # path for its in-proc replicas)
        app.router.add_post("/worker/prefix", json_body(prefix))
        app.router.add_post("/fleet/courier/fetch", json_body(prefix))
        app.router.add_post("/worker/drain", json_body(drain))
        app.router.add_post("/worker/undrain", json_body(undrain))
        app.router.add_post("/worker/role", json_body(role))
        app.router.add_post("/worker/migrate", json_body(migrate))
        app.router.add_post("/worker/cancel", json_body(cancel))
        app.router.add_get("/worker/status", status)
        app.router.add_get("/health", health)
        return app

    def run_forever(self, host: str = "127.0.0.1", port: int = 0) -> None:
        """Serve until killed. Prints exactly one machine-readable ready
        line to stdout (``LLMCTL_WORKER_READY port=N``) so a spawning
        parent can discover an ephemeral port; everything else logs to
        stderr."""
        from aiohttp import web

        async def _main():
            runner = web.AppRunner(self.build_app(), access_log=None)
            await runner.setup()
            site = web.TCPSite(runner, host, port)
            await site.start()
            bound = runner.addresses[0][1]
            # our own courier endpoint: prefix fetches ask owners to
            # push here
            self.self_endpoint = f"http://{host}:{bound}"
            self.start()
            print(f"LLMCTL_WORKER_READY port={bound}", flush=True)
            logger.info("fleet worker replica %d (%s) serving on %s:%d",
                        self.replica.replica_id, self.replica.role,
                        host, bound)
            try:
                while True:
                    await asyncio.sleep(3600)
            finally:
                await runner.cleanup()
                self.shutdown()

        try:
            asyncio.run(_main())
        except KeyboardInterrupt:
            pass
