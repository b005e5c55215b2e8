"""Serve fleet control plane: N engine replicas behind a router.

Everything below one replica — iteration-level continuous batching, paged
KV, chunked prefill, speculation — is `serve/engine.py`, untouched (the
Orca split, PAPERS.md). This package adds the first layer where a request
can outlive a single engine process:

- :class:`~.router.FleetRouter` — prefix-affinity consistent hashing +
  least-outstanding-tokens placement, fleet admission (429 + Retry-After)
- :class:`~.replica.EngineReplica` — a threaded engine whose crash and
  drain paths extract in-flight requests instead of failing them
- :class:`~.supervisor.ReplicaSupervisor` — health probes, requeue,
  restart with exponential backoff
- :class:`~.faults.FaultInjector` — deterministic crash / probe-timeout /
  straggler injection so every path above is testable on CPU
- :class:`ServeFleet` — the facade wiring them together

Replicas are threads over engines on the local (possibly virtual) mesh
— the same in-process simulation strategy the repo uses for multi-chip
training (tests/conftest.py) — OR separate OS processes / hosts running
`llmctl fleet worker`, fronted by :class:`~.remote.RemoteReplica`
(``FleetConfig.remote_replicas`` + the per-replica ``fleet_endpoints``
courier map). The control plane is transport-agnostic by construction
(it only ever calls ``submit``/``probe``/``take_orphans``), and KV
payloads move over the push-based, destination-terminated courier
(serve/fleet/transport.py) either way.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Optional, Sequence

from ...config.schema import FleetConfig, ModelConfig, ServeConfig
from ..engine import InferenceEngine
from ..kv_cache import resolve_page_size
from ..scheduler import Request, SamplingParams
from .faults import (DestUnreachable, FaultInjector, FaultPlan,
                     InjectedCrash, ProbeTimeout, RpcBlackhole)
from .migration import MigrationTicket
from .pipeline import PipelineCoordinator, plan_stages
from .remote import RemoteReplica, RemoteUnavailable
from .replica import (ROLE_DECODE, ROLE_MIXED, ROLE_PREFILL, EngineReplica,
                      reset_for_requeue)
from .front import FleetFrontTier
from .kv_store import KV_STORE_OWNER, FleetKVStore
from .store_service import StoreClient, StoreService
from .weights import WeightCourier, WeightShipError
from .autoscaler import (FleetAutoscaler, ProcessWorkerSpawner,
                         synthesize_worker_argv)
from .router import (FleetRouter, FleetSaturated, normalize_priority,
                     prefix_digest)
from .state import (FleetStateStore, InMemoryStateStore,
                    SharedFileStateStore, StoreFenced, build_state_store)
from .streams import FleetStreamHub
from .supervisor import ReplicaSupervisor
from .transport import (CourierReceiver, HTTPCourierTransport,
                        InProcTransport, KVCourier, TransferAborted,
                        TransportError, build_transport, is_ticket_stub,
                        ticket_stub)

__all__ = [
    "CourierReceiver",
    "DestUnreachable",
    "EngineReplica",
    "FaultInjector",
    "FaultPlan",
    "FleetAutoscaler",
    "FleetFrontTier",
    "FleetKVStore",
    "FleetRouter",
    "FleetSaturated",
    "FleetStateStore",
    "KV_STORE_OWNER",
    "FleetStreamHub",
    "HTTPCourierTransport",
    "InMemoryStateStore",
    "InProcTransport",
    "InjectedCrash",
    "KVCourier",
    "MigrationTicket",
    "PipelineCoordinator",
    "ProbeTimeout",
    "ProcessWorkerSpawner",
    "RemoteReplica",
    "RemoteUnavailable",
    "RpcBlackhole",
    "ROLE_DECODE",
    "ROLE_MIXED",
    "ROLE_PREFILL",
    "ReplicaSupervisor",
    "ServeFleet",
    "SharedFileStateStore",
    "StoreClient",
    "StoreFenced",
    "StoreService",
    "TransferAborted",
    "TransportError",
    "WeightCourier",
    "WeightShipError",
    "build_state_store",
    "build_transport",
    "is_ticket_stub",
    "normalize_priority",
    "plan_stages",
    "prefix_digest",
    "reset_for_requeue",
    "synthesize_worker_argv",
    "ticket_stub",
]

logger = logging.getLogger("llmctl.serve.fleet")


class ServeFleet:
    """N replicas + router + supervisor, ready to serve.

    Weights are loaded/initialised ONCE (by replica 0) and shared read-only
    across replicas — on the test CPU that is N KV pools over one param
    tree, and on real hardware it mirrors replicas serving one artifact.

    ``supervise=True`` runs the supervisor on its own thread (production);
    ``supervise=False`` leaves probing/requeue/restart to explicit
    ``supervisor.poll_once()`` calls (deterministic tests, dryrun)."""

    def __init__(self, model_cfg: ModelConfig, serve_cfg: ServeConfig,
                 fleet_cfg: Optional[FleetConfig] = None, params=None,
                 fault_plan: Optional[FaultPlan] = None,
                 observer: Optional[Callable[[str, dict], None]] = None,
                 eos_token_id: Optional[int] = None, seed: int = 0,
                 supervise: bool = True,
                 front_id: Optional[str] = None):
        self.fleet_cfg = fleet_cfg or FleetConfig()
        self.fleet_cfg.validate()    # incl. endpoint-map/remote mismatch
        self.serve_cfg = serve_cfg
        self.injector = FaultInjector(fault_plan) if fault_plan else None
        roles = self.fleet_cfg.role_list()
        remote_ids = self.fleet_cfg.remote_replica_ids()
        endpoints = self.fleet_cfg.endpoint_map()
        # KV courier: every migration / handoff / salvaged-partial
        # payload crosses this chunked, checksummed, retrying transport
        # (serve/fleet/transport.py), push-based and destination-
        # terminated: the completed payload attaches BY TICKET in the
        # destination host's receiver and restores locally. In-proc
        # destinations use the local receiver; remote destinations are
        # pushed over HTTP per the fleet_endpoints map.
        self.courier = KVCourier(self.fleet_cfg, injector=self.injector)
        # tiered fleet KV store (serve/fleet/kv_store.py): a host-tier
        # DRAM ring (+ optional disk spill) holding demoted prefix pages
        # in compressed courier-frame form. Replicas demote evicted and
        # drain-flushed pages here; the router's hint path falls back to
        # it when no live replica covers a prompt; fetches replay the
        # frames through the courier receiver. None = no store tier.
        # None = no store tier; with `kv_store_endpoint` (or the
        # replicated `kv_store_endpoints` list) set the SAME logical
        # store lives in separate `llmctl fleet store` process(es) and
        # a duck-compatible StoreClient (demote_async / holds /
        # inventory / fetch / snapshot) stands in for it — the
        # networked KV fabric: every front and every remote worker
        # resolve ONE store, so pages survive any single serving
        # process — and with N members behind the one KV_STORE_OWNER,
        # any single STORE process too (failover + write fan-out live
        # in the client; the injector seeds store kill/partition
        # chaos).
        store_eps = self.fleet_cfg.kv_store_endpoint_list()
        if store_eps:
            self.kv_store = StoreClient(self.fleet_cfg,
                                        injector=self.injector)
        elif self.fleet_cfg.kv_store:
            self.kv_store = FleetKVStore(self.fleet_cfg)
        else:
            self.kv_store = None
        self.courier.kv_store = self.kv_store
        # weight courier (serve/fleet/weights.py): checkpoints ride the
        # same store fabric as KV pages — `ship_weights()` registers
        # the loaded params so bare `--weights-from-store` workers can
        # bootstrap over the wire.
        self.weight_courier = (
            WeightCourier(self.fleet_cfg, injector=self.injector)
            if store_eps else None)
        # replicable front state (serve/fleet/state.py): the stream logs
        # and router ledger live behind this store. The default
        # in-memory store keeps today's single-front behavior
        # byte-for-byte; `state_store = "file"` externalizes both so N
        # stateless fronts (each its own ServeFleet over the SAME remote
        # workers and store directory) serve one fleet — the HA front
        # tier.
        self.store = build_state_store(self.fleet_cfg,
                                       front_id=front_id)
        self.front_id = self.store.front_id
        # fleet SSE streaming: the per-request token log + stream hub
        # (serve/fleet/streams.py). Every replica a streaming request
        # crosses publishes its token batches here with monotonic
        # sequence numbers; the hub dedupes by seq, so crash requeue,
        # drain migration, disagg handoff, and SIGKILL'd workers are
        # invisible to SSE clients — delivery just resumes from the last
        # acked token on the new producer.
        self.streams = FleetStreamHub(
            ttl_ms=self.fleet_cfg.stream_log_ttl_ms,
            max_buffered_batches=self.fleet_cfg
            .stream_max_buffered_batches,
            store=self.store)
        # inbound chunk reassembly for the HTTP front
        # (/fleet/courier/chunk) shares the courier's receiver, so
        # socket-delivered and in-proc transfers attach in one place
        self.courier_receiver = self.courier.receiver
        self.replicas: list = []
        for i in range(self.fleet_cfg.replicas):
            if i in remote_ids:
                r = RemoteReplica(
                    i, endpoints[i], fleet_cfg=self.fleet_cfg,
                    injector=self.injector,
                    on_finish=self._on_request_exit, role=roles[i])
            else:
                r = EngineReplica(
                    i, model_cfg, serve_cfg, params=params,
                    # distinct base seeds so unseeded sampled requests
                    # don't mirror each other across replicas (greedy /
                    # explicit seeds are unaffected)
                    seed=seed + 1000 * i, injector=self.injector,
                    on_finish=self._on_request_exit,
                    eos_token_id=eos_token_id,
                    fleet_cfg=self.fleet_cfg, role=roles[i])
                r.courier_receiver = self.courier_receiver
                if params is None:      # replica 0 owns the load; share
                    params = r.engine.params
                    model_cfg = r.model_cfg
            self.replicas.append(r)
        self.model_cfg = model_cfg
        self._params = params
        # elastic scaling needs to build replicas AFTER construction:
        # keep the remaining EngineReplica constructor inputs around
        self._seed = seed
        self._eos_token_id = eos_token_id
        # fleet-global prefix cache: hints need the page size the
        # engines actually hash with; 0 disables the whole plane. (A local
        # replica's engine has resolved an unstated size by now; over
        # remote replicas alone the fleet resolves it as they do, from the
        # model's rows at THIS configuration's dtype)
        resolve_page_size(model_cfg, serve_cfg,
                          most=InferenceEngine.RIDE_ROWS)
        page_size = (serve_cfg.kv_block_size
                     if (serve_cfg.prefix_caching
                         and self.fleet_cfg.prefix_fetch) else 0)
        self.router = FleetRouter(self.replicas, self.fleet_cfg,
                                  observer=observer, courier=self.courier,
                                  page_size=page_size, store=self.store,
                                  kv_store=self.kv_store)
        # HA front tier: a terminal record folded from a sibling front
        # completes the local Request object (waiters, SSE finish)
        self.router.on_store_pop = self._complete_from_store
        # pipelined multi-replica prefill: the coordinator exists even
        # when gated off (min_tokens=0) so the snapshot/metrics surface
        # is stable; the router delegates qualifying long prompts to it
        self.pipeline = PipelineCoordinator(self.fleet_cfg, page_size)
        self.pipeline.bind(self.router, self.replicas, self.courier)
        self.router.pipeline = self.pipeline
        for r in self.replicas:
            self._wire_replica(r)
        # elastic autoscaler (serve/fleet/autoscaler.py): scale up/down
        # from queue pressure (+ KV-pool pressure) + TTFT-guard
        # preemption, driven from the supervisor poll. None = fixed
        # fleet (today's default). `autoscale_spawn = "worker"` scales
        # up with fresh `llmctl fleet worker` OS processes whose argv
        # is synthesized from THIS process's config — no operator
        # command line needed.
        spawner = None
        if self.fleet_cfg.autoscale and \
                getattr(self.fleet_cfg, "autoscale_spawn",
                        "") == "worker":
            spawner = ProcessWorkerSpawner(
                synthesize_worker_argv(
                    self.model_cfg, self.serve_cfg, self.fleet_cfg,
                    weights_name=self.serve_cfg.model),
                spawn_timeout_s=self.fleet_cfg
                .autoscale_spawn_timeout_s,
                store_endpoints=store_eps)
        self.autoscaler = (FleetAutoscaler(self, self.fleet_cfg,
                                           spawner=spawner)
                           if self.fleet_cfg.autoscale else None)
        self.supervisor = ReplicaSupervisor(
            self.replicas, self.router, self.fleet_cfg,
            injector=self.injector, params=params, observer=observer,
            streams=self.streams, store=self.store,
            kv_store=self.kv_store, pipeline=self.pipeline,
            autoscaler=self.autoscaler, weights=self.weight_courier)
        self._supervise = supervise
        # warm-spare pool: in-proc provisioning time IS XLA compile
        # time, and paying it on the supervisor thread mid-burst would
        # land the new replica after the crowd has passed. A background
        # warmer pre-builds + pre-compiles up to two standby engines
        # (ids just above the provisioned range); `_scale_up` adopts
        # one instantly and falls back to a cold build once the pool
        # is spent.
        self._spares: list = []
        self._spares_pending: set = set()
        self._spares_cv = threading.Condition()
        self._spares_closed = False
        if self.autoscaler is not None \
                and self.autoscaler.spawner is None:
            n = len(self.replicas)
            spare_ids = [n + k for k in
                         range(max(min(self.autoscaler.ceiling(),
                                       n + 2) - n, 0))]
            if spare_ids:
                self._spares_pending.update(spare_ids)
                threading.Thread(
                    target=self._warm_spares, args=(spare_ids,),
                    daemon=True, name="fleet-spare-warmer").start()

    def _wire_replica(self, r) -> None:
        """Attach one replica's fleet-facing callbacks — factored out of
        ``__init__`` so elastically-added replicas join with the exact
        wiring provisioned ones get."""
        if getattr(r, "remote", False):
            # multi-front: finished entries for requests ANOTHER
            # front submitted still close the shared log + ledger
            r.on_foreign = self._on_foreign_finished
            # a remote prefill worker parks its handoffs under a
            # ticket and publishes them through its outbox; the
            # supervisor's migrated-collection places them — and it
            # runs its own prefix fetches (the hint travels on the
            # submit wire). Its token batches arrive cursor-tagged
            # through the same outbox poll.
            r.on_tokens = self._on_remote_stream_tokens
            return
        r.courier_receiver = self.courier_receiver
        # in-proc streaming: the engine's on_token feeds the hub
        # directly, with the request object as the gap authority
        r.on_token = self._on_stream_tokens
        # disaggregation wiring: a prefill-role replica asks the
        # router for a decode destination BEFORE extracting (local-
        # decode fallback when no pool has room), then places the
        # handed-off sequence synchronously from its engine thread
        r.handoff_dest = self.router.handoff_dest
        r.on_handoff = self._place_handoff
        # prefix-fetch wiring: this replica both serves its cached
        # pages to the fleet (provider) and fetches missing ones
        # through the courier's fetch verb
        self.courier.prefix_providers[r.replica_id] = \
            r.request_prefix_extract
        r.prefix_fetcher = self.courier.fetch_prefix
        # pipelined prefill: stage chunk progress feeds the
        # coordinator's event pump (enqueue-only on its side)
        r.on_pipeline_chunk = self.pipeline.on_stage_chunk
        # tiered KV store: evicted/retired prefix pages demote down
        # a tier instead of being destroyed
        if self.kv_store is not None:
            r.set_kv_store(self.kv_store)

    # -- elastic membership (autoscaler mechanics) ---------------------------

    def _build_engine_replica(self, replica_id: int) -> EngineReplica:
        """Construct + warm-compile one in-proc replica sharing the
        already-loaded weights. Jitted closures are per-engine, so a
        cold engine would bill its XLA compiles to the first unlucky
        requests' TTFT — exactly the class a scale-up exists to
        protect. Pow-2 prompt lengths cover the prefill buckets; decode
        compiles once; the counter ledger is left clean."""
        r = EngineReplica(
            replica_id, self.model_cfg, self.serve_cfg,
            params=self._params, seed=self._seed + 1000 * replica_id,
            injector=self.injector, on_finish=self._on_request_exit,
            eos_token_id=self._eos_token_id,
            fleet_cfg=self.fleet_cfg, role=ROLE_MIXED)
        n = 8
        while n <= min(256, self.serve_cfg.max_seq_len - 4):
            r.engine.generate([list(range(1, n + 1))],
                              SamplingParams(temperature=0.0,
                                             max_tokens=2))
            n <<= 1
        r.engine.reset_counters()
        return r

    def _warm_spares(self, ids: list) -> None:
        """Background warmer: stock the standby pool while the
        provisioned fleet serves. Runs once, at construction."""
        for rid in ids:
            if self._spares_closed:
                return
            try:
                r = self._build_engine_replica(rid)
            except Exception:
                logger.exception("spare replica %d warm-up failed", rid)
                with self._spares_cv:
                    self._spares_pending.discard(rid)
                    self._spares_cv.notify_all()
                continue
            with self._spares_cv:
                self._spares_pending.discard(rid)
                if self._spares_closed:
                    released = r
                else:
                    self._spares.append(r)
                    released = None
                self._spares_cv.notify_all()
            if released is not None:
                try:
                    released.engine.release()
                except Exception:
                    pass

    def wait_warm_spares(self, timeout_s: float = 300.0) -> bool:
        """Block until the standby pool finishes warming (or the
        timeout passes). Latency-sensitive callers — benchmarks, SLO
        measurement windows — use this so spare XLA compiles don't
        contend with serving inside the window they care about; a
        scale-up after this returns adopts a spare instantly. True
        when no spare warm-ups remain in flight."""
        deadline = time.monotonic() + timeout_s
        with self._spares_cv:
            while self._spares_pending \
                    and time.monotonic() < deadline:
                self._spares_cv.wait(timeout=1.0)
            return not self._spares_pending

    def spawn_engine_replica(self, replica_id: int) -> EngineReplica:
        """One in-proc replica for the autoscaler's default scale-up —
        from the warm-spare pool when stocked (instant), else a cold
        build (construct + compile, seconds). If the warmer is compiling
        exactly this id, wait for the warm engine rather than start a
        duplicate cold build."""
        deadline = time.monotonic() + 300.0
        with self._spares_cv:
            while replica_id in self._spares_pending \
                    and time.monotonic() < deadline:
                self._spares_cv.wait(timeout=1.0)
            for s in self._spares:
                if s.replica_id == replica_id:
                    self._spares.remove(s)
                    return s
        return self._build_engine_replica(replica_id)

    def spawn_remote_replica(self, replica_id: int,
                             endpoint: str) -> RemoteReplica:
        """Build (don't start/wire) a front for a freshly-spawned
        ``llmctl fleet worker`` at its discovered endpoint."""
        return RemoteReplica(
            replica_id, endpoint, fleet_cfg=self.fleet_cfg,
            injector=self.injector, on_finish=self._on_request_exit,
            role=ROLE_MIXED)

    def adopt_replica(self, replica, endpoint: Optional[str] = None
                      ) -> None:
        """Join a started replica to the live fleet: wiring, router ring
        + endpoint map, pipeline candidate set. ``self.replicas`` is the
        SAME list object the supervisor iterates, so it sees the new
        member on its next poll step; membership only ever changes on
        the supervisor thread (autoscaler), so no iterator races."""
        self._wire_replica(replica)
        self.replicas.append(replica)
        if endpoint is not None:
            # live endpoint-map update: status, courier pushes, and
            # sibling workers all resolve the newcomer from here
            self.fleet_cfg.fleet_endpoints[replica.replica_id] = endpoint
        self.router.add_replica(replica, endpoint=endpoint)
        self.pipeline.bind(self.router, self.replicas, self.courier)

    def release_replica(self, replica_id: int) -> None:
        """Remove a DRAINED replica from the live fleet and free its
        engine. The drain already migrated residents and flushed the
        prefix inventory to the KV store — this is pure teardown."""
        r = next((x for x in self.replicas
                  if x.replica_id == replica_id), None)
        if r is None:
            return
        self.replicas.remove(r)
        self.router.remove_replica(replica_id)
        self.pipeline.bind(self.router, self.replicas, self.courier)
        self.courier.prefix_providers.pop(replica_id, None)
        self.fleet_cfg.fleet_endpoints.pop(replica_id, None)
        self.supervisor.forget(replica_id)
        try:
            r.stop()
        except Exception:
            pass
        engine = getattr(r, "engine", None)
        if engine is not None:
            try:
                engine.release()
            except Exception:
                pass

    def _on_request_exit(self, replica_id: int, req: Request) -> None:
        self.router.on_request_exit(replica_id, req)

    def ship_weights(self, name: str = "") -> dict:
        """Register this fleet's loaded checkpoint in the store service
        (default name: the model name) so bare hosts — `llmctl fleet
        worker --weights-from-store`, including autoscaler-spawned ones
        — bootstrap over the wire instead of a shared artifact path.
        Idempotent and upload-resumable; raises
        :class:`~.weights.WeightShipError` naming the endpoint when the
        service is unreachable."""
        if self.weight_courier is None:
            raise RuntimeError(
                "ship_weights needs kv_store_endpoint — no store "
                "service is configured for this fleet")
        if self._params is None:
            raise RuntimeError(
                "ship_weights: this front holds no loaded params "
                "(all replicas remote) — ship from the process that "
                "loaded the checkpoint, or `llmctl fleet ship-weights`")
        return self.weight_courier.ship(name or self.serve_cfg.model,
                                        self._params)

    # -- HA front tier seams -------------------------------------------------

    def _on_foreign_finished(self, replica_id: int, entry: dict) -> None:
        """A worker's finished entry for a request some OTHER front
        submitted (the multi-front outbox split): final-sync + finish
        the shared stream log, then close the shared ledger. The
        journaled pop record carries the terminal tokens, so the owning
        front folds it and completes its local waiter."""
        rid = str(entry.get("request_id", ""))
        if not rid:
            return
        tokens = [int(t) for t in entry.get("generated_tokens", [])]
        if self.streams.has(rid):
            self.streams.sync(rid, tokens, replica=replica_id)
            self.streams.finish(rid, entry.get("finish_reason"),
                                entry.get("error"))
        self.router.foreign_exit(rid, entry, replica_id)

    def _complete_from_store(self, rid: str, rec: dict) -> None:
        """Folded terminal ledger record: if this front still holds the
        Request object (it submitted it; the finish drained elsewhere),
        complete it so HTTP waiters and SSE finish frames resolve."""
        for r in self.replicas:
            fn = getattr(r, "complete_foreign", None)
            if fn is not None and fn(rid, rec):
                return

    def _on_stream_tokens(self, replica_id: int, req: Request,
                          tokens: list) -> None:
        self.streams.publish_from_request(req, tokens, replica=replica_id)

    def _on_remote_stream_tokens(self, replica_id: int, request_id: str,
                                 start: int, tokens: list) -> None:
        self.streams.publish(request_id, start, tokens,
                             replica=replica_id)

    def _place_handoff(self, replica_id: int, req: Request,
                       dest: Optional[int]) -> None:
        self.router.place_handoff(req, from_replica=replica_id, dest=dest)

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        for r in self.replicas:
            r.start()
        if self._supervise:
            self.supervisor.start()

    def shutdown(self) -> None:
        self.supervisor.stop()
        if self.autoscaler is not None and \
                self.autoscaler.spawner is not None:
            self.autoscaler.spawner.shutdown()
        # drain the standby pool: unconsumed spares free their engines;
        # the warmer releases any build still in flight when it lands
        with self._spares_cv:
            self._spares_closed = True
            spares, self._spares = self._spares, []
            self._spares_pending.clear()
            self._spares_cv.notify_all()
        for s in spares:
            try:
                s.engine.release()
            except Exception:
                pass
        for r in self.replicas:
            r.stop()
            engine = getattr(r, "engine", None)   # remote: no engine here
            if engine is not None:
                try:
                    engine.release()
                except Exception:
                    pass

    # -- serving -------------------------------------------------------------

    def submit(self, prompt_tokens: Sequence[int],
               sampling: Optional[SamplingParams] = None,
               request_id: Optional[str] = None,
               on_complete: Optional[Callable[[Request], None]] = None,
               priority: str = "standard") -> Request:
        return self.router.submit(prompt_tokens, sampling,
                                  request_id=request_id,
                                  on_complete=on_complete,
                                  priority=priority)

    def submit_streaming(self, prompt_tokens: Sequence[int],
                         sampling: Optional[SamplingParams] = None,
                         request_id: Optional[str] = None,
                         on_complete: Optional[Callable[[Request], None]]
                         = None, priority: str = "standard") -> Request:
        """Admit one STREAMING request: its token batches flow through
        the fleet stream hub (``self.streams``) with monotonic sequence
        numbers, across every re-placement the fleet performs. The log
        is opened BEFORE placement so no producer can race the first
        token past it; a rejected submission tears it down again. The
        hub finishes (and final-syncs) the log on the request's terminal
        state — normal completion AND router-side failure — before the
        caller's ``on_complete`` fires."""
        import uuid as _uuid
        rid = request_id or f"fleet-{_uuid.uuid4().hex[:24]}"
        self.streams.open(rid)

        def _complete(req: Request) -> None:
            meta = getattr(req, "fleet_meta", {}) or {}
            self.streams.finish_from_request(req,
                                             replica=meta.get("replica"))
            if on_complete is not None:
                on_complete(req)

        try:
            return self.router.submit(prompt_tokens, sampling,
                                      request_id=rid,
                                      on_complete=_complete, stream=True,
                                      priority=priority)
        except Exception:
            self.streams.discard(rid)
            raise

    def generate(self, prompts: Sequence[Sequence[int]],
                 sampling: Optional[SamplingParams] = None,
                 timeout_s: float = 300.0) -> list[Request]:
        """Synchronous batch convenience (tests + dryrun): submit every
        prompt, wait for terminal states. Without a supervisor thread the
        wait loop polls the supervisor, so crash/drain recovery still
        happens — deterministically on THIS thread."""
        events: list[threading.Event] = []
        reqs: list[Request] = []
        for p in prompts:
            ev = threading.Event()
            reqs.append(self.submit(p, sampling,
                                    on_complete=lambda _r, ev=ev: ev.set()))
            events.append(ev)
        deadline = time.monotonic() + timeout_s
        for ev in events:
            while not ev.wait(timeout=0.02):
                if not self._supervise:
                    self.supervisor.poll_once()
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"fleet generate: {sum(not e.is_set() for e in events)}"
                        f" of {len(events)} requests still pending")
        return reqs

    # -- operator surface ----------------------------------------------------

    def drain(self, replica_id: int) -> bool:
        return self.supervisor.drain(replica_id)

    def undrain(self, replica_id: int) -> bool:
        return self.supervisor.undrain(replica_id)

    def migrate(self, request_id: str, dest_replica: int) -> bool:
        """Move one in-flight request to ``dest_replica`` WITH its KV
        pages (no re-prefill) — `llmctl fleet migrate`."""
        return self.supervisor.migrate(request_id, dest_replica)

    def set_role(self, replica_id: int, role: str) -> bool:
        """Manually re-role one replica (prefill|decode|mixed) —
        `llmctl fleet role` / POST /fleet/role."""
        return self.supervisor.set_role(replica_id, role)

    def status(self) -> dict:
        return self.supervisor.snapshot()

    def serve_prefix_fetch(self, body: dict) -> dict:
        """Owner side of ``POST /fleet/courier/fetch`` when the owning
        replica is IN-PROC behind this front: extract the cached prefix
        pages (on that replica's engine thread) and PUSH them, chunked,
        to the remote fetcher's courier endpoint. Mirrors the worker's
        handler so remote workers can fetch from in-proc owners."""
        from .transport import HTTPCourierTransport, TransportError
        try:
            owner = int(body.get("replica", -1))
            hashes = [bytes.fromhex(h) for h in body.get("hashes", [])]
        except (TypeError, ValueError):
            return {"ok": False, "error": "malformed replica/hashes"}
        ticket = str(body.get("ticket") or "")
        dest_ep = str(body.get("dest_endpoint") or "").rstrip("/")
        if not hashes or not ticket or not dest_ep:
            return {"ok": False, "error":
                    "body must be {replica, hashes, ticket, dest_endpoint}"}
        provider = self.courier.prefix_providers.get(owner)
        if provider is None:
            return {"ok": False,
                    "error": f"no in-proc replica {owner} here"}
        payload = provider(hashes, self.fleet_cfg.prefix_fetch_timeout_s)
        if not payload:
            return {"ok": False, "error": "prefix pages not cached"}
        transport = HTTPCourierTransport(
            self.fleet_cfg, injector=self.injector,
            stats=self.courier.stats, endpoint=dest_ep)
        try:
            transport.transfer(payload, src=owner,
                               dest=body.get("dest"), ticket=ticket)
        except TransportError as e:
            return {"ok": False, "error": str(e)}
        return {"ok": True, "ticket": ticket,
                "covered": int(payload["pages"]["num_pages"])}
