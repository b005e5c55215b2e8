"""HA front tier: externalized fleet state + stateless fronts.

The load-bearing assertions mirror the tentpole's acceptance bar:

- the shared file state store journals, folds, fences, and elects a
  deterministic adopter (units, two store instances over one dir);
- two stream hubs over one store converge on one log per request —
  either front serves the replay for a stream it never terminated, a
  locally-buffered out-of-order batch still reaches the journal when a
  FOLD fills its gap, and finish propagates (the failover delivery
  contract without any sockets);
- two routers over one store share the ledger: membership, terminal
  counters, the per-request requeue budget, and a dead front's parked
  request is adopted (fence-first) and re-placed by the survivor;
- the full foreign-finish path over a real socket: two ServeFleets on
  one store and one fake worker — the front that never submitted the
  request closes the shared log and the submitting front's waiter
  still fires (the kill-the-front correctness core, deterministic);
- the unfinished-stream-log leak is fixed (gc + router.knows);
- FaultInjector's seeded front-kill/front-stall faults draw
  deterministically and fire once;
- the loadgen FrontStreamClient survives a front that dies mid-SSE:
  doubling-backoff round-robin reconnect to the next front with
  Last-Event-ID, per-front reconnect counts reported;
- a front's /health answers "starting"/503 until it attached to the
  store and read one supervisor snapshot (the readiness gate).

The multi-process SIGKILL chaos proof (real `llmctl fleet front`
processes over real workers) lives in the `serve.fleet2+ha-front`
dryrun regime.
"""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from types import SimpleNamespace

import pytest

from distributed_llm_training_and_inference_system_tpu.config import (
    get_model_config)
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ConfigError,
    FleetConfig,
    ServeConfig,
)
from distributed_llm_training_and_inference_system_tpu.serve import (
    SamplingParams,
)
from distributed_llm_training_and_inference_system_tpu.serve.fleet import (
    FleetStreamHub,
    ServeFleet,
)
from distributed_llm_training_and_inference_system_tpu.serve.fleet.faults import (  # noqa: E501
    FaultInjector,
    FaultPlan,
)
from distributed_llm_training_and_inference_system_tpu.serve.fleet.router import (  # noqa: E501
    FleetRouter,
)
from distributed_llm_training_and_inference_system_tpu.serve.fleet.state import (  # noqa: E501
    InMemoryStateStore,
    SharedFileStateStore,
    StoreFenced,
)

pytestmark = pytest.mark.sse


def serve_cfg(**overrides) -> ServeConfig:
    kw = dict(model="gpt-test", max_batch_size=2, max_seq_len=256,
              prefill_chunk=32, kv_block_size=8, dtype="float32")
    kw.update(overrides)
    return ServeConfig(**kw)


@pytest.fixture(scope="module")
def model_cfg():
    return get_model_config("gpt-test")


# -- state store units --------------------------------------------------------


class TestSharedFileStateStore:
    def test_journal_round_trip_filters_own_records(self, tmp_path):
        a = SharedFileStateStore(tmp_path, front_id="A")
        b = SharedFileStateStore(tmp_path, front_id="B")
        a.record({"ns": "x", "op": "one"})
        b.record({"ns": "x", "op": "two"})
        a.record({"ns": "x", "op": "three"})
        # B sees A's records (in order), never its own
        got = b.poll()
        assert [r["op"] for r in got] == ["one", "three"]
        assert all(r["f"] == "A" for r in got)
        # cursor advanced: nothing new
        assert b.poll() == []
        a.record({"ns": "x", "op": "four"})
        assert [r["op"] for r in b.poll()] == ["four"]

    def test_sync_dispatches_by_namespace(self, tmp_path):
        a = SharedFileStateStore(tmp_path, front_id="A")
        b = SharedFileStateStore(tmp_path, front_id="B")
        seen = []
        b.on("x", lambda rec: seen.append(rec["op"]))
        a.record({"ns": "x", "op": "hello"})
        a.record({"ns": "unhandled", "op": "ignored"})
        assert b.sync() == 2        # both folded, one dispatched
        assert seen == ["hello"]

    def test_registry_attach_heartbeat_alive_expiry(self, tmp_path):
        # expiry well above a loaded CI worker's stall between b's
        # heartbeat and its view (50 ms flaked under six xdist workers)
        a = SharedFileStateStore(tmp_path, front_id="A", expiry_s=0.5)
        b = SharedFileStateStore(tmp_path, front_id="B", expiry_s=0.5)
        ea = a.attach(info={"port": 1234})
        eb = b.attach()
        assert eb == ea + 1                  # monotone fencing epochs
        view = b.fronts_view()
        assert view["A"]["port"] == 1234 and view["A"]["alive"]
        assert a.front_alive("B")
        time.sleep(0.7)
        b.heartbeat()
        view = b.fronts_view()
        assert not view["A"]["alive"] and view["B"]["alive"]

    def test_fencing_refuses_writes_and_reattach_clears(self, tmp_path):
        a = SharedFileStateStore(tmp_path, front_id="A")
        b = SharedFileStateStore(tmp_path, front_id="B")
        assert b.fence("A") is True
        assert b.fence("A") is False         # already fenced
        assert a.is_fenced()
        with pytest.raises(StoreFenced):
            a.record({"ns": "x", "op": "zombie"})
        # a NEW incarnation re-attaching under the id is un-fenced
        a.attach()
        a.record({"ns": "x", "op": "fresh"})
        assert [r["op"] for r in b.poll()] == ["fresh"]

    def test_adopter_is_smallest_alive_front(self, tmp_path):
        a = SharedFileStateStore(tmp_path, front_id="A", expiry_s=0.5)
        b = SharedFileStateStore(tmp_path, front_id="B", expiry_s=0.5)
        a.attach()
        b.attach()
        assert a.is_adopter() and not b.is_adopter()
        time.sleep(0.7)                      # A goes stale
        b.heartbeat()
        assert b.is_adopter()

    def test_counters_and_registry_survive_reopen(self, tmp_path):
        a = SharedFileStateStore(tmp_path, front_id="A")
        a.attach(info={"port": 7})
        assert a.incr("failovers") == 1
        assert a.incr("failovers", 2) == 3
        # a fresh instance over the same dir reads the same state
        c = SharedFileStateStore(tmp_path, front_id="C")
        assert c.counters_view() == {"failovers": 3}
        assert c.fronts_view()["A"]["port"] == 7

    def test_in_memory_store_is_inert(self):
        s = InMemoryStateStore()
        s.record({"ns": "x", "op": "gone"})
        assert s.poll() == [] and s.sync() == 0
        assert not s.shared and s.fronts_view() == {}
        assert s.is_adopter() and s.front_alive(s.front_id)


# -- two hubs over one store --------------------------------------------------


class TestHubSharedStore:
    def mk(self, tmp_path, fid):
        return FleetStreamHub(
            store=SharedFileStateStore(tmp_path, front_id=fid))

    def test_other_front_serves_replay_and_live_tail(self, tmp_path):
        hub_a = self.mk(tmp_path, "A")
        hub_b = self.mk(tmp_path, "B")
        hub_a.open("r")
        hub_a.publish("r", 0, [1, 2, 3], replica=0)
        # B never terminated this stream; it serves the replay anyway
        assert hub_b.has("r")
        got = []
        sub = hub_b.subscribe("r", 1, got.append, resume=True)
        assert sub["tokens"] == [2, 3]
        assert hub_b.total_front_resumes == 1    # a failover resume
        assert hub_a.total_front_resumes == 0
        # live continuation crosses the store into B's subscriber
        hub_a.publish("r", 3, [4, 5], replica=0)
        hub_b.store.sync()
        assert got == [("tokens", 3, [4, 5])]
        hub_a.finish("r", "stop")
        hub_b.store.sync()
        assert got[-1] == ("finish", "stop", None)
        # both views agree on the log
        assert hub_a.tokens_of("r") == hub_b.tokens_of("r") \
            == [1, 2, 3, 4, 5]

    def test_local_pending_batch_journaled_when_fold_fills_gap(
            self, tmp_path):
        """B holds a LOCAL out-of-order batch; the gap is filled by a
        FOLD from A. B's drained batch must still reach the journal —
        it is B's fact — so A converges too."""
        hub_a = self.mk(tmp_path, "A")
        hub_b = self.mk(tmp_path, "B")
        hub_a.open("r")
        hub_a.publish("r", 0, [9], replica=0)
        hub_b.store.sync()
        hub_b.publish("r", 3, [12, 13], replica=1)   # ahead of gap: held
        hub_a.sync("r", [9, 10, 11])                 # A heals the gap
        hub_b.store.sync()
        assert hub_b.tokens_of("r") == [9, 10, 11, 12, 13]
        hub_a.store.sync()
        assert hub_a.tokens_of("r") == [9, 10, 11, 12, 13]

    def test_late_attached_front_folds_whole_history(self, tmp_path):
        hub_a = self.mk(tmp_path, "A")
        hub_a.open("r")
        hub_a.publish("r", 0, [1, 2], replica=0)
        hub_a.finish("r", "length")
        # C starts AFTER the stream finished: full replay still works
        hub_c = self.mk(tmp_path, "C")
        sub = hub_c.subscribe("r", 0, lambda ev: None, resume=True)
        assert sub["tokens"] == [1, 2] and sub["finished"]
        assert sub["finish_reason"] == "length"

    def test_cross_front_duplicate_publish_suppressed(self, tmp_path):
        hub_a = self.mk(tmp_path, "A")
        hub_b = self.mk(tmp_path, "B")
        hub_a.open("r")
        hub_a.publish("r", 0, [1, 2], replica=0)
        hub_b.store.sync()
        # both fronts fold the same worker batch (outbox race): dedupe
        hub_b.publish("r", 0, [1, 2, 3], replica=0)
        hub_a.store.sync()
        assert hub_a.tokens_of("r") == [1, 2, 3]
        assert hub_a.stats()["identity_mismatches"] == 0

    def test_discard_propagates(self, tmp_path):
        hub_a = self.mk(tmp_path, "A")
        hub_b = self.mk(tmp_path, "B")
        hub_a.open("r")
        assert hub_b.has("r")
        hub_a.discard("r")
        hub_b.store.sync()
        assert not hub_b._logs.get("r")


# -- unfinished-log GC (the PR-8 leak) ---------------------------------------


class TestUnfinishedLogGC:
    def test_orphan_log_collected_once_router_forgets(self):
        hub = FleetStreamHub(ttl_ms=1.0)
        hub.open("orphan")
        hub.open("live")
        rec = []
        hub.subscribe("orphan", 0, rec.append)
        time.sleep(0.01)
        # router still knows both: nothing collected
        assert hub.gc(known=lambda rid: True) == 0
        # router forgot "orphan" (failed before placement): collected,
        # counted, subscriber released with a finish event
        evicted = hub.gc(known=lambda rid: rid == "live")
        assert evicted == 1
        assert not hub.has("orphan") and hub.has("live")
        assert hub.stats()["orphan_logs_gc"] == 1
        assert rec and rec[-1][0] == "finish"

    def test_grace_window_protects_fresh_logs(self):
        hub = FleetStreamHub(ttl_ms=60_000.0)
        hub.open("fresh")        # opened but not yet in the router
        assert hub.gc(known=lambda rid: False) == 0
        assert hub.has("fresh")

    def test_without_known_behavior_unchanged(self):
        hub = FleetStreamHub(ttl_ms=1.0)
        hub.open("r")
        time.sleep(0.01)
        assert hub.gc() == 0                 # live logs never evicted
        hub.finish("r", "stop")
        time.sleep(0.01)
        assert hub.gc() == 1


# -- two routers over one store ----------------------------------------------


class FakeReplica:
    def __init__(self, rid, accept=True):
        self.replica_id = rid
        self.accept_flag = accept
        self.reqs = []
        self.state = "healthy"
        self.role = "mixed"

    def accepting(self):
        return self.accept_flag

    def submit(self, req):
        if self.accept_flag:
            self.reqs.append(req)
            return True
        return False

    def queue_depth(self):
        return 0

    def outstanding_tokens(self):
        return len(self.reqs)


class TestRouterSharedLedger:
    def mk(self, tmp_path, fid, replica, **cfg_kw):
        cfg = FleetConfig(replicas=1, affinity_prefix_tokens=0,
                          **cfg_kw)
        store = SharedFileStateStore(tmp_path, front_id=fid,
                                     expiry_s=0.05)
        store.attach()
        return FleetRouter([replica], cfg, store=store)

    def test_membership_counters_and_terminal_fold(self, tmp_path):
        ra = self.mk(tmp_path, "A", FakeReplica(0))
        rb = self.mk(tmp_path, "B", FakeReplica(0))
        req = ra.submit([1, 2, 3])
        rb.store.sync()
        assert rb.knows(req.request_id)
        assert rb.stats()["submitted"] == 1
        assert rb.stats()["in_flight"] == 1
        from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (  # noqa: E501
            RequestState)
        req.state = RequestState.FINISHED
        req.finish_reason = "stop"
        req.generated_tokens = [7, 8]
        ra.on_request_exit(0, req)
        rb.store.sync()
        st = rb.stats()
        assert st["completed"] == 1 and st["in_flight"] == 0
        assert not rb.knows(req.request_id)

    def test_requeue_budget_shared_across_fronts(self, tmp_path):
        fa = FakeReplica(0)
        ra = self.mk(tmp_path, "A", fa, max_requeues=2)
        rb = self.mk(tmp_path, "B", FakeReplica(0), max_requeues=2)
        req = ra.submit([1, 2, 3])
        ra.requeue([req], from_replica=0)
        ra.requeue([req], from_replica=0)
        rb.store.sync()
        # B folded requeues=2: one more ANYWHERE busts the budget
        meta = rb._meta[req.request_id]
        assert meta["requeues"] == 2
        assert rb.stats()["requeues"] == 2

    def test_dead_front_parked_request_adopted(self, tmp_path):
        fa = FakeReplica(0)
        ra = self.mk(tmp_path, "A", fa)
        fb = FakeReplica(0)
        rb = self.mk(tmp_path, "B", fb)
        req = ra.submit([1, 2, 3])
        fa.accept_flag = False
        ra.requeue([req], from_replica=0)     # nowhere to go: parks
        assert ra.parked_count() == 1
        rb.store.sync()
        assert rb.stats()["parked_remote"] == 1
        # while A is alive, B must NOT steal its parked request
        rb.store.heartbeat()
        ra.store.heartbeat()
        assert rb.flush_parked() == 0
        # A falls silent. Wait for the adoption, not for the clock: B is
        # the adopter only while its OWN heartbeat is younger than the
        # store's 50 ms, and a pass in which it went stale before
        # flush_parked looked adopts nothing, changes nothing, and is
        # made again
        placed, deadline = 0, time.monotonic() + 10.0
        while not placed and time.monotonic() < deadline:
            time.sleep(0.08)                  # A's heartbeat goes stale
            rb.store.heartbeat()
            placed = rb.flush_parked()
        assert placed == 1
        assert fb.reqs and fb.reqs[0].request_id == req.request_id
        assert rb.total_parked_adopted == 1
        assert rb.stats()["parked_adopted"] == 1
        # fence-first: the dead owner can no longer write
        assert rb.store.is_fenced("A")

    def test_in_memory_router_identical_surface(self):
        r = FleetRouter([FakeReplica(0)],
                        FleetConfig(replicas=1,
                                    affinity_prefix_tokens=0))
        req = r.submit([1, 2, 3])
        assert r.knows(req.request_id)
        st = r.stats()
        assert st["parked_remote"] == 0 and st["parked_adopted"] == 0


# -- foreign finish over a real socket ---------------------------------------


def make_fake_worker():
    """Minimal stdlib fake `llmctl fleet worker`: accepts submits,
    serves a scripted outbox, answers probes healthy."""
    fake = SimpleNamespace(submitted=[], outbox=[], endpoint=None)

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def _reply(self, body, status=200):
            data = json.dumps(body).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):
            self._reply({"state": "healthy", "queue_depth": 0,
                         "active": 0, "outstanding_tokens": 0})

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            json.loads(self.rfile.read(n) or b"{}")
            if self.path == "/worker/submit":
                fake.submitted.append(True)
                self._reply({"ok": True})
            elif self.path == "/worker/outbox/take":
                entries, fake.outbox = fake.outbox, []
                self._reply({"entries": entries})
            else:
                self._reply({"ok": True})

    server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    fake.endpoint = f"http://127.0.0.1:{server.server_address[1]}"
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    fake.close = lambda: (server.shutdown(), server.server_close())
    return fake


@pytest.mark.socket
class TestForeignFinish:
    def test_sibling_front_closes_stream_and_owner_waiter_fires(
            self, model_cfg, tmp_path):
        """The kill-the-front correctness core, deterministically: front
        A submits a streaming request; the worker's stream + finished
        outbox entries drain to front B (the outbox split); B closes
        the SHARED log and journals the terminal tokens; A folds and
        its waiter fires with the full token list."""
        fake = make_fake_worker()
        try:
            def fleet(fid):
                return ServeFleet(
                    model_cfg, serve_cfg(),
                    FleetConfig(replicas=1, remote_replicas="0",
                                fleet_endpoints={0: fake.endpoint},
                                affinity_prefix_tokens=0,
                                state_store="file",
                                state_store_dir=str(tmp_path),
                                probe_interval_s=0.05),
                    supervise=False, front_id=fid)

            fa, fb = fleet("A"), fleet("B")
            fa.store.attach()
            fb.store.attach()
            done = threading.Event()
            req = fa.submit_streaming(
                [1, 2, 3],
                SamplingParams(temperature=0.0, max_tokens=4),
                on_complete=lambda _r: done.set())
            rid = req.request_id
            assert fake.submitted
            # a client is attached to B from the start — B never
            # terminated the original connection
            got = []
            assert fb.streams.has(rid)
            fb.streams.subscribe(rid, 0, got.append)
            # the worker streams through B's poll, then finishes there
            fake.outbox.append({"kind": "stream", "request_id": rid,
                                "start": 0, "tokens": [7, 8],
                                "seed": 1})
            fb.replicas[0].poll_outbox()
            assert got == [("tokens", 0, [7, 8])]
            fake.outbox.append({
                "kind": "finished", "request_id": rid,
                "generated_tokens": [7, 8, 9], "finish_reason": "stop",
                "state": "completed", "error": None, "ttft_ms": 1.0})
            fb.replicas[0].poll_outbox()
            # B healed the tail and finished the shared log
            assert got[-1] == ("finish", "stop", None)
            assert [e for e in got if e[0] == "tokens"] \
                == [("tokens", 0, [7, 8]), ("tokens", 2, [9])]
            assert fb.router.stats()["completed"] == 1
            # A folds the terminal record: waiter fires, object complete
            fa.store.sync()
            assert done.is_set()
            assert req.generated_tokens == [7, 8, 9]
            assert req.finish_reason == "stop"
            sa = fa.router.stats()
            assert sa["completed"] == 1 and sa["in_flight"] == 0
            assert fa.streams.tokens_of(rid) == [7, 8, 9]
        finally:
            fake.close()


# -- seeded front faults ------------------------------------------------------


class TestFrontFaults:
    def test_seeded_draw_deterministic_and_fires_once(self):
        t1 = FaultInjector(FaultPlan(seed=7, front_kill_front=0))
        t2 = FaultInjector(FaultPlan(seed=7, front_kill_front=0))
        assert t1._front_kill_at == t2._front_kill_at
        at = t1._front_kill_at
        assert FaultPlan().front_fault_lo_s <= at \
            < FaultPlan().front_fault_hi_s
        assert t1.front_faults_due(at - 0.01) == []
        assert t1.front_faults_due(at) == [("kill", 0)]
        assert t1.front_faults_due(at + 99) == []      # fired once

    def test_pinned_times_and_stall(self):
        inj = FaultInjector(FaultPlan(
            front_kill_front=1, front_kill_after_s=2.0,
            front_stall_front=0, front_stall_after_s=1.0,
            front_stall_ms=50.0))
        assert inj.front_faults_due(0.5) == []
        assert inj.front_faults_due(1.5) == [("stall", 0, 50.0)]
        assert inj.front_faults_due(2.5) == [("kill", 1)]

    def test_no_front_faults_by_default(self):
        inj = FaultInjector(FaultPlan(seed=3))
        assert inj.front_faults_due(1e9) == []


# -- loadgen front-list reconnect hardening ----------------------------------


def make_sse_front(rid, first_tokens, tail_tokens, die_after_first=False):
    """Fake front: POST /v1/completions streams ``first_tokens`` one
    event per token (then drops the connection WITHOUT [DONE] when
    ``die_after_first``); GET /v1/streams/{rid} replays from
    last_event_id+1 out of first+tail and finishes properly."""
    all_tokens = list(first_tokens) + list(tail_tokens)

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.0"

        def log_message(self, *a):
            pass

        def _event(self, seq_last, toks, finish=None):
            payload = {"id": rid, "seq": seq_last,
                       "choices": [{"token_ids": toks,
                                    "finish_reason": finish}]}
            return (f"id: {seq_last}\ndata: "
                    f"{json.dumps(payload)}\n\n").encode()

        def _head(self):
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.end_headers()

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            self.rfile.read(n)
            self._head()
            for i, t in enumerate(first_tokens):
                self.wfile.write(self._event(i, [t]))
            if not die_after_first:
                self.wfile.write(b"data: [DONE]\n\n")
            # return without [DONE]: the abrupt close a SIGKILL causes

        def do_GET(self):
            from urllib.parse import parse_qs, urlparse
            q = parse_qs(urlparse(self.path).query)
            last = int(q.get("last_event_id", ["-1"])[0])
            self._head()
            for i in range(last + 1, len(all_tokens)):
                self.wfile.write(self._event(
                    i, [all_tokens[i]],
                    finish="stop" if i == len(all_tokens) - 1 else None))
            self.wfile.write(b"data: [DONE]\n\n")

    server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    return server, f"http://127.0.0.1:{server.server_address[1]}"


@pytest.mark.socket
class TestFrontStreamClient:
    def test_reconnects_round_robin_with_replay(self):
        from distributed_llm_training_and_inference_system_tpu.serve.loadgen import (  # noqa: E501
            FrontStreamClient)
        s1, u1 = make_sse_front("rid-1", [10, 11], [12, 13],
                                die_after_first=True)
        s2, u2 = make_sse_front("rid-1", [10, 11], [12, 13])
        try:
            client = FrontStreamClient([u1, u2], backoff_s=0.01)
            out = client.stream([1, 2, 3], max_tokens=4, start_front=0)
            assert out["ok"], out
            assert out["tokens"] == [10, 11, 12, 13]
            assert out["gaps"] == 0 and out["dups"] == 0
            assert out["finish_reason"] == "stop"
            # the reconnect landed on the NEXT front, counted per front
            assert client.reconnects_per_front[u2] == 1
            assert client.reconnects_per_front[u1] == 0
            assert client.total_reconnects == 1
        finally:
            s1.shutdown(), s1.server_close()
            s2.shutdown(), s2.server_close()

    def test_dead_first_front_retries_submission(self):
        from distributed_llm_training_and_inference_system_tpu.serve.loadgen import (  # noqa: E501
            FrontStreamClient)
        s2, u2 = make_sse_front("rid-2", [5, 6], [])
        try:
            # front 0 refuses connections outright
            client = FrontStreamClient(
                ["http://127.0.0.1:9", u2], backoff_s=0.01)
            out = client.stream([1], max_tokens=2, start_front=0)
            assert out["ok"] and out["tokens"] == [5, 6]
            assert client.total_retries >= 1
        finally:
            s2.shutdown(), s2.server_close()

    def test_exhausted_attempts_reports_failure(self):
        from distributed_llm_training_and_inference_system_tpu.serve.loadgen import (  # noqa: E501
            FrontStreamClient)
        client = FrontStreamClient(["http://127.0.0.1:9"],
                                   max_attempts=2, backoff_s=0.005)
        out = client.stream([1], max_tokens=2)
        assert not out["ok"] and out["error"]


# -- config validation --------------------------------------------------------


class TestFrontTierConfig:
    def test_fronts_require_file_store_and_remote_replicas(self):
        with pytest.raises(ConfigError, match="state_store=file"):
            FleetConfig(replicas=1, fronts=2).validate()
        with pytest.raises(ConfigError, match="remote"):
            FleetConfig(replicas=1, fronts=2, state_store="file",
                        state_store_dir="/tmp/x").validate()
        with pytest.raises(ConfigError, match="state_store_dir"):
            FleetConfig(replicas=1, state_store="file").validate()
        with pytest.raises(ConfigError, match="state_store"):
            FleetConfig(replicas=1, state_store="redis").validate()
        FleetConfig(replicas=1, fronts=2, state_store="file",
                    state_store_dir="/tmp/x", remote_replicas="0",
                    fleet_endpoints={0: "http://h:1"}).validate()


# -- front readiness gate -----------------------------------------------------


@pytest.mark.socket
class TestFrontReadiness:
    def test_health_starting_until_attached_and_snapshotted(
            self, model_cfg, tmp_path):
        import asyncio

        from distributed_llm_training_and_inference_system_tpu.serve.fleet.http import (  # noqa: E501
            FleetServer)
        srv = FleetServer(
            model_cfg, serve_cfg(host="127.0.0.1", port=0),
            FleetConfig(replicas=1, remote_replicas="0",
                        # dead endpoint: replicas unreachable, but the
                        # READINESS gate is about store+snapshot, not
                        # replica health
                        fleet_endpoints={0: "http://127.0.0.1:9"},
                        state_store="file",
                        state_store_dir=str(tmp_path),
                        probe_interval_s=0.05))

        async def scenario():
            resp = await srv.handle_health(None)
            before = json.loads(resp.body.decode())
            assert resp.status == 503 and before["status"] == "starting"
            runner = await srv.start_async()
            try:
                resp = await srv.handle_health(None)
                after = json.loads(resp.body.decode())
                # ready: no longer "starting" — now reporting real
                # fleet state (replicas start optimistically healthy
                # until probes correct them, so either verdict is fine;
                # the gate's contract is only "attached + snapshotted")
                assert after["status"] in ("healthy", "degraded")
                assert srv.fleet.store.fronts_view()[
                    srv.fleet.front_id]["alive"]
                snap = srv.fleet.status()
                assert snap["front_tier"]["front_id"] \
                    == srv.fleet.front_id
            finally:
                if srv._refresher is not None:
                    srv._refresher.cancel()
                await runner.cleanup()
                srv.fleet.shutdown()

        asyncio.run(scenario())


# -- journal compaction (PR-12 known gap: snapshot + truncate) ---------------


class TestJournalCompaction:
    """The file store's journal grows unboundedly without compaction
    (PR-12 known gap). The contract: ``compact()`` folds the prefix
    every attached, unfenced front has already consumed into
    snapshot.jsonl — terminal request groups collapsed to aggregated
    count records, finished stream groups dropped, counter records
    merged — truncates the journal to its tail under a fresh generation
    (one atomic registry flip), and a FRESH front folding snapshot +
    tail reaches the same live state and counters as one folding the
    original journal."""

    def _workload(self, store, requests=30, terminal=20, streams=5,
                  finished=3):
        for i in range(requests):
            rid = f"r{i}"
            store.record({"ns": "ledger", "op": "put", "rid": rid,
                          "wire": {"prompt_tokens": [1, 2, 3]}})
            store.record({"ns": "ledger", "op": "count",
                          "key": "submitted", "replica": 0})
            store.record({"ns": "ledger", "op": "meta", "rid": rid,
                          "replica": 0})
            if i < terminal:
                store.record({"ns": "ledger", "op": "pop", "rid": rid,
                              "outcome": "completed", "replica": 0,
                              "tokens": [i]})
        for i in range(streams):
            rid = f"s{i}"
            store.record({"ns": "stream", "op": "open", "rid": rid})
            store.record({"ns": "stream", "op": "append", "rid": rid,
                          "s": 0, "t": [1, 2, 3], "r": 0})
            if i < finished:
                store.record({"ns": "stream", "op": "finish",
                              "rid": rid, "reason": "stop",
                              "error": None})

    def _fresh_state(self, tmp_path, fid="FRESH"):
        store = SharedFileStateStore(tmp_path, front_id=fid)
        store.attach()
        hub = FleetStreamHub(store=store)
        router = FleetRouter([FakeReplica(0)],
                             FleetConfig(affinity_prefix_tokens=0),
                             store=store)
        store.sync()
        return hub, router

    def test_compacted_store_replays_identically(self, tmp_path):
        import shutil
        a_dir = tmp_path / "a"
        a = SharedFileStateStore(a_dir, front_id="A")
        a.attach()
        self._workload(a)
        a.poll()                              # advance A's fold frontier
        before = (a_dir / "journal.jsonl").stat().st_size
        shutil.copytree(a_dir, tmp_path / "b")   # uncompacted twin
        pruned = a.compact()
        assert pruned > 0
        reg = json.loads((a_dir / "fronts.json").read_text())
        tail = (a_dir / f"journal.{reg['journal_gen']}.jsonl")
        snap = (a_dir / reg["journal_snapshot"])
        assert tail.stat().st_size + snap.stat().st_size < before
        assert not (a_dir / "journal.jsonl").exists()   # old gen gone

        h1, r1 = self._fresh_state(a_dir)
        h2, r2 = self._fresh_state(tmp_path / "b")
        s1, s2 = r1.stats(), r2.stats()
        for key in ("completed", "failed", "rejected", "submitted",
                    "requeues", "in_flight"):
            assert s1[key] == s2[key], (key, s1[key], s2[key])
        assert s1["completed_per_replica"] == s2["completed_per_replica"]
        assert sorted(r1._meta) == sorted(r2._meta)
        # LIVE streams replay identically; finished ones (which the TTL
        # would GC anyway) are dropped by compaction — the documented
        # semantic difference
        live1 = {rid for rid, log in h1._logs.items()
                 if not log.finished}
        live2 = {rid for rid, log in h2._logs.items()
                 if not log.finished}
        assert live1 == live2
        for rid in live1:
            assert h1._logs[rid].tokens == h2._logs[rid].tokens

    def test_trim_bounded_by_slowest_front_cursor(self, tmp_path):
        """A sibling that has folded nothing past its cursor must keep
        its unread tail in the journal — and keep folding correctly
        across the generation flip, with nothing double-counted."""
        a = SharedFileStateStore(tmp_path, front_id="A")
        b = SharedFileStateStore(tmp_path, front_id="B")
        a.attach()
        b.attach()
        rb = FleetRouter([FakeReplica(0)],
                         FleetConfig(affinity_prefix_tokens=0), store=b)
        self._workload(a, requests=10, terminal=10, streams=0)
        b.sync()                              # B fully folded
        completed_mid = rb.stats()["completed"]
        assert completed_mid == 10
        self._workload(a, requests=4, terminal=4, streams=0)
        a.poll()
        assert a.compact() > 0                # trims only B's folded part
        # B folds the tail (the 4 new requests) across the flip
        b.sync()
        assert rb.stats()["completed"] == 14  # no loss, no double count
        # second compaction can now take the rest
        a.poll()
        a.compact()
        c = SharedFileStateStore(tmp_path, front_id="C")
        c.attach()
        rc = FleetRouter([FakeReplica(0)],
                         FleetConfig(affinity_prefix_tokens=0), store=c)
        c.sync()
        assert rc.stats()["completed"] == 14

    def test_fenced_front_cannot_compact(self, tmp_path):
        a = SharedFileStateStore(tmp_path, front_id="A")
        b = SharedFileStateStore(tmp_path, front_id="B")
        a.attach()
        self._workload(a, requests=3, terminal=3, streams=0)
        a.poll()
        b.fence("A")
        assert a.compact() == 0

    def test_periodic_compaction_via_record(self, tmp_path):
        a = SharedFileStateStore(tmp_path, front_id="A",
                                 compact_every=40)
        a.attach()
        # interleave folds so the cursor keeps up and compaction can
        # actually trim when record() triggers it
        for _ in range(4):
            self._workload(a, requests=5, terminal=5, streams=0)
            a.poll()
        assert a.compactions >= 1
        reg = json.loads((tmp_path / "fronts.json").read_text())
        assert reg.get("journal_gen", 0) >= 1
        # the store still round-trips for a fresh reader
        _hub, router = self._fresh_state(tmp_path)
        assert router.stats()["completed"] == 20

    def test_aggregated_counts_preserve_per_front_filtering(
            self, tmp_path):
        """Compacted count records keep their originating front id, so
        the originator never double-folds its own aggregates."""
        a = SharedFileStateStore(tmp_path, front_id="A")
        a.attach()
        ra = FleetRouter([FakeReplica(0)],
                         FleetConfig(affinity_prefix_tokens=0), store=a)
        self._workload(a, requests=6, terminal=6, streams=0)
        a.poll()
        a.compact()
        before = ra.stats()["completed"]
        a.sync()                              # folds nothing of its own
        assert ra.stats()["completed"] == before
