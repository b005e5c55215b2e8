"""Engine-backed fleet tests: the control plane over real threaded
InferenceEngine replicas on CPU.

The load-bearing assertions mirror the subsystem's acceptance bar:

- with a replica CRASHED mid-decode by the deterministic fault injector,
  every accepted request completes via requeue with output
  token-identical to a crash-free run, and the router ledger accounts
  for every request (completed + failed + rejected == submitted);
- a DRAINED replica's in-flight sequences resume on survivors without KV
  corruption and token-identically (scheduler-under-drain satellite);
- probe-timeout teardown restarts under exponential backoff;
- loadgen fleet targeting reports the per-replica breakdown;
- the per-replica Prometheus gauges exist under their documented names.

Weights are built once (module fixture) and shared across every engine,
so each test pays only its replicas' compile time.
"""

import dataclasses
import threading
import time

import pytest

from distributed_llm_training_and_inference_system_tpu.config import (
    get_model_config)
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    FleetConfig,
    ServeConfig,
)
from distributed_llm_training_and_inference_system_tpu.serve import (
    InferenceEngine,
    SamplingParams,
)
from distributed_llm_training_and_inference_system_tpu.serve.fleet import (
    FaultPlan,
    ServeFleet,
)

PROMPTS = [[5, 17, 99, 3, 42, 7, 23], [1, 2, 3, 4, 5], [9, 8, 7, 6],
           [11, 12, 13], [21, 22, 23, 24, 25, 26], [31, 32, 33]]


def serve_cfg(**overrides) -> ServeConfig:
    kw = dict(model="gpt-test", max_batch_size=2, max_seq_len=256,
              prefill_chunk=32, kv_block_size=8, dtype="float32")
    kw.update(overrides)
    return ServeConfig(**kw)


@pytest.fixture(scope="module")
def model_cfg():
    return get_model_config("gpt-test")


@pytest.fixture(scope="module")
def ref_engine(model_cfg):
    """Single undisturbed engine: the token-identity oracle AND the shared
    param tree every fleet in this module reuses."""
    return InferenceEngine(model_cfg, serve_cfg(), seed=0)


def make_fleet(model_cfg, params, *, replicas=2, plan=None, fleet_kw=None,
               serve_kw=None, warm=False) -> ServeFleet:
    fc_kw = dict(replicas=replicas, affinity_prefix_tokens=0,
                 restart_backoff_s=0.05, probe_interval_s=0.05)
    fc_kw.update(fleet_kw or {})
    fc = FleetConfig(**fc_kw)
    if warm and (plan is None or plan.slow_replica is None):
        # slow-replica widener for every mid-decode scenario (the seeded
        # compressed-courier test used to carry its own): on a loaded host
        # the test thread can be descheduled long enough for a 48-token
        # run to finish before its drain lands, leaving nothing to
        # migrate. Replica 0 is where a fresh fleet's load-tie routes the
        # first request, and the one these tests drain.
        plan = dataclasses.replace(plan or FaultPlan(), slow_replica=0,
                                   slow_ms=3.0)
    fleet = ServeFleet(model_cfg, serve_cfg(**(serve_kw or {})), fc,
                       params=params, fault_plan=plan, supervise=False,
                       seed=0)
    if warm:
        # compile every replica's programs BEFORE the engine threads run:
        # migration scenarios must interrupt sequences mid-DECODE, and an
        # un-warmed replica spends its first seconds compiling while its
        # sibling races ahead
        for r in fleet.replicas:
            r.engine.generate([[1, 2, 3]],
                              SamplingParams(temperature=0.0, max_tokens=4))
    fleet.start()
    return fleet


class TestFleetBasics:
    def test_greedy_matches_single_engine(self, model_cfg, ref_engine):
        greedy = SamplingParams(temperature=0.0, max_tokens=8)
        ref = [r.generated_tokens
               for r in ref_engine.generate(PROMPTS, greedy)]
        fleet = make_fleet(model_cfg, ref_engine.params,
                           fleet_kw={"affinity_prefix_tokens": 8})
        try:
            got = [r.generated_tokens
                   for r in fleet.generate(PROMPTS, greedy, timeout_s=240)]
            assert got == ref
            st = fleet.router.stats()
            assert st["completed"] == len(PROMPTS)
            # both replicas did SOME routing work or affinity pinned — the
            # ledger must add up either way
            assert st["completed"] + st["failed"] + st["rejected"] \
                == st["submitted"]
        finally:
            fleet.shutdown()


class TestCrashRequeue:
    def test_crash_mid_decode_token_identical_nothing_dropped(
            self, model_cfg, ref_engine):
        """Acceptance criterion: one replica crashes mid-decode; every
        accepted request completes via requeue, token-identical to the
        crash-free run, fully accounted."""
        greedy = SamplingParams(temperature=0.0, max_tokens=24)
        ref = [r.generated_tokens
               for r in ref_engine.generate(PROMPTS, greedy)]
        plan = FaultPlan(crash_replica=0, crash_after_steps=2)
        fleet = make_fleet(model_cfg, ref_engine.params, plan=plan)
        try:
            reqs = fleet.generate(PROMPTS, greedy, timeout_s=240)
            got = [r.generated_tokens for r in reqs]
            st = fleet.router.stats()
            assert st["requeues"] >= 1, (
                f"crash at step 2 requeued nothing: {st}")
            assert got == ref
            assert st["completed"] == len(PROMPTS)
            assert st["completed"] + st["failed"] + st["rejected"] \
                == st["submitted"]
            assert st["in_flight"] == 0
        finally:
            fleet.shutdown()

    def test_crashed_replica_restarts_and_serves_again(
            self, model_cfg, ref_engine):
        greedy = SamplingParams(temperature=0.0, max_tokens=16)
        plan = FaultPlan(crash_replica=0, crash_after_steps=1)
        fleet = make_fleet(model_cfg, ref_engine.params, plan=plan)
        try:
            fleet.generate(PROMPTS[:4], greedy, timeout_s=240)
            deadline = time.monotonic() + 30
            while fleet.replicas[0].state != "healthy":
                fleet.supervisor.poll_once()
                time.sleep(0.02)
                assert time.monotonic() < deadline, (
                    f"replica 0 never restarted: {fleet.status()}")
            assert fleet.replicas[0].restarts == 1
            # the rebuilt engine serves correctly
            ref = [r.generated_tokens
                   for r in ref_engine.generate([PROMPTS[0]], greedy)]
            got = [r.generated_tokens for r in fleet.generate(
                [PROMPTS[0]], greedy, timeout_s=240)]
            assert got == ref
        finally:
            fleet.shutdown()


class TestDrain:
    def _submit_all(self, fleet, sampling):
        events, reqs = [], []
        for p in PROMPTS:
            ev = threading.Event()
            reqs.append(fleet.submit(
                p, sampling, on_complete=lambda _r, ev=ev: ev.set()))
            events.append(ev)
        return reqs, events

    def _await_all(self, fleet, events, timeout=240.0):
        deadline = time.monotonic() + timeout
        while not all(e.is_set() for e in events):
            fleet.supervisor.poll_once()
            time.sleep(0.02)
            assert time.monotonic() < deadline, "fleet drain test hung"

    def test_drain_requeues_inflight_token_identical(
            self, model_cfg, ref_engine):
        """Scheduler-under-drain satellite: sequences mid-decode on the
        drained replica resume elsewhere with no KV corruption — output
        token-identical to an undisturbed run."""
        greedy = SamplingParams(temperature=0.0, max_tokens=64)
        ref = [r.generated_tokens
               for r in ref_engine.generate(PROMPTS, greedy)]
        fleet = make_fleet(model_cfg, ref_engine.params)
        try:
            reqs, events = self._submit_all(fleet, greedy)
            # wait until replica 0 is actually decoding (tokens exist),
            # so the drain genuinely interrupts in-flight sequences
            deadline = time.monotonic() + 120
            while not any(r.generated_tokens and not e.is_set()
                          for r, e in zip(reqs, events)):
                time.sleep(0.01)
                assert time.monotonic() < deadline
            assert fleet.drain(0)
            self._await_all(fleet, events)
            got = [r.generated_tokens for r in reqs]
            assert got == ref
            assert fleet.replicas[0].state == "drained"
            st = fleet.router.stats()
            assert st["completed"] == len(PROMPTS)
            # drained replica's pool was released cleanly: undrain it and
            # serve on it again (corrupted/leaked KV would diverge or OOM)
            fleet.undrain(0)
            ref2 = [r.generated_tokens for r in ref_engine.generate(
                [PROMPTS[0]], greedy)]
            got2 = [r.generated_tokens for r in fleet.generate(
                [PROMPTS[0]], greedy, timeout_s=240)]
            assert got2 == ref2
        finally:
            fleet.shutdown()

    def test_seeded_sampling_survives_drain(self, model_cfg, ref_engine):
        """Requeue preserves assigned_seed, so even sampled output is
        reproduced exactly after a drain (position-folded PRNG — the same
        guarantee the preemption tests pin within one engine)."""
        sampled = SamplingParams(temperature=0.9, top_k=16, max_tokens=48,
                                 seed=1234)
        ref = [r.generated_tokens
               for r in ref_engine.generate([PROMPTS[0]], sampled)]
        fleet = make_fleet(model_cfg, ref_engine.params)
        try:
            ev = threading.Event()
            req = fleet.submit(PROMPTS[0], sampled,
                               on_complete=lambda _r: ev.set())
            deadline = time.monotonic() + 120
            while not req.generated_tokens and not ev.is_set():
                time.sleep(0.01)
                assert time.monotonic() < deadline
            meta = fleet.router._meta.get(req.request_id) or {}
            home = meta.get("replica")
            if home is not None and not ev.is_set():
                fleet.drain(home)
            self._await_all(fleet, [ev])
            assert req.generated_tokens == ref[0]
        finally:
            fleet.shutdown()


class TestMigration:
    """Cross-replica KV migration (serve/fleet/migration.py): sequences
    move WITH their pages — zero re-prefill, token-identical resume —
    and every failure mode degrades to the PR-2 requeue path."""

    def _submit(self, fleet, prompts, sampling):
        events, reqs = [], []
        for p in prompts:
            ev = threading.Event()
            reqs.append(fleet.submit(
                p, sampling, on_complete=lambda _r, ev=ev: ev.set()))
            events.append(ev)
        return reqs, events

    def _await_all(self, fleet, events, timeout=240.0):
        deadline = time.monotonic() + timeout
        while not all(e.is_set() for e in events):
            fleet.supervisor.poll_once()
            time.sleep(0.005)
            assert time.monotonic() < deadline, "migration test hung"

    def _drain_at_token(self, fleet, n_tokens):
        """Have the replica that serves a request ask for its own drain
        from its ENGINE thread, in the step that delivers the request's
        ``n_tokens``-th token: the drain finds the sequence mid-decode
        however late the main thread runs (waiting for the tokens and
        draining from here lost that race under six workers: a replica
        drained with nothing left to migrate). Installed before the
        submit; returns (the event that says it was asked, the list that
        names the replica). ``fleet.drain`` of that replica afterwards is
        the router's half."""
        asked, home = threading.Event(), []
        for rep in fleet.replicas:
            def hook(req, toks, rep=rep, forward=rep.engine.on_token):
                forward(req, toks)
                if not home and len(req.generated_tokens) >= n_tokens:
                    home.append(rep.replica_id)
                    rep.request_drain()
                    asked.set()
            rep.engine.on_token = hook
        return asked, home

    def _wait_decoding(self, reqs, events, n_tokens=2, timeout=120.0,
                      mode=all):
        deadline = time.monotonic() + timeout
        while not mode(len(r.generated_tokens) >= n_tokens or e.is_set()
                       for r, e in zip(reqs, events)):
            time.sleep(0.002)
            assert time.monotonic() < deadline

    def test_drain_migration_zero_reprefill_token_identical(
            self, model_cfg, ref_engine):
        """Acceptance criterion: drain-with-migration emits ZERO re-prefill
        tokens for migrated sequences (engine total_prefill_tokens is flat
        across the drain) and output is token-identical to an undisturbed
        run."""
        greedy = SamplingParams(temperature=0.0, max_tokens=48)
        ref = [r.generated_tokens
               for r in ref_engine.generate(PROMPTS[:4], greedy)]
        fleet = make_fleet(model_cfg, ref_engine.params, warm=True)
        try:
            reqs, events = self._submit(fleet, PROMPTS[:4], greedy)
            self._wait_decoding(reqs, events)
            pre = sum(rep.engine.total_prefill_tokens
                      for rep in fleet.replicas)
            assert fleet.drain(0)
            self._await_all(fleet, events)
            post = sum(rep.engine.total_prefill_tokens
                       for rep in fleet.replicas)
            assert [r.generated_tokens for r in reqs] == ref
            assert post == pre, (
                f"drain-with-migration re-prefilled: {pre} -> {post}")
            snap = fleet.status()
            assert snap["migration"]["migrations"] >= 1
            assert snap["migration"]["migrated_tokens"] > 0
            assert snap["migration"]["reprefill_tokens_avoided"] > 0
            assert snap["migration"]["by_reason"].get("drain", 0) >= 1
            st = fleet.router.stats()
            assert st["completed"] == 4
            assert st["completed"] + st["failed"] + st["rejected"] \
                == st["submitted"]
        finally:
            fleet.shutdown()

    def test_migration_token_identity_seeded_sampling(
            self, model_cfg, ref_engine):
        """Operator-path migration (fleet.migrate) mid-decode under
        temperature>0 sampling: the restored sequence continues the same
        position-folded PRNG stream on the destination — bit-identical
        output, no re-prefill for the migrated sequence."""
        sampled = SamplingParams(temperature=0.9, top_k=16, max_tokens=48,
                                 seed=4321)
        ref = [r.generated_tokens
               for r in ref_engine.generate([PROMPTS[0]], sampled)]
        fleet = make_fleet(model_cfg, ref_engine.params, warm=True)
        try:
            reqs, events = self._submit(fleet, [PROMPTS[0]], sampled)
            self._wait_decoding(reqs, events, n_tokens=4)
            src = fleet.router.replica_of(reqs[0].request_id)
            dest = 1 - src
            assert fleet.migrate(reqs[0].request_id, dest)
            self._await_all(fleet, events)
            assert reqs[0].generated_tokens == ref[0]
            snap = fleet.status()
            assert snap["migration"]["by_reason"].get("operator", 0) == 1
            # the sequence landed (and finished) on the destination
            assert fleet.router.stats()["migrations"] == 1
        finally:
            fleet.shutdown()

    def test_crash_during_migration_falls_back_to_requeue(
            self, model_cfg, ref_engine):
        """FaultInjector crash racing an in-flight migration: the ticket
        dies with the engine, the victim falls back to plain requeue
        (re-prefill), and the ledger still balances — nothing dropped,
        output still token-identical."""
        greedy = SamplingParams(temperature=0.0, max_tokens=24)
        ref = [r.generated_tokens
               for r in ref_engine.generate(PROMPTS, greedy)]
        plan = FaultPlan(crash_replica=0, crash_after_steps=4)
        fleet = make_fleet(model_cfg, ref_engine.params, plan=plan,
                           warm=True)
        try:
            reqs, events = self._submit(fleet, PROMPTS, greedy)
            self._wait_decoding(reqs, events, n_tokens=1, mode=any)
            # start a migration off replica 0 just before its planned
            # crash; whether the crash lands between the copy phases or
            # just after, every invariant below must hold
            for req in reqs:
                if fleet.router.replica_of(req.request_id) == 0 \
                        and not req.generated_tokens:
                    continue
                if fleet.router.replica_of(req.request_id) == 0:
                    fleet.replicas[0].request_migrate(req.request_id,
                                                      dest=1)
                    break
            self._await_all(fleet, events)
            st = fleet.router.stats()
            assert [r.generated_tokens for r in reqs] == ref
            assert st["completed"] == len(PROMPTS)
            assert st["completed"] + st["failed"] + st["rejected"] \
                == st["submitted"]
            assert st["in_flight"] == 0
            assert fleet.replicas[0].migrations_in_flight() == 0
        finally:
            fleet.shutdown()

    def test_two_phase_pause_bounded_with_straggler_source(
            self, model_cfg, ref_engine):
        """The stop-and-copy pause covers only the pages written since the
        pre-copy — asserted structurally on a straggler-injected source
        (slow decode must not widen the stop phase, which is the point of
        pre-copying while the source keeps decoding)."""
        greedy = SamplingParams(temperature=0.0, max_tokens=64)
        ref = [r.generated_tokens
               for r in ref_engine.generate([PROMPTS[0]], greedy)]
        plan = FaultPlan(slow_replica=0, slow_ms=20.0)
        fleet = make_fleet(model_cfg, ref_engine.params, plan=plan,
                           warm=True)
        try:
            reqs, events = self._submit(fleet, [PROMPTS[0]], greedy)
            # replica 0 is the least-loaded tiebreak winner -> our victim
            assert fleet.router.replica_of(reqs[0].request_id) == 0
            self._wait_decoding(reqs, events, n_tokens=18)
            assert fleet.replicas[0].request_migrate(
                reqs[0].request_id, dest=1, reason="rebalance")
            self._await_all(fleet, events)
            assert reqs[0].generated_tokens == ref[0]
            log = list(fleet.replicas[0].migration_log)
            assert len(log) == 1, log
            d = log[0]
            # >=18 tokens decoded before the ticket -> >=2 full pages
            # (page_size 8) pre-copied while decode kept running
            assert d["precopy_pages"] >= 2, d
            # the stop phase copied strictly less than the whole sequence:
            # only the tail written since the pre-copy (bounded by one
            # decode dispatch + the partial page, NOT by context length)
            grown = d["positions_stop"] - d["positions_precopy"]
            ps = fleet.replicas[0].engine.kv.page_size
            assert d["stop_pages"] < d["total_pages"], d
            assert d["stop_pages"] <= grown // ps + 2, d
            assert d["pause_ms"] > 0
        finally:
            fleet.shutdown()

    def test_drain_migration_int8_kv_pages(self, model_cfg, ref_engine):
        """Quantized pages migrate too: the QuantPages {values, scale}
        payload splits/merges across the two copy phases and restores on
        the destination bit-identically."""
        from distributed_llm_training_and_inference_system_tpu.serve import (
            InferenceEngine)
        greedy = SamplingParams(temperature=0.0, max_tokens=64)
        q8_ref = InferenceEngine(model_cfg,
                                 serve_cfg(kv_quantization="int8"),
                                 params=ref_engine.params, seed=0)
        ref = [r.generated_tokens
               for r in q8_ref.generate([PROMPTS[0]], greedy)]
        fleet = make_fleet(model_cfg, ref_engine.params, warm=True,
                           serve_kw={"kv_quantization": "int8"})
        try:
            reqs, events = self._submit(fleet, [PROMPTS[0]], greedy)
            self._wait_decoding(reqs, events, n_tokens=4)
            src = fleet.router.replica_of(reqs[0].request_id)
            assert fleet.drain(src)
            self._await_all(fleet, events)
            assert reqs[0].generated_tokens == ref[0]
            logs = [d for r in fleet.replicas for d in r.migration_log]
            assert len(logs) == 1 and logs[0]["precopy_pages"] >= 1, logs
        finally:
            fleet.shutdown()

    def test_orphan_requeue_keeps_prompt_prefix_hashes(
            self, model_cfg, ref_engine):
        """Satellite: a crash orphan that never decoded keeps its prompt
        hashes through reset_for_requeue, so a survivor holding the prefix
        serves it from cache (counted in reprefill_tokens_avoided via the
        engine's requeue-cached counter)."""
        from distributed_llm_training_and_inference_system_tpu.serve.fleet import (  # noqa: E501
            reset_for_requeue)
        from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (  # noqa: E501
            Request)
        req = Request(request_id="r1", prompt_tokens=list(range(40)),
                      sampling=SamplingParams(max_tokens=4))
        req.prefix_hashes = [b"a", b"b"]
        reset_for_requeue(req)
        assert req.prefix_hashes == [b"a", b"b"]   # content, not replica
        assert req.fleet_requeued
        # once tokens were generated the hashed chain no longer covers the
        # resume context -> rehashed at admission on the survivor
        req.generated_tokens = [1, 2]
        reset_for_requeue(req)
        assert req.prefix_hashes is None
        # keep_kv carries a migration payload; default drops it
        req.swapped_kv = {"pages": {}}
        reset_for_requeue(req, keep_kv=True)
        assert req.swapped_kv is not None
        reset_for_requeue(req)
        assert req.swapped_kv is None


class TestCourierChaos:
    """Engine-backed courier chaos (this PR's acceptance bar): under
    seeded chunk drop + corruption + delay faults, drain migration and
    handoff complete token-identically with retries counted and nothing
    dropped; a transfer past its retry budget falls back to re-prefill
    with a balanced ledger and an aborts increment."""

    # share TestMigration's submit/await plumbing without inheriting its
    # test methods (they must not run twice)
    _submit = TestMigration._submit
    _await_all = TestMigration._await_all
    _wait_decoding = TestMigration._wait_decoding
    _drain_at_token = TestMigration._drain_at_token

    CHAOS_KW = dict(courier_chunk_bytes=1024, courier_max_retries=12,
                    courier_retry_backoff_ms=0.2,
                    courier_retry_backoff_max_ms=2.0,
                    courier_chunk_deadline_ms=20.0)
    CHAOS_PLAN = dict(seed=5, chunk_drop_rate=0.2, chunk_corrupt_rate=0.15,
                      chunk_delay_rate=0.1, chunk_delay_ms=30.0,
                      chunk_duplicate_rate=0.1)

    def test_drain_migration_under_chunk_chaos_greedy(
            self, model_cfg, ref_engine):
        """Drop+corrupt+delay+duplicate on every payload's chunks: the
        drain migration still lands with ZERO re-prefill (transfers all
        eventually verify end-to-end), token-identical, retries and
        corruptions counted, no aborts."""
        greedy = SamplingParams(temperature=0.0, max_tokens=48)
        ref = [r.generated_tokens
               for r in ref_engine.generate(PROMPTS[:4], greedy)]
        fleet = make_fleet(model_cfg, ref_engine.params, warm=True,
                           plan=FaultPlan(**self.CHAOS_PLAN),
                           fleet_kw=dict(self.CHAOS_KW))
        try:
            reqs, events = self._submit(fleet, PROMPTS[:4], greedy)
            self._wait_decoding(reqs, events)
            pre = sum(rep.engine.total_prefill_tokens
                      for rep in fleet.replicas)
            assert fleet.drain(0)
            self._await_all(fleet, events)
            post = sum(rep.engine.total_prefill_tokens
                       for rep in fleet.replicas)
            assert [r.generated_tokens for r in reqs] == ref
            assert post == pre, (
                f"chaos courier re-prefilled: {pre} -> {post}")
            cour = fleet.status()["courier"]
            assert cour["transfers"] >= 1
            assert cour["retries"] >= 1, cour
            assert cour["aborts"] == 0, cour
            st = fleet.router.stats()
            assert st["completed"] == 4
            assert st["completed"] + st["failed"] + st["rejected"] \
                == st["submitted"]
        finally:
            fleet.shutdown()

    def test_drain_migration_under_chaos_seeded_sampling(
            self, model_cfg, ref_engine):
        """Same chaos, temperature>0 with an explicit seed: the payload
        that crossed a lossy link still resumes the exact PRNG stream."""
        sampled = SamplingParams(temperature=0.9, top_k=16, max_tokens=32,
                                 seed=97)
        ref = [r.generated_tokens
               for r in ref_engine.generate([PROMPTS[0]], sampled)]
        fleet = make_fleet(model_cfg, ref_engine.params, warm=True,
                           plan=FaultPlan(**self.CHAOS_PLAN),
                           fleet_kw=dict(self.CHAOS_KW))
        try:
            reqs, events = self._submit(fleet, [PROMPTS[0]], sampled)
            self._wait_decoding(reqs, events, n_tokens=4)
            src = fleet.router.replica_of(reqs[0].request_id)
            assert fleet.drain(src)
            self._await_all(fleet, events)
            assert reqs[0].generated_tokens == ref[0]
            assert fleet.status()["courier"]["aborts"] == 0
        finally:
            fleet.shutdown()

    def test_int8_kv_chaos_token_identity(self, model_cfg, ref_engine):
        """Quantized {values, scale} payloads cross the lossy link too —
        byte-for-byte, so int8-KV decode stays bit-identical."""
        from distributed_llm_training_and_inference_system_tpu.serve import (
            InferenceEngine)
        greedy = SamplingParams(temperature=0.0, max_tokens=48)
        q8_ref = InferenceEngine(model_cfg,
                                 serve_cfg(kv_quantization="int8"),
                                 params=ref_engine.params, seed=0)
        ref = [r.generated_tokens
               for r in q8_ref.generate([PROMPTS[0]], greedy)]
        fleet = make_fleet(model_cfg, ref_engine.params, warm=True,
                           plan=FaultPlan(**self.CHAOS_PLAN),
                           serve_kw={"kv_quantization": "int8"},
                           fleet_kw=dict(self.CHAOS_KW))
        try:
            asked, home = self._drain_at_token(fleet, n_tokens=4)
            reqs, events = self._submit(fleet, [PROMPTS[0]], greedy)
            assert asked.wait(120) and fleet.drain(home[0])
            self._await_all(fleet, events)
            assert reqs[0].generated_tokens == ref[0]
            cour = fleet.status()["courier"]
            assert cour["transfers"] >= 1 and cour["aborts"] == 0
        finally:
            fleet.shutdown()

    def test_abort_falls_back_to_reprefill_balanced_ledger(
            self, model_cfg, ref_engine):
        """100% chunk loss with a tiny retry budget: every transfer
        aborts, the payload is dropped, and the sequence re-prefills on
        the destination — token-identical output, aborts counted, ledger
        balanced, nothing stuck."""
        greedy = SamplingParams(temperature=0.0, max_tokens=64)
        ref = [r.generated_tokens
               for r in ref_engine.generate(PROMPTS[:4], greedy)]
        fleet = make_fleet(
            model_cfg, ref_engine.params, warm=True,
            plan=FaultPlan(seed=2, chunk_drop_rate=1.0),
            fleet_kw=dict(courier_chunk_bytes=1024,
                          courier_max_retries=1,
                          courier_retry_backoff_ms=0.2,
                          courier_retry_backoff_max_ms=1.0,
                          courier_chunk_deadline_ms=20.0))
        try:
            reqs, events = self._submit(fleet, PROMPTS[:4], greedy)
            self._wait_decoding(reqs, events)
            pre = sum(rep.engine.total_prefill_tokens
                      for rep in fleet.replicas)
            # drain a replica that actually HOLDS residents (placement
            # is load-driven; a fixed id could be empty on a fast run)
            src = next(r.replica_id for r in fleet.replicas
                       if r.resident_requests())
            assert fleet.drain(src)
            self._await_all(fleet, events)
            post = sum(rep.engine.total_prefill_tokens
                       for rep in fleet.replicas)
            assert [r.generated_tokens for r in reqs] == ref
            cour = fleet.status()["courier"]
            assert cour["aborts"] >= 1, cour
            assert cour["transfers"] == 0, cour
            # the drained sequences DID re-prefill: the degradation is
            # real, not a silent success
            assert post > pre
            st = fleet.router.stats()
            assert st["completed"] == 4 and st["failed"] == 0
            assert st["completed"] + st["failed"] + st["rejected"] \
                == st["submitted"]
            assert st["in_flight"] == 0
        finally:
            fleet.shutdown()

    def test_disagg_handoff_under_chunk_chaos(self, model_cfg,
                                              ref_engine):
        """Prefill->decode handoffs ride the same lossy courier: token
        identity and zero decode-side prefill hold under chunk chaos."""
        greedy = SamplingParams(temperature=0.0, max_tokens=20)
        ref = [r.generated_tokens
               for r in ref_engine.generate(PROMPTS[:4], greedy)]
        fleet = make_fleet(
            model_cfg, ref_engine.params, warm=True,
            plan=FaultPlan(**self.CHAOS_PLAN),
            fleet_kw=dict(self.CHAOS_KW, roles="prefill,decode"))
        for rep in fleet.replicas:
            rep.engine.total_prefill_tokens = 0      # warmup prefilled
            rep.engine.total_unexpected_prefills = 0
        try:
            reqs, events = self._submit(fleet, PROMPTS[:4], greedy)
            self._await_all(fleet, events)
            assert [r.generated_tokens for r in reqs] == ref
            snap = fleet.status()
            assert snap["handoff"]["handoffs"] == 4
            assert snap["courier"]["transfers"] >= 4
            assert snap["courier"]["aborts"] == 0
            assert fleet.replicas[1].engine.total_prefill_tokens == 0
            total = sum(rep.engine.total_prefill_tokens
                        for rep in fleet.replicas)
            assert total == sum(len(p) for p in PROMPTS[:4])
        finally:
            fleet.shutdown()


class TestCourierCompressed:
    """Compressed courier (this PR's tentpole, engine-backed): with
    ``courier_codec="delta-zlib"`` every migration / handoff payload is
    delta-filtered + per-chunk deflated on the wire, under the same
    seeded chunk chaos as TestCourierChaos — token identity, zero
    re-prefill, and the wire/raw ledger must all hold. A codec bug can
    only surface as a counted abort (re-prefill), never wrong bytes —
    these tests prove the good path stays bit-exact."""

    _submit = TestMigration._submit
    _await_all = TestMigration._await_all
    _wait_decoding = TestMigration._wait_decoding
    _drain_at_token = TestMigration._drain_at_token

    COMP_KW = dict(TestCourierChaos.CHAOS_KW, courier_codec="delta-zlib")

    def test_compressed_drain_migration_chaos_greedy(
            self, model_cfg, ref_engine):
        """fp32 payloads under chaos + compression: drain migration
        lands token-identically with zero re-prefill; the corrupt
        fault flips COMPRESSED frame bytes and the frame CRC still
        catches every one (corruptions counted, aborts zero)."""
        greedy = SamplingParams(temperature=0.0, max_tokens=48)
        ref = [r.generated_tokens
               for r in ref_engine.generate(PROMPTS[:4], greedy)]
        fleet = make_fleet(model_cfg, ref_engine.params, warm=True,
                           plan=FaultPlan(**TestCourierChaos.CHAOS_PLAN),
                           fleet_kw=dict(self.COMP_KW))
        try:
            reqs, events = self._submit(fleet, PROMPTS[:4], greedy)
            self._wait_decoding(reqs, events)
            pre = sum(rep.engine.total_prefill_tokens
                      for rep in fleet.replicas)
            assert fleet.drain(0)
            self._await_all(fleet, events)
            post = sum(rep.engine.total_prefill_tokens
                       for rep in fleet.replicas)
            assert [r.generated_tokens for r in reqs] == ref, (
                "compressed drain migration diverged")
            assert post == pre
            cour = fleet.status()["courier"]
            assert cour["transfers"] >= 1 and cour["aborts"] == 0
            assert cour["retries"] >= 1, cour
            assert cour["bytes_wire"] > 0 and cour["bytes_raw"] > 0
            st = fleet.router.stats()
            assert st["completed"] == 4
            assert st["completed"] + st["failed"] + st["rejected"] \
                == st["submitted"]
        finally:
            fleet.shutdown()

    def test_compressed_int8_drain_seeded_chaos(
            self, model_cfg, ref_engine):
        """int8-KV payloads + seeded sampling + chaos + compression:
        bit-identical resume with the wire/raw ledger populated. (The
        >= 2x ratio bar lives in test_courier_transport.py on
        realistically-correlated pages — gpt-test's random-init
        activations are near-incompressible by construction, which is
        itself worth pinning: the codec must never NEED compressibility
        for correctness.)"""
        from distributed_llm_training_and_inference_system_tpu.serve import (
            InferenceEngine)
        sampled = SamplingParams(temperature=0.9, top_k=16,
                                 max_tokens=32, seed=97)
        q8_ref = InferenceEngine(model_cfg,
                                 serve_cfg(kv_quantization="int8"),
                                 params=ref_engine.params, seed=0)
        ref = [r.generated_tokens
               for r in q8_ref.generate([PROMPTS[0]], sampled)]
        fleet = make_fleet(model_cfg, ref_engine.params, warm=True,
                           plan=FaultPlan(**TestCourierChaos.CHAOS_PLAN),
                           serve_kw={"kv_quantization": "int8"},
                           fleet_kw=dict(self.COMP_KW))
        try:
            asked, home = self._drain_at_token(fleet, n_tokens=4)
            reqs, events = self._submit(fleet, [PROMPTS[0]], sampled)
            assert asked.wait(120) and fleet.drain(home[0])
            self._await_all(fleet, events)
            assert reqs[0].generated_tokens == ref[0], (
                "compressed int8 seeded migration diverged")
            cour = fleet.status()["courier"]
            assert cour["aborts"] == 0, cour
            assert cour["bytes_wire"] > 0 and cour["bytes_raw"] > 0, cour
            assert cour["compression_ratio"] > 0.9, cour
        finally:
            fleet.shutdown()

    def test_compressed_disagg_handoff_chaos(self, model_cfg,
                                             ref_engine):
        """Prefill->decode handoffs ride the compressed lossy courier:
        token identity and zero decode-side prefill hold."""
        greedy = SamplingParams(temperature=0.0, max_tokens=20)
        ref = [r.generated_tokens
               for r in ref_engine.generate(PROMPTS[:4], greedy)]
        fleet = make_fleet(
            model_cfg, ref_engine.params, warm=True,
            plan=FaultPlan(**TestCourierChaos.CHAOS_PLAN),
            fleet_kw=dict(self.COMP_KW, roles="prefill,decode"))
        for rep in fleet.replicas:
            rep.engine.total_prefill_tokens = 0      # warmup prefilled
            rep.engine.total_unexpected_prefills = 0
        try:
            reqs, events = self._submit(fleet, PROMPTS[:4], greedy)
            self._await_all(fleet, events)
            assert [r.generated_tokens for r in reqs] == ref, (
                "compressed disagg handoff diverged")
            snap = fleet.status()
            assert snap["handoff"]["handoffs"] == 4
            assert snap["courier"]["transfers"] >= 4
            assert snap["courier"]["aborts"] == 0
            assert fleet.replicas[1].engine.total_prefill_tokens == 0
            total = sum(rep.engine.total_prefill_tokens
                        for rep in fleet.replicas)
            assert total == sum(len(p) for p in PROMPTS[:4])
        finally:
            fleet.shutdown()


class TestRoleAutoDemotion:
    """Satellite (PR-4 known gap): crash-promoted mixed replicas demote
    back to their provisioned role once the crashed class is healthy for
    role_restore_hysteresis consecutive polls."""

    def _sup(self, roles, **cfg_kw):
        from distributed_llm_training_and_inference_system_tpu.serve.fleet.router import (  # noqa: E501
            FleetRouter)
        from distributed_llm_training_and_inference_system_tpu.serve.fleet.supervisor import (  # noqa: E501
            ReplicaSupervisor)
        from test_fleet_disagg import RoleFake
        kw = dict(replicas=len(roles), affinity_prefix_tokens=0,
                  roles=",".join(roles), role_restore_hysteresis=2)
        kw.update(cfg_kw)
        cfg = FleetConfig(**kw)
        reps = [RoleFake(i, role=ro) for i, ro in enumerate(roles)]
        return ReplicaSupervisor(reps, FleetRouter(reps, cfg), cfg), reps

    def test_promote_then_demote_after_hysteresis(self):
        sup, reps = self._sup(["prefill", "decode"])
        reps[0].state = "crashed"           # prefill class gone
        sup.poll_once()
        assert reps[1].role == "mixed"
        assert sup.total_role_promotions == 1
        # crashed class returns: demotion waits out the hysteresis
        reps[0].state = "healthy"
        sup.poll_once()                     # streak 1
        assert reps[1].role == "mixed"
        sup.poll_once()                     # streak 2 = hysteresis
        assert reps[1].role == "decode"     # provisioned role restored
        assert sup.total_role_demotions == 1
        assert sup.snapshot()["handoff"]["demotions"] == 1
        # one-shot: further polls change nothing
        sup.poll_once()
        assert reps[1].role == "decode" and sup.total_role_demotions == 1

    def test_flapping_restart_resets_streak(self):
        sup, reps = self._sup(["prefill", "decode"],
                              role_restore_hysteresis=3)
        reps[0].state = "crashed"
        sup.poll_once()
        assert reps[1].role == "mixed"
        reps[0].state = "healthy"
        sup.poll_once()                     # streak 1
        sup.poll_once()                     # streak 2
        reps[0].state = "crashed"           # flap: class lost again
        sup.poll_once()                     # streak resets
        reps[0].state = "healthy"
        sup.poll_once()
        sup.poll_once()
        assert reps[1].role == "mixed"      # only streak 2 of 3
        sup.poll_once()
        assert reps[1].role == "decode"

    def test_operator_rerole_cancels_pending_demotion(self):
        sup, reps = self._sup(["prefill", "decode"])
        reps[0].state = "crashed"
        sup.poll_once()
        assert reps[1].role == "mixed"
        # the operator takes over: the promotion record is dropped and
        # the supervisor never demotes a role it no longer owns
        sup.set_role(1, "prefill")
        reps[0].state = "healthy"
        for _ in range(4):
            sup.poll_once()
        assert reps[1].role == "prefill"
        assert sup.total_role_demotions == 0

    def test_disabled_hysteresis_keeps_promotion(self):
        sup, reps = self._sup(["prefill", "decode"],
                              role_restore_hysteresis=0)
        reps[0].state = "crashed"
        sup.poll_once()
        assert reps[1].role == "mixed"
        reps[0].state = "healthy"
        for _ in range(5):
            sup.poll_once()
        assert reps[1].role == "mixed"      # PR-4 behavior preserved

    def test_promoted_from_surfaces_in_snapshot(self):
        sup, reps = self._sup(["prefill", "decode"])
        reps[0].state = "crashed"
        sup.poll_once()
        rows = {r["replica"]: r for r in sup.snapshot()["replicas"]}
        assert rows[1]["promoted_from"] == "decode"
        assert rows[0]["promoted_from"] is None


class TestSupervisor:
    def test_probe_timeout_teardown_restart_backoff(
            self, model_cfg, ref_engine):
        plan = FaultPlan(probe_timeout_replica=1, probe_timeout_count=2)
        fleet = make_fleet(
            model_cfg, ref_engine.params, plan=plan,
            fleet_kw={"probe_failures": 2, "restart_backoff_max_s": 1.0})
        try:
            b0 = fleet.supervisor.current_backoff_s(1)
            fleet.supervisor.poll_once()      # miss 1
            fleet.supervisor.poll_once()      # miss 2 -> teardown
            assert fleet.replicas[1].state in ("stopped", "crashed")
            time.sleep(0.1)                   # > restart_backoff_s=0.05
            fleet.supervisor.poll_once()
            assert fleet.replicas[1].state == "healthy"
            assert fleet.replicas[1].restarts == 1
            assert fleet.supervisor.current_backoff_s(1) == min(b0 * 2, 1.0)
            snap = fleet.status()
            assert snap["restarts"] == 1
            assert {r["replica"] for r in snap["replicas"]} == {0, 1}
        finally:
            fleet.shutdown()


class TestFleetLoadgen:
    def test_poisson_per_replica_breakdown_with_crash(
            self, model_cfg, ref_engine):
        from distributed_llm_training_and_inference_system_tpu.serve.loadgen import (  # noqa: E501
            run_poisson)
        plan = FaultPlan(crash_replica=0, crash_after_steps=2)
        fleet = make_fleet(model_cfg, ref_engine.params, plan=plan)
        try:
            res = run_poisson(fleet, offered_rps=30.0, num_requests=10,
                              prompt_len=8, max_tokens=24, seed=0)
            assert res.completed == 10, res.summary()
            assert res.requeues >= 1
            assert set(res.per_replica) == {0, 1}
            assert sum(v["requests"] for v in res.per_replica.values()) \
                == 10
            for v in res.per_replica.values():
                assert {"requests", "p50_ttft_ms", "p99_ttft_ms",
                        "requeues"} <= set(v)
            assert sum(v["requeues"]
                       for v in res.per_replica.values()) == res.requeues
            s = res.summary()
            assert "per_replica" in s and "requeues" in s
        finally:
            fleet.shutdown()

    def test_closed_loop_fleet_completes(self, model_cfg, ref_engine):
        from distributed_llm_training_and_inference_system_tpu.serve.loadgen import (  # noqa: E501
            run_closed_loop)
        fleet = make_fleet(model_cfg, ref_engine.params)
        try:
            res = run_closed_loop(fleet, concurrency=3, num_requests=6,
                                  prompt_len=6, max_tokens=6, seed=1)
            assert res.completed == 6, res.summary()
            assert sum(v["requests"]
                       for v in res.per_replica.values()) == 6
        finally:
            fleet.shutdown()


@pytest.mark.socket
class TestFleetHTTP:
    @pytest.fixture()
    def server(self, model_cfg, ref_engine):
        import asyncio

        from distributed_llm_training_and_inference_system_tpu.serve.fleet.http import (  # noqa: E501
            FleetServer)
        srv = FleetServer(
            model_cfg,
            serve_cfg(host="127.0.0.1", port=0),
            FleetConfig(replicas=2, probe_interval_s=0.05,
                        restart_backoff_s=0.05),
            params=ref_engine.params)
        loop = asyncio.new_event_loop()
        started = threading.Event()
        state = {}

        def run():
            asyncio.set_event_loop(loop)

            async def main():
                runner = await srv.start_async()
                state["port"] = runner.addresses[0][1]
                started.set()

            loop.run_until_complete(main())
            loop.run_forever()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        assert started.wait(timeout=60)
        yield srv, state["port"]
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=5)
        srv.fleet.shutdown()

    def test_endpoints(self, server, ref_engine, model_cfg):
        import requests as rq
        srv, port = server
        base = f"http://127.0.0.1:{port}"

        # completion routed through the fleet == single-engine output
        greedy = SamplingParams(temperature=0.0, max_tokens=6)
        [ref] = ref_engine.generate([PROMPTS[0]], greedy)
        r = rq.post(f"{base}/v1/completions", json={
            "prompt": PROMPTS[0], "max_tokens": 6, "temperature": 0.0,
        }, timeout=120)
        assert r.status_code == 200
        body = r.json()
        assert body["choices"][0]["token_ids"] == ref.generated_tokens
        assert body["metrics"]["replica"] in (0, 1)
        assert body["metrics"]["requeues"] == 0

        # health + status surfaces
        h = rq.get(f"{base}/health", timeout=10).json()
        assert h["status"] == "healthy" and h["replicas_healthy"] == 2
        snap = rq.get(f"{base}/fleet/status", timeout=10).json()
        assert {x["replica"] for x in snap["replicas"]} == {0, 1}
        assert snap["router"]["completed"] >= 1

        # drain/undrain round trip; unknown replica -> 404
        assert rq.post(f"{base}/fleet/drain", json={"replica": 0},
                       timeout=10).json()["ok"]
        deadline = time.monotonic() + 30
        while True:
            states = {x["replica"]: x["state"] for x in rq.get(
                f"{base}/fleet/status", timeout=10).json()["replicas"]}
            if states[0] == "drained":
                break
            time.sleep(0.05)
            assert time.monotonic() < deadline
        assert rq.post(f"{base}/fleet/undrain", json={"replica": 0},
                       timeout=10).json()["ok"]
        assert rq.post(f"{base}/fleet/drain", json={"replica": 9},
                       timeout=10).status_code == 404

        # role surface: set/readback round trip; bad role / unknown
        # replica / bad body refused
        assert rq.post(f"{base}/fleet/role",
                       json={"replica": 1, "role": "decode"},
                       timeout=10).json()["ok"]
        snap = rq.get(f"{base}/fleet/status", timeout=10).json()
        roles = {x["replica"]: x.get("role") for x in snap["replicas"]}
        assert roles[1] == "decode"
        assert rq.post(f"{base}/fleet/role",
                       json={"replica": 1, "role": "mixed"},
                       timeout=10).json()["ok"]
        assert rq.post(f"{base}/fleet/role",
                       json={"replica": 9, "role": "decode"},
                       timeout=10).status_code == 404
        assert rq.post(f"{base}/fleet/role",
                       json={"replica": 1, "role": "driver"},
                       timeout=10).status_code == 400
        assert rq.post(f"{base}/fleet/role", json={"replica": 1},
                       timeout=10).status_code == 400

        # migrate surface: unknown replica / unknown request / bad body
        assert rq.post(f"{base}/fleet/migrate",
                       json={"request_id": "nope", "replica": 9},
                       timeout=10).status_code == 404
        assert rq.post(f"{base}/fleet/migrate",
                       json={"request_id": "nope", "replica": 1},
                       timeout=10).status_code == 404
        assert rq.post(f"{base}/fleet/migrate", json={"replica": 1},
                       timeout=10).status_code == 400

        # contract edges: SSE accepted since PR 8 (delivery contract
        # covered in test_fleet_streams.py), bad body refused
        r_sse = rq.post(f"{base}/v1/completions",
                        json={"prompt": [1, 2], "stream": True,
                              "max_tokens": 4, "temperature": 0.0},
                        stream=True, timeout=240)
        assert r_sse.status_code == 200
        assert r_sse.headers["Content-Type"].startswith(
            "text/event-stream")
        r_sse.close()
        assert rq.post(f"{base}/v1/completions",
                       json={"prompt": [1.5]},
                       timeout=10).status_code == 400

        # courier surface: chunks pushed in over POST reassemble, verify
        # end-to-end, and ATTACH by ticket in the fleet's receiver (the
        # destination-terminated cross-host transport; the old sender-
        # return /fleet/courier/claim loopback is gone)
        import numpy as np
        from distributed_llm_training_and_inference_system_tpu.serve.fleet.transport import (  # noqa: E501
            HTTPCourierTransport, encode_payload, make_chunks)
        payload = {
            "pages": {"k": np.arange(2 * 2 * 2 * 8 * 16, dtype=np.float32)
                      .reshape(2, 2, 2, 8, 16),
                      "v": np.ones((2, 2, 2, 8, 16), np.float32),
                      "num_pages": 2},
            "positions": 13, "last_token": 5,
        }
        manifest, blob = encode_payload(payload)
        chunks = make_chunks("http-t1", manifest, blob, 1024)
        for c in chunks:
            ack = rq.post(f"{base}/fleet/courier/chunk",
                          json=c.to_wire(), timeout=10).json()
            assert ack["ok"]
        assert ack["complete"] and ack["missing"] == []
        # duplicate retransmit is idempotent (even after completion)
        dup = rq.post(f"{base}/fleet/courier/chunk",
                      json=chunks[0].to_wire(), timeout=10).json()
        assert dup["ok"] and dup["duplicate"]
        # the payload attached destination-side, by ticket
        got = srv.fleet.courier_receiver.take_payload("http-t1")
        assert got is not None and got["positions"] == 13
        assert np.array_equal(got["pages"]["k"], payload["pages"]["k"])
        # the claim loopback endpoint no longer exists
        assert rq.post(f"{base}/fleet/courier/claim",
                       json={"ticket": "http-t1"},
                       timeout=10).status_code == 404
        # corrupt chunk -> ok=false ack; malformed frame -> 400
        wire = chunks[0].to_wire()
        wire["crc32"] = wire["crc32"] ^ 1
        bad = rq.post(f"{base}/fleet/courier/chunk", json=wire,
                      timeout=10).json()
        assert bad["ok"] is False
        assert rq.post(f"{base}/fleet/courier/chunk",
                       json={"ticket": "x"}, timeout=10).status_code == 400

        # full HTTPCourierTransport push: transfer() drives the socket
        # endpoint end-to-end and the identical payload attaches by
        # ticket in the destination's receiver
        t = HTTPCourierTransport(endpoint=base)
        ticket = t.transfer(payload, src=0, dest=1)
        out = srv.fleet.courier_receiver.take_payload(ticket)
        assert out["positions"] == 13 and out["last_token"] == 5
        assert np.array_equal(out["pages"]["k"], payload["pages"]["k"])
        assert np.array_equal(out["pages"]["v"], payload["pages"]["v"])
        assert t.stats.snapshot()["transfers"] == 1

        # /fleet/status surfaces the endpoint map + per-replica
        # endpoint/remote columns (satellite)
        snap = rq.get(f"{base}/fleet/status", timeout=10).json()
        assert snap["endpoints"] == {}
        for rep in snap["replicas"]:
            assert rep["endpoint"] == "local"
            assert rep["remote"] is False


class TestFleetMetrics:
    def test_prometheus_gauge_names_and_labels(self):
        """Satellite: per-replica fleet metrics exist under their
        documented names with the replica label (operators alarm on these
        — a silent rename would break dashboards)."""
        prometheus_client = pytest.importorskip("prometheus_client")
        from distributed_llm_training_and_inference_system_tpu.metrics.observability import (  # noqa: E501
            PrometheusExporter)
        try:
            exporter = PrometheusExporter(port=0)
        except ValueError:
            pytest.skip("prometheus registry already populated "
                        "(another exporter instance in this process)")
        snap = {
            "replicas": [
                {"replica": 0, "state": "healthy", "queue_depth": 3,
                 "active": 2, "outstanding_tokens": 170, "restarts": 1,
                 "prefix_hit_rate": 0.75, "role": "prefill"},
                {"replica": 1, "state": "crashed", "queue_depth": 0,
                 "active": 0, "outstanding_tokens": 0, "restarts": 0,
                 "prefix_hit_rate": 0.0, "role": "decode"},
            ],
            "router": {"requeues": 5, "rejected": 2},
            "migration": {"migrations": 2, "migrated_tokens": 300,
                          "reprefill_tokens_avoided": 123,
                          "pauses_ms": [1.5, 3.5], "pause_count": 2},
            "handoff": {"handoffs": 3, "handoff_tokens": 96,
                        "local_fallbacks": 1,
                        "stalls_ms": [2.0, 4.0, 6.0], "stall_count": 3},
            "courier": {"chunks": 40, "retries": 6, "corruptions": 2,
                        "duplicates": 1, "resumes": 3, "aborts": 1,
                        "expired": 2,
                        "transfers": 4, "bytes_moved": 4096,
                        "bytes_wire": 1024, "bytes_raw": 4096,
                        "compression_ratio": 4.0,
                        "in_flight": 0,
                        "transfer_ms": [1.0, 2.0, 3.0, 4.0],
                        "transfer_count": 4},
            "prefix_fetch": {"fetches": 2, "pages": 8, "bytes": 2048,
                             "misses": 1, "aborts": 1,
                             "fetch_ms": [2.0, 3.0, 4.0, 5.0],
                             "fetch_count": 4},
            "spec": {"dispatches": 10, "drafts": 70, "accepted": 35,
                     "resumes": 2, "acceptance": 0.5},
            "streams": {"active": 1, "tokens": 11, "duplicates": 1,
                        "replayed": 3, "reconnects": 1,
                        "gaps_healed": 2, "backpressure_drops": 1,
                        "orphan_logs_gc": 1, "front_resumes": 1,
                        "replay_sizes": [3], "replay_count": 1},
            "front_tier": {
                "fronts": {
                    "front-0": {"alive": True, "active_streams": 2,
                                "port": 8080},
                    "front-1": {"alive": False, "fenced": True,
                                "active_streams": 0, "port": 8081}},
                "front_id": "front-0", "failovers": 1,
                "reconnects": 1},
        }
        exporter.export_fleet(snap)
        samples = {}
        front_samples = {}
        for metric in prometheus_client.REGISTRY.collect():
            for s in metric.samples:
                if "front" in s.labels:
                    front_samples[(s.name, s.labels["front"])] = s.value
                else:
                    samples[(s.name, s.labels.get("replica"))] = s.value
        assert samples[("llmctl_fleet_replica_queue_depth", "0")] == 3
        assert samples[("llmctl_fleet_replica_outstanding_tokens", "0")] \
            == 170
        assert samples[("llmctl_fleet_replica_active", "0")] == 2
        assert samples[("llmctl_fleet_replica_healthy", "0")] == 1.0
        assert samples[("llmctl_fleet_replica_healthy", "1")] == 0.0
        assert samples[("llmctl_fleet_replica_restarts_total", "0")] == 1
        assert samples[("llmctl_fleet_requeues_total", None)] == 5
        assert samples[("llmctl_fleet_rejected_total", None)] == 2
        # KV-migration plane (this PR): counters, the pause histogram,
        # and the per-replica prefix-hit-rate gauge
        assert samples[("llmctl_fleet_migrations_total", None)] == 2
        assert samples[("llmctl_fleet_migrated_tokens_total", None)] == 300
        assert samples[
            ("llmctl_fleet_reprefill_tokens_avoided_total", None)] == 123
        assert samples[
            ("llmctl_fleet_migration_pause_ms_count", None)] == 2
        assert samples[("llmctl_fleet_migration_pause_ms_sum", None)] \
            == pytest.approx(5.0)
        assert samples[("llmctl_fleet_replica_prefix_hit_rate", "0")] \
            == 0.75
        # disaggregation plane (this PR): the prefill->decode handoff
        # counter, the per-handoff stall histogram, and the per-replica
        # role gauge (0=mixed, 1=prefill, 2=decode)
        assert samples[("llmctl_fleet_handoffs_total", None)] == 3
        assert samples[("llmctl_fleet_handoff_stall_ms_count", None)] == 3
        assert samples[("llmctl_fleet_handoff_stall_ms_sum", None)] \
            == pytest.approx(12.0)
        assert samples[("llmctl_fleet_replica_role", "0")] == 1
        assert samples[("llmctl_fleet_replica_role", "1")] == 2
        # courier transport plane (this PR): chunk/retry/corruption/
        # resume/abort counters + the end-to-end transfer histogram
        assert samples[("llmctl_fleet_courier_chunks_total", None)] == 40
        assert samples[("llmctl_fleet_courier_retries_total", None)] == 6
        assert samples[
            ("llmctl_fleet_courier_corruptions_total", None)] == 2
        assert samples[("llmctl_fleet_courier_resumes_total", None)] == 3
        assert samples[("llmctl_fleet_courier_aborts_total", None)] == 1
        assert samples[("llmctl_fleet_courier_expired_total", None)] == 2
        # wire codec plane (this PR): bytes on the wire vs the raw
        # payload bytes they covered — the compression-ratio ledger
        assert samples[
            ("llmctl_fleet_courier_wire_bytes_total", None)] == 1024
        assert samples[
            ("llmctl_fleet_courier_raw_bytes_total", None)] == 4096
        assert samples[
            ("llmctl_fleet_courier_transfer_ms_count", None)] == 4
        assert samples[("llmctl_fleet_courier_transfer_ms_sum", None)] \
            == pytest.approx(10.0)
        # fleet-global prefix-fetch plane (this PR): fetched pages/bytes
        # + degrade counters and the fetch-latency histogram
        assert samples[
            ("llmctl_fleet_prefix_fetch_pages_total", None)] == 8
        assert samples[
            ("llmctl_fleet_prefix_fetch_bytes_total", None)] == 2048
        assert samples[
            ("llmctl_fleet_prefix_fetch_misses_total", None)] == 1
        assert samples[
            ("llmctl_fleet_prefix_fetch_aborts_total", None)] == 1
        assert samples[
            ("llmctl_fleet_prefix_fetch_ms_count", None)] == 4
        assert samples[("llmctl_fleet_prefix_fetch_ms_sum", None)] \
            == pytest.approx(14.0)
        # speculative-decode plane (round 14): fleet-wide acceptance
        # counters + migrated-SpecState resumes (courier-aware spec)
        assert samples[("llmctl_fleet_spec_dispatches_total", None)] == 10
        assert samples[("llmctl_fleet_spec_drafts_total", None)] == 70
        assert samples[("llmctl_fleet_spec_accepted_total", None)] == 35
        assert samples[("llmctl_fleet_spec_resumes_total", None)] == 2
        # stream plane + HA front tier (round 17): the orphan-log GC
        # counter, failover resume counter, tier failovers, and the
        # per-front liveness/load gauges
        assert samples[("llmctl_fleet_stream_tokens_total", None)] == 11
        assert samples[
            ("llmctl_fleet_stream_orphan_gcs_total", None)] == 1
        assert samples[
            ("llmctl_fleet_front_reconnects_total", None)] == 1
        assert samples[
            ("llmctl_fleet_front_failovers_total", None)] == 1
        assert front_samples[("llmctl_fleet_front_up", "front-0")] == 1.0
        assert front_samples[("llmctl_fleet_front_up", "front-1")] == 0.0
        assert front_samples[
            ("llmctl_fleet_front_active_streams", "front-0")] == 2
        # counters export deltas: a second identical snapshot must not
        # double-count the running totals (incl. the pause histogram)
        exporter.export_fleet(snap)
        for metric in prometheus_client.REGISTRY.collect():
            for s in metric.samples:
                if s.name in ("llmctl_fleet_requeues_total",
                              "llmctl_fleet_migrations_total",
                              "llmctl_fleet_handoffs_total",
                              "llmctl_fleet_courier_retries_total",
                              "llmctl_fleet_courier_aborts_total",
                              "llmctl_fleet_spec_drafts_total"):
                    assert s.value == {
                        "llmctl_fleet_requeues_total": 5,
                        "llmctl_fleet_migrations_total": 2,
                        "llmctl_fleet_handoffs_total": 3,
                        "llmctl_fleet_courier_retries_total": 6,
                        "llmctl_fleet_courier_aborts_total": 1,
                        "llmctl_fleet_spec_drafts_total": 70}[s.name]
                if s.name in ("llmctl_fleet_migration_pause_ms_count",
                              "llmctl_fleet_handoff_stall_ms_count"):
                    assert s.value == {
                        "llmctl_fleet_migration_pause_ms_count": 2,
                        "llmctl_fleet_handoff_stall_ms_count": 3}[s.name]
        # registry cross-check (graftlint counter-wiring satellite): the
        # literal names pinned above AND the scrape output must both
        # agree with metrics/names.py — the ONE source of truth the
        # exporter constructs from and the lint pass verifies. A fleet
        # metric added off-registry, or a registry entry that stops
        # being scraped, fails here.
        from distributed_llm_training_and_inference_system_tpu.metrics import (  # noqa: E501
            names as metric_names)
        observed = set()
        for metric in prometheus_client.REGISTRY.collect():
            for s in metric.samples:
                if s.name.startswith("llmctl_fleet"):
                    observed.add(s.name)
        expected = set()
        for n in metric_names.fleet_metric_names():
            spec = metric_names.METRICS[n]
            if spec.kind == metric_names.HISTOGRAM:
                expected |= {f"{n}_count", f"{n}_sum", f"{n}_bucket"}
            else:
                expected.add(metric_names.scraped_name(n))
        missing = expected - observed
        assert not missing, f"registered but not scraped: {missing}"
        allowed = expected | {
            metric_names.scraped_name(n).replace("_total", "")
            + "_created"
            for n in metric_names.fleet_metric_names()
            if metric_names.METRICS[n].kind != metric_names.GAUGE}
        stray = observed - allowed
        assert not stray, f"scraped but off-registry: {stray}"
