"""Observability: metrics collection + Prometheus export, WIRED IN.

Parity: reference metrics/observability.py (MetricsCollector :63,
PrometheusExporter :230, ObservabilityManager :331; the reference's span
exporter is not rebuilt: spans are ``metrics/spans.py``, on the profiler's
clock) — with the crucial difference that the reference never connects any
of it to the engine/server (SURVEY §5.5: "nothing in engine/server feeds
the collector"). Here runtime/engine.py and serve/server.py call
``engine_observer()`` / ``record_inference`` on every step.

TPU specifics: device memory comes from jax device.memory_stats() (HBM
bytes in use/limit) instead of torch.cuda; MFU/tokens-per-sec-per-chip are
first-class gauges (the BASELINE.json metrics).
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

logger = logging.getLogger("llmctl.metrics")


@dataclass
class SystemSample:
    timestamp: float
    cpu_percent: float
    mem_percent: float
    mem_used_gb: float
    net_sent_mbps: float
    net_recv_mbps: float
    disk_read_mbps: float
    disk_write_mbps: float
    hbm_used_gb: dict[int, float] = field(default_factory=dict)
    hbm_limit_gb: dict[int, float] = field(default_factory=dict)


class MetricsCollector:
    """Background sampler: psutil system stats + per-device HBM, 1s cadence,
    bounded history (reference MetricsCollector observability.py:63-228)."""

    def __init__(self, interval: float = 1.0, history: int = 1000):
        self.interval = interval
        self.history: collections.deque[SystemSample] = collections.deque(
            maxlen=history)
        self.training: collections.deque[dict] = collections.deque(maxlen=history)
        self.inference: collections.deque[dict] = collections.deque(maxlen=history)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last_net = None
        self._last_disk = None

    def sample_once(self) -> SystemSample:
        import psutil
        now = time.time()
        net = psutil.net_io_counters()
        disk = psutil.disk_io_counters()
        net_sent = net_recv = disk_r = disk_w = 0.0
        if self._last_net is not None:
            t0, n0 = self._last_net
            dt = max(now - t0, 1e-3)
            net_sent = (net.bytes_sent - n0.bytes_sent) / dt / 1e6 * 8
            net_recv = (net.bytes_recv - n0.bytes_recv) / dt / 1e6 * 8
        if disk is not None and self._last_disk is not None:
            t0, d0 = self._last_disk
            dt = max(now - t0, 1e-3)
            disk_r = (disk.read_bytes - d0.read_bytes) / dt / 1e6
            disk_w = (disk.write_bytes - d0.write_bytes) / dt / 1e6
        self._last_net = (now, net)
        if disk is not None:
            self._last_disk = (now, disk)

        hbm_used, hbm_limit = {}, {}
        try:
            import jax
            for i, dev in enumerate(jax.local_devices()):
                stats = dev.memory_stats() or {}
                if "bytes_in_use" in stats:
                    hbm_used[i] = stats["bytes_in_use"] / 1e9
                if "bytes_limit" in stats:
                    hbm_limit[i] = stats["bytes_limit"] / 1e9
        except Exception:  # device backend may not expose stats (CPU)
            pass

        vm = psutil.virtual_memory()
        sample = SystemSample(
            timestamp=now, cpu_percent=psutil.cpu_percent(interval=None),
            mem_percent=vm.percent, mem_used_gb=vm.used / 1e9,
            net_sent_mbps=net_sent, net_recv_mbps=net_recv,
            disk_read_mbps=disk_r, disk_write_mbps=disk_w,
            hbm_used_gb=hbm_used, hbm_limit_gb=hbm_limit)
        self.history.append(sample)
        return sample

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()

        def loop():
            while not self._stop.wait(self.interval):
                try:
                    self.sample_once()
                except Exception as e:  # keep the sampler alive
                    logger.debug("metrics sample failed: %s", e)

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="llmctl-metrics")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None

    def record_training(self, payload: dict) -> None:
        self.training.append({"timestamp": time.time(), **payload})

    def record_inference(self, payload: dict) -> None:
        self.inference.append({"timestamp": time.time(), **payload})

    def summary(self) -> dict:
        out: dict[str, Any] = {}
        if self.history:
            s = self.history[-1]
            out["system"] = {
                "cpu_percent": s.cpu_percent, "mem_percent": s.mem_percent,
                "hbm_used_gb": s.hbm_used_gb, "hbm_limit_gb": s.hbm_limit_gb,
            }
        if self.training:
            out["training"] = dict(self.training[-1])
        if self.inference:
            recent = list(self.inference)[-100:]
            lat = sorted(r.get("latency_ms", 0.0) for r in recent)
            out["inference"] = {
                "requests": len(recent),
                "p50_latency_ms": lat[len(lat) // 2] if lat else 0.0,
                "p99_latency_ms": lat[int(len(lat) * 0.99)] if lat else 0.0,
            }
        return out


class _QueueWaitCollector:
    """A histogram the program has already counted (``QueueWaitHistogram``
    .snapshot(): bounds in ms, counts by bucket, sum, n), as a Prometheus
    histogram in seconds."""

    def __init__(self, name: str):
        from prometheus_client import REGISTRY

        from .names import METRICS
        self.name, self.help = name, METRICS[name].help
        self.snap = {"le": ["+inf"], "counts": [0], "sum": 0.0}
        REGISTRY.register(self)

    def collect(self):
        from prometheus_client.core import HistogramMetricFamily
        from prometheus_client.utils import floatToGoString
        family = HistogramMetricFamily(self.name, self.help)
        running, buckets = 0, []
        for le, count in zip(self.snap["le"], self.snap["counts"]):
            running += count
            buckets.append((floatToGoString(
                float("inf") if le == "+inf" else le / 1e3), running))
        family.add_metric([], buckets, self.snap["sum"] / 1e3)
        yield family


class PrometheusExporter:
    """llmctl_* gauges/counters/histograms on a scrape port (reference
    PrometheusExporter observability.py:230-274)."""

    def __init__(self, port: int = 9100):
        from prometheus_client import (Counter, Gauge, Histogram,
                                       start_http_server)

        from .names import COUNTER, GAUGE, HISTOGRAM, METRICS
        self.port = port
        self._start_http_server = start_http_server
        classes = {GAUGE: Gauge, COUNTER: Counter, HISTOGRAM: Histogram}

        def mk(name: str):
            # every metric is DECLARED in metrics/names.py (kind, help,
            # labels, buckets) and CONSTRUCTED here by name — graftlint's
            # counter-wiring pass cross-checks both directions, so a
            # registry entry without a constructor line (or vice versa)
            # fails lint instead of silently dropping a scrape series
            spec = METRICS[name]
            kwargs = {"labelnames": list(spec.labels)}
            if spec.buckets is not None:
                kwargs["buckets"] = spec.buckets
            return classes[spec.kind](name, spec.help, **kwargs)

        self.train_loss = mk("llmctl_train_loss")
        self.train_mfu = mk("llmctl_train_mfu")
        self.tokens_per_sec = mk("llmctl_train_tokens_per_sec")
        self.tokens_per_sec_chip = mk("llmctl_train_tokens_per_sec_per_chip")
        self.grad_norm = mk("llmctl_train_grad_norm")
        self.lr = mk("llmctl_train_lr")
        self.steps = mk("llmctl_train_step")
        self.eval_loss = mk("llmctl_eval_loss")
        self.hbm_used = mk("llmctl_hbm_used_gb")
        self.cpu = mk("llmctl_cpu_percent")
        self.mem = mk("llmctl_mem_percent")
        self.infer_requests = mk("llmctl_inference_requests_total")
        self.infer_latency = mk("llmctl_inference_latency_seconds")
        self.infer_ttft = mk("llmctl_inference_ttft_seconds")
        self.infer_queue = mk("llmctl_inference_queue_depth")
        # the scheduler's own histogram (engine.stats()["queue_wait_ms"]:
        # admit time - arrival, once an admission), handed to the scrape as
        # it stands, so that /v1/stats and Prometheus show ONE distribution
        self.infer_queue_wait = _QueueWaitCollector(
            "llmctl_inference_queue_wait_seconds")
        # the engine thread's self time by llmctl.engine.* span
        # (metrics/spans.py), from the running totals of engine.stats()
        self.engine_phase_seconds = mk("llmctl_engine_phase_seconds_total")
        # seconds a slot held a request and nothing was in flight, by the
        # span the engine thread was in (engine.stats()["starved_by_phase"])
        self.starved_seconds = mk("llmctl_starved_seconds_total")
        # what became of the decode steps' slots
        # (engine.stats()["slot_steps"]: useful, overrun, prompt_wait, empty)
        self.slot_steps = mk("llmctl_slot_steps_total")
        # the process's start-up (engine.stats()["startup"]["phases"])
        self.startup_phase_seconds = mk("llmctl_startup_phase_seconds")
        # an MoE model's routing (engine.stats()["moe"]["choices"])
        self.moe_expert_choices = mk("llmctl_moe_expert_choices_total")
        self.decode_tokens_per_sec = mk("llmctl_decode_tokens_per_sec")
        # on-demand admission telemetry (round 3): preemption pressure and
        # swap-in counts are the KV-capacity health signals. Cumulative
        # counts are COUNTERS (prometheus appends _total; rate() works);
        # the engine reports running totals, so export_inference incs the
        # delta since the last report
        self.infer_preemptions = mk("llmctl_inference_preemptions")
        self.infer_swap_ins = mk("llmctl_inference_swap_ins")
        self.infer_ride_tokens = mk("llmctl_inference_prefill_ride_tokens")
        self.infer_carry_tokens = mk("llmctl_inference_state_carry_tokens")
        # self-drafting (engine.stats()["mtp_*"])
        self.infer_mtp = {
            "mtp_drafts": mk("llmctl_inference_mtp_drafts"),
            "mtp_accepted": mk("llmctl_inference_mtp_accepted"),
            "mtp_slot_steps": mk("llmctl_inference_mtp_slot_steps"),
            "mtp_tokens": mk("llmctl_inference_mtp_tokens")}
        self.infer_swapped_bytes = mk("llmctl_inference_swapped_host_bytes")
        # serve-fleet control plane (serve/fleet/): per-replica health the
        # operator alarms on. Queue depth + outstanding tokens are the
        # routing signals themselves; restarts/requeues/rejections are the
        # failure-path counters the fault-injection tests exercise.
        self.fleet_queue_depth = mk("llmctl_fleet_replica_queue_depth")
        self.fleet_outstanding = mk(
            "llmctl_fleet_replica_outstanding_tokens")
        self.fleet_active = mk("llmctl_fleet_replica_active")
        self.fleet_healthy = mk("llmctl_fleet_replica_healthy")
        self.fleet_restarts = mk("llmctl_fleet_replica_restarts")
        self.fleet_requeues = mk("llmctl_fleet_requeues")
        self.fleet_rejected = mk("llmctl_fleet_rejected")
        # KV migration plane (serve/fleet/migration.py): how much work
        # moved between replicas and what it saved vs re-prefill
        self.fleet_migrations = mk("llmctl_fleet_migrations")
        self.fleet_migrated_tokens = mk("llmctl_fleet_migrated_tokens")
        self.fleet_reprefill_avoided = mk(
            "llmctl_fleet_reprefill_tokens_avoided")
        self.fleet_migration_pause = mk("llmctl_fleet_migration_pause_ms")
        self.fleet_prefix_hit_rate = mk(
            "llmctl_fleet_replica_prefix_hit_rate")
        # disaggregated prefill/decode plane (serve/fleet/ roles)
        self.fleet_handoffs = mk("llmctl_fleet_handoffs")
        self.fleet_handoff_stall = mk("llmctl_fleet_handoff_stall_ms")
        self.fleet_replica_role = mk("llmctl_fleet_replica_role")
        # courier transport plane (serve/fleet/transport.py)
        self.fleet_courier_chunks = mk("llmctl_fleet_courier_chunks")
        self.fleet_courier_retries = mk("llmctl_fleet_courier_retries")
        self.fleet_courier_corruptions = mk(
            "llmctl_fleet_courier_corruptions")
        self.fleet_courier_resumes = mk("llmctl_fleet_courier_resumes")
        self.fleet_courier_aborts = mk("llmctl_fleet_courier_aborts")
        self.fleet_courier_wire_bytes = mk(
            "llmctl_fleet_courier_wire_bytes")
        self.fleet_courier_raw_bytes = mk(
            "llmctl_fleet_courier_raw_bytes")
        self.fleet_courier_expired = mk("llmctl_fleet_courier_expired")
        self.fleet_courier_transfer = mk(
            "llmctl_fleet_courier_transfer_ms")
        # fleet-global prefix cache (serve/fleet/ prefix fetch)
        self.fleet_prefix_fetch_pages = mk(
            "llmctl_fleet_prefix_fetch_pages")
        self.fleet_prefix_fetch_bytes = mk(
            "llmctl_fleet_prefix_fetch_bytes")
        self.fleet_prefix_fetch_misses = mk(
            "llmctl_fleet_prefix_fetch_misses")
        self.fleet_prefix_fetch_aborts = mk(
            "llmctl_fleet_prefix_fetch_aborts")
        self.fleet_prefix_fetch = mk("llmctl_fleet_prefix_fetch_ms")
        # inventory TTL cache (FleetConfig.prefix_inventory_ttl_ms)
        self.fleet_inventory_cache_hits = mk(
            "llmctl_fleet_prefix_inventory_cache_hits")
        self.fleet_inventory_cache_misses = mk(
            "llmctl_fleet_prefix_inventory_cache_misses")
        # tiered fleet KV store (serve/fleet/kv_store.py)
        self.fleet_kvstore_hits = mk("llmctl_fleet_kvstore_hits")
        self.fleet_kvstore_misses = mk("llmctl_fleet_kvstore_misses")
        self.fleet_kvstore_demotions = mk(
            "llmctl_fleet_kvstore_demotions")
        self.fleet_kvstore_evictions = mk(
            "llmctl_fleet_kvstore_evictions")
        self.fleet_kvstore_bytes = mk("llmctl_fleet_kvstore_bytes")
        # networked KV fabric: the standalone-store client's own view
        # (serve/fleet/store_service.py) + courier weight distribution
        # (serve/fleet/weights.py)
        self.fleet_kvstore_remote_hits = mk(
            "llmctl_fleet_kvstore_remote_hits")
        self.fleet_kvstore_remote_misses = mk(
            "llmctl_fleet_kvstore_remote_misses")
        # replicated store tier (serve/fleet/store_tier.py): client
        # failover + member fencing/anti-entropy
        self.fleet_kvstore_retry = mk("llmctl_fleet_kvstore_retry")
        self.fleet_kvstore_failovers = mk(
            "llmctl_fleet_kvstore_failovers")
        self.fleet_kvstore_hedges = mk("llmctl_fleet_kvstore_hedges")
        self.fleet_kvstore_fenced_rejects = mk(
            "llmctl_fleet_kvstore_fenced_rejects")
        self.fleet_kvstore_sync_pulls = mk(
            "llmctl_fleet_kvstore_sync_pulls")
        self.fleet_weights_chunks = mk("llmctl_fleet_weights_chunks")
        self.fleet_weights_resumes = mk("llmctl_fleet_weights_resumes")
        self.fleet_weights_bytes = mk("llmctl_fleet_weights_bytes")
        # pipelined multi-replica prefill (serve/fleet/pipeline.py)
        self.fleet_pipeline_prefills = mk(
            "llmctl_fleet_pipeline_prefills")
        self.fleet_pipeline_stages = mk("llmctl_fleet_pipeline_stages")
        self.fleet_pipeline_collapses = mk(
            "llmctl_fleet_pipeline_collapses")
        self.fleet_pipeline_preshipped_pages = mk(
            "llmctl_fleet_pipeline_preshipped_pages")
        self.fleet_pipeline_stage = mk("llmctl_fleet_pipeline_stage_ms")
        self.fleet_pipeline_preship_timeouts = mk(
            "llmctl_fleet_pipeline_preship_timeouts")
        self.fleet_store_hint_remote_skips = mk(
            "llmctl_fleet_store_hint_remote_skips")
        # fleet SSE streaming (serve/fleet/streams.py): the exactly-once
        # delivery ledger
        self.fleet_stream_active = mk("llmctl_fleet_stream_active")
        self.fleet_stream_tokens = mk("llmctl_fleet_stream_tokens")
        self.fleet_stream_duplicates = mk(
            "llmctl_fleet_stream_duplicates")
        self.fleet_stream_replayed = mk(
            "llmctl_fleet_stream_replayed_tokens")
        self.fleet_stream_reconnects = mk(
            "llmctl_fleet_stream_reconnects")
        self.fleet_stream_gaps_healed = mk(
            "llmctl_fleet_stream_gaps_healed")
        self.fleet_stream_backpressure_drops = mk(
            "llmctl_fleet_stream_backpressure_drops")
        self.fleet_stream_replay = mk("llmctl_fleet_stream_replay_tokens")
        self.fleet_stream_orphan_gcs = mk(
            "llmctl_fleet_stream_orphan_gcs")
        # HA front tier (serve/fleet/front.py + state.py)
        self.fleet_front_failovers = mk("llmctl_fleet_front_failovers")
        self.fleet_front_reconnects = mk(
            "llmctl_fleet_front_reconnects")
        self.fleet_front_up = mk("llmctl_fleet_front_up")
        self.fleet_front_active_streams = mk(
            "llmctl_fleet_front_active_streams")
        # speculative decode plane (serve/speculative.py SpecState)
        self.fleet_spec_dispatches = mk("llmctl_fleet_spec_dispatches")
        self.fleet_spec_drafts = mk("llmctl_fleet_spec_drafts")
        self.fleet_spec_accepted = mk("llmctl_fleet_spec_accepted")
        self.fleet_spec_resumes = mk("llmctl_fleet_spec_resumes")
        # elastic autoscaler + SLO tiers (serve/fleet/autoscaler.py)
        self.fleet_autoscale_scale_ups = mk(
            "llmctl_fleet_autoscale_scale_ups")
        self.fleet_autoscale_scale_downs = mk(
            "llmctl_fleet_autoscale_scale_downs")
        self.fleet_autoscale_spawn_failures = mk(
            "llmctl_fleet_autoscale_spawn_failures")
        self.fleet_autoscale_retire_rollbacks = mk(
            "llmctl_fleet_autoscale_retire_rollbacks")
        self.fleet_autoscale_preemptions = mk(
            "llmctl_fleet_autoscale_preemptions")
        self.fleet_replicas = mk("llmctl_fleet_replicas")
        self._last_totals: dict[str, float] = {}
        self._server_started = False

    def serve(self) -> None:
        if not self._server_started:
            self._start_http_server(self.port)
            self._server_started = True

    def export_system(self, sample: SystemSample) -> None:
        self.cpu.set(sample.cpu_percent)
        self.mem.set(sample.mem_percent)
        for dev, used in sample.hbm_used_gb.items():
            self.hbm_used.labels(device=str(dev)).set(used)

    def export_training(self, m: dict) -> None:
        if "loss" in m:
            self.train_loss.set(m["loss"])
        if "mfu" in m:
            self.train_mfu.set(m["mfu"])
        if "tokens_per_sec" in m:
            self.tokens_per_sec.set(m["tokens_per_sec"])
        if "tokens_per_sec_per_chip" in m:
            self.tokens_per_sec_chip.set(m["tokens_per_sec_per_chip"])
        if "grad_norm" in m:
            self.grad_norm.set(m["grad_norm"])
        if "lr" in m:
            self.lr.set(m["lr"])
        if "step" in m:   # true optimizer step (events fire at log_interval)
            self.steps.set(m["step"])

    def _inc_to(self, counter, key: str, total: float) -> None:
        """A running total, as the engine reports them: add what it gained
        since the last report."""
        delta = total - self._last_totals.get(key, 0)
        if delta > 0:
            counter.inc(delta)
        self._last_totals[key] = total

    def export_inference(self, m: dict) -> None:
        self.infer_requests.inc()
        if "latency_ms" in m:
            self.infer_latency.observe(m["latency_ms"] / 1e3)
        if "ttft_ms" in m and m["ttft_ms"] is not None:
            self.infer_ttft.observe(m["ttft_ms"] / 1e3)
        if "queue_depth" in m:
            self.infer_queue.set(m["queue_depth"])
        if "queue_wait_ms" in m:
            self.infer_queue_wait.snap = m["queue_wait_ms"]
        for phase, cell in m.get("phases", {}).items():
            self._inc_to(self.engine_phase_seconds.labels(phase=phase),
                         f"phase:{phase}", cell["s"])
        for span, total in m.get("starved_by_phase", {}).items():
            self._inc_to(self.starved_seconds.labels(span=span),
                         f"starved:{span}", total)
        for name in ("useful", "overrun", "prompt_wait", "empty"):
            if name in m.get("slot_steps", ()):
                self._inc_to(self.slot_steps.labels(**{"class": name}),
                             f"slot_steps:{name}", m["slot_steps"][name])
        for phase, cell in m.get("startup_phases", {}).items():
            self.startup_phase_seconds.labels(phase=phase).set(cell["s"])
        for expert, total in enumerate(m.get("moe_choices", ())):
            self._inc_to(self.moe_expert_choices.labels(expert=str(expert)),
                         f"moe:{expert}", total)
        if "decode_tokens_per_sec" in m:
            self.decode_tokens_per_sec.set(m["decode_tokens_per_sec"])
        for key, counter in (("preemptions", self.infer_preemptions),
                             ("swap_ins", self.infer_swap_ins),
                             ("prefill_ride_tokens", self.infer_ride_tokens),
                             ("state_carry_tokens",
                              self.infer_carry_tokens),
                             *self.infer_mtp.items()):
            if key in m:
                self._inc_to(counter, key, m[key])
        if "swapped_host_bytes" in m:
            self.infer_swapped_bytes.set(m["swapped_host_bytes"])

    def export_fleet(self, snap: dict) -> None:
        """Export a supervisor snapshot (serve/fleet/supervisor.py
        ``snapshot()``): per-replica gauges + fleet counters. Counters
        arrive as running totals, so the delta since the last snapshot is
        inc'ed (same convention as preemptions/swap_ins above)."""
        for rep in snap.get("replicas", []):
            rid = str(rep["replica"])
            self.fleet_queue_depth.labels(replica=rid).set(
                rep.get("queue_depth", 0))
            self.fleet_outstanding.labels(replica=rid).set(
                rep.get("outstanding_tokens", 0))
            self.fleet_active.labels(replica=rid).set(rep.get("active", 0))
            self.fleet_healthy.labels(replica=rid).set(
                1.0 if rep.get("state") == "healthy" else 0.0)
            key = f"fleet_restarts_{rid}"
            delta = rep.get("restarts", 0) - self._last_totals.get(key, 0)
            if delta > 0:
                self.fleet_restarts.labels(replica=rid).inc(delta)
            self._last_totals[key] = rep.get("restarts", 0)
            if "prefix_hit_rate" in rep:
                self.fleet_prefix_hit_rate.labels(replica=rid).set(
                    rep["prefix_hit_rate"])
            if "role" in rep:
                self.fleet_replica_role.labels(replica=rid).set(
                    {"mixed": 0, "prefill": 1, "decode": 2}.get(
                        rep["role"], 0))
        router = snap.get("router", {})
        for key, counter in (
                ("requeues", self.fleet_requeues),
                ("rejected", self.fleet_rejected),
                ("inventory_cache_hits", self.fleet_inventory_cache_hits),
                ("inventory_cache_misses",
                 self.fleet_inventory_cache_misses),
                ("store_hint_remote_skips",
                 self.fleet_store_hint_remote_skips)):
            total = router.get(key, 0)
            delta = total - self._last_totals.get(f"fleet_{key}", 0)
            if delta > 0:
                counter.inc(delta)
            self._last_totals[f"fleet_{key}"] = total
        mig = snap.get("migration", {})
        for key, counter in (
                ("migrations", self.fleet_migrations),
                ("migrated_tokens", self.fleet_migrated_tokens),
                ("reprefill_tokens_avoided", self.fleet_reprefill_avoided)):
            total = mig.get(key, 0)
            delta = total - self._last_totals.get(f"fleet_mig_{key}", 0)
            if delta > 0:
                counter.inc(delta)
            self._last_totals[f"fleet_mig_{key}"] = total
        # pauses arrive as a bounded recent list + a cumulative count:
        # observe only the count delta's worth of newest entries, so a
        # repeated snapshot can't double-fill the histogram
        count = mig.get("pause_count", 0)
        new = int(count - self._last_totals.get("fleet_mig_pauses", 0))
        pauses = mig.get("pauses_ms", [])
        if new > 0:
            for p in pauses[-min(new, len(pauses)):]:
                self.fleet_migration_pause.observe(p)
        self._last_totals["fleet_mig_pauses"] = count
        # disaggregation plane: handoff counter + stall histogram follow
        # the same delta-on-running-totals contract as migration above
        ho = snap.get("handoff", {})
        total = ho.get("handoffs", 0)
        delta = total - self._last_totals.get("fleet_handoffs", 0)
        if delta > 0:
            self.fleet_handoffs.inc(delta)
        self._last_totals["fleet_handoffs"] = total
        count = ho.get("stall_count", 0)
        new = int(count - self._last_totals.get("fleet_handoff_stalls", 0))
        stalls = ho.get("stalls_ms", [])
        if new > 0:
            for s in stalls[-min(new, len(stalls)):]:
                self.fleet_handoff_stall.observe(s)
        self._last_totals["fleet_handoff_stalls"] = count
        # courier transport plane: counters on running totals, the
        # transfer histogram on the bounded recent window (same delta
        # contract as migration pauses / handoff stalls above)
        cour = snap.get("courier", {})
        for key, counter in (
                ("chunks", self.fleet_courier_chunks),
                ("retries", self.fleet_courier_retries),
                ("corruptions", self.fleet_courier_corruptions),
                ("resumes", self.fleet_courier_resumes),
                ("aborts", self.fleet_courier_aborts),
                ("bytes_wire", self.fleet_courier_wire_bytes),
                ("bytes_raw", self.fleet_courier_raw_bytes),
                ("expired", self.fleet_courier_expired)):
            total = cour.get(key, 0)
            delta = total - self._last_totals.get(f"fleet_cour_{key}", 0)
            if delta > 0:
                counter.inc(delta)
            self._last_totals[f"fleet_cour_{key}"] = total
        count = cour.get("transfer_count", 0)
        new = int(count - self._last_totals.get("fleet_cour_transfers", 0))
        xfers = cour.get("transfer_ms", [])
        if new > 0:
            for t in xfers[-min(new, len(xfers)):]:
                self.fleet_courier_transfer.observe(t)
        self._last_totals["fleet_cour_transfers"] = count
        # fleet-global prefix-fetch plane: same delta-on-running-totals
        # contract; the latency histogram fills from the bounded recent
        # window gated by the cumulative attempt count
        pf = snap.get("prefix_fetch", {})
        for key, counter in (
                ("pages", self.fleet_prefix_fetch_pages),
                ("bytes", self.fleet_prefix_fetch_bytes),
                ("misses", self.fleet_prefix_fetch_misses),
                ("aborts", self.fleet_prefix_fetch_aborts)):
            total = pf.get(key, 0)
            delta = total - self._last_totals.get(f"fleet_pf_{key}", 0)
            if delta > 0:
                counter.inc(delta)
            self._last_totals[f"fleet_pf_{key}"] = total
        count = pf.get("fetch_count", 0)
        new = int(count - self._last_totals.get("fleet_pf_fetches", 0))
        window = pf.get("fetch_ms", [])
        if new > 0:
            for t in window[-min(new, len(window)):]:
                self.fleet_prefix_fetch.observe(t)
        self._last_totals["fleet_pf_fetches"] = count
        # tiered fleet KV store: demotion/hit/miss/eviction counters and
        # the compressed bytes replayed on hits, delta'd from the
        # snapshot's running totals like every other fleet counter
        ks = snap.get("kv_store", {})
        for key, counter in (
                ("hits", self.fleet_kvstore_hits),
                ("misses", self.fleet_kvstore_misses),
                ("demotions", self.fleet_kvstore_demotions),
                ("evictions", self.fleet_kvstore_evictions),
                ("bytes_served", self.fleet_kvstore_bytes),
                # networked backend only: the client-side replay/miss
                # counts (the in-proc store never sets these keys)
                ("remote_hits", self.fleet_kvstore_remote_hits),
                ("remote_misses", self.fleet_kvstore_remote_misses),
                # replicated tier: client failover counters plus the
                # member-side fencing/anti-entropy counts (the latter
                # appear when this process scrapes a member's status)
                ("retries", self.fleet_kvstore_retry),
                ("failovers", self.fleet_kvstore_failovers),
                ("hedges", self.fleet_kvstore_hedges),
                ("fenced_rejects", self.fleet_kvstore_fenced_rejects),
                ("sync_pulls", self.fleet_kvstore_sync_pulls)):
            total = ks.get(key, 0)
            delta = total - self._last_totals.get(f"fleet_ks_{key}", 0)
            if delta > 0:
                counter.inc(delta)
            self._last_totals[f"fleet_ks_{key}"] = total
        # courier weight distribution: chunks/resumes/bytes this
        # process moved through the store service (supervisor snapshot
        # "weights" section; running totals like every fleet counter)
        wt = snap.get("weights", {})
        for key, counter in (
                ("chunks", self.fleet_weights_chunks),
                ("resumes", self.fleet_weights_resumes),
                ("bytes", self.fleet_weights_bytes)):
            total = wt.get(key, 0)
            delta = total - self._last_totals.get(f"fleet_wt_{key}", 0)
            if delta > 0:
                counter.inc(delta)
            self._last_totals[f"fleet_wt_{key}"] = total
        # pipelined multi-replica prefill: counters on running totals,
        # the stage-latency histogram on the bounded recent window gated
        # by the cumulative stage count (same contract as courier
        # transfers above)
        pl = snap.get("pipeline", {})
        for key, counter in (
                ("pipelines", self.fleet_pipeline_prefills),
                ("stages", self.fleet_pipeline_stages),
                ("collapses", self.fleet_pipeline_collapses),
                ("preshipped_pages",
                 self.fleet_pipeline_preshipped_pages),
                ("preship_timeouts",
                 self.fleet_pipeline_preship_timeouts)):
            total = pl.get(key, 0)
            delta = total - self._last_totals.get(f"fleet_pl_{key}", 0)
            if delta > 0:
                counter.inc(delta)
            self._last_totals[f"fleet_pl_{key}"] = total
        count = pl.get("stage_count", 0)
        new = int(count - self._last_totals.get("fleet_pl_stages_obs", 0))
        window = pl.get("stage_ms", [])
        if new > 0:
            for t in window[-min(new, len(window)):]:
                self.fleet_pipeline_stage.observe(t)
        self._last_totals["fleet_pl_stages_obs"] = count
        # speculative-decode plane: per-replica counters arrive fleet-
        # aggregated as running totals (supervisor snapshot "spec"
        # section); the pump deltas them like every other fleet counter
        sp = snap.get("spec", {})
        for key, counter in (
                ("dispatches", self.fleet_spec_dispatches),
                ("drafts", self.fleet_spec_drafts),
                ("accepted", self.fleet_spec_accepted),
                ("resumes", self.fleet_spec_resumes)):
            total = sp.get(key, 0)
            delta = total - self._last_totals.get(f"fleet_sp_{key}", 0)
            if delta > 0:
                counter.inc(delta)
            self._last_totals[f"fleet_sp_{key}"] = total
        # fleet SSE streaming plane: counters on running totals; the
        # replay-size histogram fills from the bounded recent window
        # gated by the cumulative reconnect count (same delta contract)
        st = snap.get("streams", {})
        if st:
            self.fleet_stream_active.set(st.get("active", 0))
        for key, counter in (
                ("tokens", self.fleet_stream_tokens),
                ("duplicates", self.fleet_stream_duplicates),
                ("replayed", self.fleet_stream_replayed),
                ("reconnects", self.fleet_stream_reconnects),
                ("gaps_healed", self.fleet_stream_gaps_healed),
                ("backpressure_drops",
                 self.fleet_stream_backpressure_drops),
                ("orphan_logs_gc", self.fleet_stream_orphan_gcs),
                ("front_resumes", self.fleet_front_reconnects)):
            total = st.get(key, 0)
            delta = total - self._last_totals.get(f"fleet_st_{key}", 0)
            if delta > 0:
                counter.inc(delta)
            self._last_totals[f"fleet_st_{key}"] = total
        count = st.get("replay_count", 0)
        new = int(count - self._last_totals.get("fleet_st_replays", 0))
        sizes = st.get("replay_sizes", [])
        if new > 0:
            for s in sizes[-min(new, len(sizes)):]:
                self.fleet_stream_replay.observe(s)
        self._last_totals["fleet_st_replays"] = count
        # HA front tier: per-front liveness/load gauges from the shared
        # store's registry + the tier failover counter (running total,
        # delta'd like every other fleet counter)
        ft = snap.get("front_tier", {})
        for fid, entry in (ft.get("fronts") or {}).items():
            self.fleet_front_up.labels(front=fid).set(
                1.0 if entry.get("alive") else 0.0)
            self.fleet_front_active_streams.labels(front=fid).set(
                entry.get("active_streams", 0))
        total = ft.get("failovers", 0)
        delta = total - self._last_totals.get("fleet_front_failovers", 0)
        if delta > 0:
            self.fleet_front_failovers.inc(delta)
        self._last_totals["fleet_front_failovers"] = total
        # elastic autoscaler: scale/preempt counters (running totals,
        # delta'd) + the live fleet-size gauge
        au = snap.get("autoscale", {})
        if au:
            self.fleet_replicas.set(au.get("replicas", 0))
        for key, counter in (
                ("scale_ups", self.fleet_autoscale_scale_ups),
                ("scale_downs", self.fleet_autoscale_scale_downs),
                ("spawn_failures", self.fleet_autoscale_spawn_failures),
                ("retire_rollbacks",
                 self.fleet_autoscale_retire_rollbacks),
                ("preemptions", self.fleet_autoscale_preemptions)):
            total = au.get(key, 0)
            delta = total - self._last_totals.get(f"fleet_au_{key}", 0)
            if delta > 0:
                counter.inc(delta)
            self._last_totals[f"fleet_au_{key}"] = total


class ObservabilityManager:
    """Composition + export pump (reference ObservabilityManager
    observability.py:331-415)."""

    def __init__(self, prometheus_port: Optional[int] = None,
                 collect_interval: float = 1.0):
        self.collector = MetricsCollector(interval=collect_interval)
        self.prometheus: Optional[PrometheusExporter] = None
        if prometheus_port:
            try:
                self.prometheus = PrometheusExporter(prometheus_port)
                self.prometheus.serve()
            except Exception as e:
                logger.warning("prometheus exporter disabled: %s", e)
        self._export_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    def start(self) -> None:
        self.collector.start()
        if self.prometheus and self._export_thread is None:
            def pump():
                while not self._stop.wait(5.0):
                    if self.collector.history:
                        self.prometheus.export_system(self.collector.history[-1])
            self._export_thread = threading.Thread(target=pump, daemon=True)
            self._export_thread.start()

    def stop(self) -> None:
        self._stop.set()
        self.collector.stop()

    def record_training_step(self, m: dict) -> None:
        self.collector.record_training(m)
        if self.prometheus:
            self.prometheus.export_training(m)

    def record_eval(self, m: dict) -> None:
        self.collector.record_training({"eval": True, **m})
        if self.prometheus and "loss" in m:
            self.prometheus.eval_loss.set(m["loss"])

    def record_inference(self, m: dict) -> None:
        self.collector.record_inference(m)
        if self.prometheus:
            self.prometheus.export_inference(m)

    def record_fleet(self, snap: dict) -> None:
        """Per-replica fleet snapshot (supervisor poll cadence)."""
        if self.prometheus:
            self.prometheus.export_fleet(snap)


# -- global singleton (reference setup_observability observability.py:417) ----

_manager: Optional[ObservabilityManager] = None


def setup_observability(prometheus_port: Optional[int] = None
                        ) -> ObservabilityManager:
    global _manager
    if _manager is None:
        import os
        if prometheus_port is None:
            port = os.environ.get("LLMCTL_METRICS_PORT")
            prometheus_port = int(port) if port else None
        _manager = ObservabilityManager(prometheus_port)
        _manager.start()
    return _manager


def get_observability() -> Optional[ObservabilityManager]:
    return _manager


def engine_observer() -> Callable[[str, dict], None]:
    """The hook runtime/engine.py feeds — this closes the reference's
    metrics-not-wired gap (SURVEY §5.5)."""
    mgr = setup_observability()

    def observe(event: str, payload: dict) -> None:
        if event == "train_step":
            mgr.record_training_step(payload)
        elif event == "eval":
            mgr.record_eval(payload)
    return observe
