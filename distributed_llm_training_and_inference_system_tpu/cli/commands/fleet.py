"""`llmctl fleet` — operate a running serve fleet over its HTTP surface.

Companion to ``llmctl serve start --replicas N`` (serve/fleet/http.py):
``status`` reads ``GET /fleet/status``; ``drain``/``undrain`` post to
``/fleet/drain`` / ``/fleet/undrain``. Stdlib urllib only — the operator
box running this may not have the serving deps installed.
"""

from __future__ import annotations

import json
import urllib.error
import urllib.request

import click

from .serve import kv_block_size_option


def _get(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=10) as resp:
        return json.loads(resp.read().decode())


def _post(url: str, body: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=10) as resp:
        return json.loads(resp.read().decode())


def _die(e: Exception) -> None:
    if isinstance(e, urllib.error.HTTPError):
        try:
            detail = json.loads(e.read().decode()).get("error", "")
        except Exception:
            detail = ""
        raise click.ClickException(f"HTTP {e.code}: {detail or e.reason}")
    raise click.ClickException(str(e))


@click.group(name="fleet")
def app():
    """Serve-fleet operations (router + replica supervisor)."""


@app.command()
@click.option("--url", default="http://127.0.0.1:8080", show_default=True,
              help="Fleet server base URL.")
@click.option("--json", "as_json", is_flag=True,
              help="Raw JSON instead of the table.")
def status(url, as_json):
    """Per-replica health, queue depths, and the router ledger."""
    try:
        snap = _get(f"{url.rstrip('/')}/fleet/status")
    except Exception as e:
        _die(e)
    if as_json:
        click.echo(json.dumps(snap, indent=2))
        return
    from rich.console import Console
    from rich.table import Table
    table = Table(title="Fleet replicas")
    for col in ("replica", "state", "role", "endpoint", "remote?",
                "queue", "active", "outstanding tok", "restarts",
                "migr out", "handoffs", "streams", "replayed",
                "courier out", "courier aborts",
                "prefix hit", "pfx fetched", "pfx miss",
                "spec acc", "last error"):
        table.add_column(col)
    per_src = snap.get("courier", {}).get("per_src", {})
    for r in snap["replicas"]:
        color = {"healthy": "green", "draining": "yellow",
                 "drained": "yellow"}.get(r["state"], "red")
        hit = r.get("prefix_hit_rate")
        role = r.get("role", "mixed")
        if r.get("promoted_from"):
            # crash-promoted; auto-demotes once the lost class returns
            role = f"{role} (was {r['promoted_from']})"
        src = per_src.get(str(r["replica"]), {})
        # speculative acceptance: drafts accepted / proposed on this
        # replica, "+N res" when sequences arrived with a migrated
        # SpecState (courier-aware speculation)
        if r.get("spec_drafts"):
            spec = f"{r.get('spec_acceptance', 0.0):.0%}"
            if r.get("spec_resumes"):
                spec += f" +{r['spec_resumes']}res"
        else:
            spec = "-"
        table.add_row(str(r["replica"]),
                      f"[{color}]{r['state']}[/{color}]",
                      role,
                      r.get("endpoint", "local"),
                      "yes" if r.get("remote") else "-",
                      str(r["queue_depth"]), str(r["active"]),
                      str(r["outstanding_tokens"]), str(r["restarts"]),
                      str(r.get("migrations", 0)),
                      str(r.get("handoffs", 0)),
                      str(r.get("active_streams", 0)),
                      str(r.get("stream_replayed_tokens", 0)),
                      str(src.get("transfers", 0)),
                      str(src.get("aborts", 0)),
                      f"{hit:.0%}" if hit is not None else "-",
                      str(r.get("prefix_fetch_pages", 0)),
                      str(r.get("prefix_fetch_misses", 0)),
                      spec,
                      (r.get("last_error") or "")[:48])
    console = Console()
    console.print(table)
    rt = snap["router"]
    console.print(
        f"router: {rt['completed']}/{rt['submitted']} completed, "
        f"{rt['rejected']} rejected (429), {rt['requeues']} requeues, "
        f"{rt['in_flight']} in flight, {rt['parked']} parked")
    mig = snap.get("migration")
    if mig:
        console.print(
            f"migration: {mig['migrations']} moved "
            f"({mig['migrated_tokens']} KV tokens, "
            f"{mig['reprefill_tokens_avoided']} re-prefill tokens "
            f"avoided, {mig.get('rebalance_migrations', 0)} "
            f"rebalancer-ordered, {mig['in_flight']} in flight)")
    ho = snap.get("handoff")
    if ho and (ho.get("handoffs") or ho.get("local_fallbacks")
               or ho.get("reroles") or ho.get("promotions")
               or ho.get("demotions")):
        console.print(
            f"disagg: {ho.get('handoffs', 0)} prefill->decode handoffs "
            f"({ho.get('handoff_tokens', 0)} KV tokens, "
            f"{ho.get('local_fallbacks', 0)} local fallbacks, "
            f"{ho.get('reroles', 0)} re-roles, "
            f"{ho.get('promotions', 0)} promotions, "
            f"{ho.get('demotions', 0)} demotions)")
    st = snap.get("streams")
    if st and (st.get("opened") or st.get("active")):
        console.print(
            f"streams: {st.get('active', 0)} live / "
            f"{st.get('opened', 0)} opened, "
            f"{st.get('tokens', 0)} tokens, "
            f"{st.get('duplicates', 0)} producer dups suppressed, "
            f"{st.get('reconnects', 0)} reconnects "
            f"({st.get('replayed', 0)} tokens replayed), "
            f"{st.get('gaps_healed', 0)} gap-healed, "
            f"{st.get('backpressure_drops', 0)} backpressure drops, "
            f"{st.get('identity_mismatches', 0)} identity violations")
    ft = snap.get("front_tier")
    if ft and ft.get("fronts"):
        per_front = ", ".join(
            f"{fid}:{e.get('port', '?')} "
            f"[{'up' if e.get('alive') else 'fenced' if e.get('fenced') else 'down'}]"  # noqa: E501
            for fid, e in sorted(ft["fronts"].items()))
        console.print(
            f"front tier: {per_front} — "
            f"{ft.get('failovers', 0)} failovers, "
            f"{ft.get('reconnects', 0)} failover resumes served here "
            f"(this front: {ft.get('front_id', '?')})")
    sp = snap.get("spec")
    if sp and sp.get("dispatches"):
        console.print(
            f"speculative: {sp.get('accepted', 0)}/{sp.get('drafts', 0)} "
            f"drafts accepted ({sp.get('acceptance', 0.0):.0%} over "
            f"{sp.get('dispatches', 0)} dispatches, "
            f"{sp.get('resumes', 0)} migrated-state resumes)")
    pf = snap.get("prefix_fetch")
    if pf and (pf.get("pages") or pf.get("misses") or pf.get("aborts")):
        console.print(
            f"prefix fetch: {pf.get('pages', 0)} pages pulled from "
            f"siblings ({pf.get('bytes', 0)} bytes, "
            f"{pf.get('fetches', 0)} fetches, "
            f"{pf.get('misses', 0)} misses, "
            f"{pf.get('aborts', 0)} aborts)")
    pl = snap.get("pipeline")
    if pl and (pl.get("pipelines") or pl.get("collapses")):
        overlap = pl.get("overlap_ratio")
        console.print(
            f"pipelined prefill: {pl.get('completed', 0)}/"
            f"{pl.get('pipelines', 0)} pipelines completed "
            f"({pl.get('stages', 0)} stages, "
            f"{pl.get('collapses', 0)} collapses to single-replica, "
            f"{pl.get('in_flight', 0)} in flight), "
            f"{pl.get('preshipped_pages', 0)} pages pre-shipped "
            f"({pl.get('preship_hidden_ms', 0)}/"
            f"{pl.get('preship_ms', 0)} ms hidden behind compute, "
            f"{pl.get('preship_timeouts', 0)} pre-ship timeouts"
            + (f", {overlap:.0%} overlap" if overlap is not None
               else "") + ")")
    au = snap.get("autoscale")
    if au and au.get("enabled"):
        retiring = au.get("retiring")
        console.print(
            f"autoscale: {au.get('replicas', 0)} replicas "
            f"(floor {au.get('floor', 0)}, ceiling {au.get('ceiling', 0)}"
            + (f", retiring {retiring}" if retiring is not None else "")
            + f"), {au.get('scale_ups', 0)} scale-ups / "
            f"{au.get('scale_downs', 0)} scale-downs, "
            f"{au.get('spawn_failures', 0)} spawn failures, "
            f"{au.get('retire_rollbacks', 0)} retire rollbacks, "
            f"{au.get('preemptions', 0)} best-effort preemptions")
    by_cls = (rt.get("submitted_by_class") or {})
    rej_cls = (rt.get("rejected_by_class") or {})
    if any(by_cls.values()) or any(rej_cls.values()):
        console.print(
            "priority: " + ", ".join(
                f"{cls} {by_cls.get(cls, 0)} admitted / "
                f"{rej_cls.get(cls, 0)} shed"
                for cls in ("interactive", "standard", "best-effort")))
    if rt.get("store_hint_remote_skips"):
        console.print(
            f"store hints: {rt['store_hint_remote_skips']} skipped for "
            f"remote destinations (store tier unreachable from "
            f"workers)")
    ks = snap.get("kv_store")
    if ks and (ks.get("demotions") or ks.get("hits") or ks.get("misses")):
        console.print(
            f"kv store: {ks.get('hits', 0)} page hits / "
            f"{ks.get('misses', 0)} misses "
            f"({ks.get('bytes_served', 0)} bytes replayed), "
            f"{ks.get('demotions', 0)} demotions, "
            f"dram {ks.get('dram_entries', 0)} pages / "
            f"{ks.get('dram_bytes', 0)} bytes, "
            f"disk {ks.get('disk_entries', 0)} pages / "
            f"{ks.get('disk_bytes', 0)} bytes, "
            f"{ks.get('evictions', 0)} evictions "
            f"({ks.get('spills', 0)} spills, "
            f"{ks.get('corrupt', 0)} corrupt) "
            f"[{ks.get('codec', '?')}]")
    if ks and len(ks.get("endpoints") or []) > 1:
        # replicated store tier: member reachability (the client's
        # health view) + the failover counters
        reach = ks.get("members") or {}
        console.print(
            "store tier: "
            + ", ".join(f"{ep} {'up' if ok else 'DOWN'}"
                        for ep, ok in reach.items())
            + f" | {ks.get('retries', 0)} retries, "
              f"{ks.get('failovers', 0)} failovers, "
              f"{ks.get('hedges', 0)} hedged fetches, "
              f"{ks.get('fenced_rejects', 0)} fenced rejects, "
              f"{ks.get('sync_pulls', 0)} anti-entropy pulls")
    cour = snap.get("courier")
    if cour and (cour.get("transfers") or cour.get("aborts")
                 or cour.get("in_flight") or cour.get("expired")):
        console.print(
            f"courier: {cour.get('in_flight', 0)} in flight, "
            f"{cour.get('transfers', 0)} transfers "
            f"({cour.get('bytes_wire', cour.get('bytes_moved', 0))} "
            f"wire / {cour.get('bytes_raw', cour.get('bytes_moved', 0))} "
            f"raw bytes, {cour.get('compression_ratio', 1.0):.2f}x "
            f"compression, "
            f"{cour.get('chunks', 0)} chunks, "
            f"{cour.get('retries', 0)} retries, "
            f"{cour.get('corruptions', 0)} corruptions, "
            f"{cour.get('resumes', 0)} resumes, "
            f"{cour.get('aborts', 0)} aborts, "
            f"{cour.get('expired', 0)} expired tickets)")


@app.command()
@click.argument("replica", type=int)
@click.option("--url", default="http://127.0.0.1:8080", show_default=True)
def drain(replica, url):
    """Gracefully drain REPLICA: its in-flight requests requeue to the
    surviving replicas (token-identical resume), then it leaves rotation."""
    try:
        out = _post(f"{url.rstrip('/')}/fleet/drain", {"replica": replica})
    except Exception as e:
        _die(e)
    click.echo(f"replica {out['replica']}: drain requested")


@app.command()
@click.argument("replica", type=int)
@click.option("--url", default="http://127.0.0.1:8080", show_default=True)
def undrain(replica, url):
    """Return a drained REPLICA to rotation."""
    try:
        out = _post(f"{url.rstrip('/')}/fleet/undrain",
                    {"replica": replica})
    except Exception as e:
        _die(e)
    click.echo(f"replica {out['replica']}: back in rotation")


@app.command()
@click.argument("replica", type=int)
@click.argument("role", type=click.Choice(["prefill", "decode", "mixed"]))
@click.option("--url", default="http://127.0.0.1:8080", show_default=True)
def role(replica, role, url):
    """Re-role REPLICA for disaggregated prefill/decode serving. A
    prefill replica admits new prompts and hands each freshly-prefilled
    sequence (with its KV) to a decode replica; decode replicas only
    restore and decode; mixed does both. Drain the replica first if the
    switch must be loss-free for its residents."""
    try:
        out = _post(f"{url.rstrip('/')}/fleet/role",
                    {"replica": replica, "role": role})
    except Exception as e:
        _die(e)
    click.echo(f"replica {out['replica']}: role set to {out['role']}")


@app.command()
@click.argument("request_id")
@click.argument("replica", type=int)
@click.option("--url", default="http://127.0.0.1:8080", show_default=True)
def migrate(request_id, replica, url):
    """Live-migrate REQUEST_ID to REPLICA with its KV pages: the source
    pre-copies full pages while it keeps decoding, stop-and-copies only
    the partial tail, and the destination resumes the sequence
    token-identically with zero re-prefill."""
    try:
        out = _post(f"{url.rstrip('/')}/fleet/migrate",
                    {"request_id": request_id, "replica": replica})
    except Exception as e:
        _die(e)
    click.echo(f"request {out['request_id']}: migrating to replica "
               f"{out['replica']}")


@app.command()
@click.option("--model", "model_name", default="gpt-125m",
              show_default=True, help="Model template name.")
@click.option("--artifact", default="",
              help="Checkpoint dir or exported weights file.")
@click.option("--replica-id", default=0, show_default=True, type=int,
              help="This worker's replica id in the parent fleet — must "
                   "match its --fleet-endpoint entry.")
@click.option("--role", default="mixed", show_default=True,
              type=click.Choice(["prefill", "decode", "mixed"]))
@click.option("--host", default="127.0.0.1", show_default=True)
@click.option("--port", default=0, show_default=True, type=int,
              help="0 binds an ephemeral port; the bound port is "
                   "printed as 'LLMCTL_WORKER_READY port=N'.")
@click.option("--max-batch-size", default=8, show_default=True, type=int)
@click.option("--max-seq-len", default=2048, show_default=True, type=int)
@click.option("--prefill-chunk", default=0, show_default=True, type=int,
              help="Finest step of the prefill bucket ladder (0 = engine "
                   "default).")
@kv_block_size_option
@click.option("--dtype", default=None,
              type=click.Choice(["bfloat16", "float32"]))
@click.option("--kv-quantization", default="none", show_default=True,
              type=click.Choice(["none", "int8", "int4"]))
@click.option("--speculative", default="off", show_default=True,
              type=click.Choice(["off", "ngram"]),
              help="Speculative decoding on this worker's engine (ngram "
                   "= host prompt-lookup drafts, device verification; "
                   "greedy output unchanged). Per-sequence SpecState "
                   "rides migration/handoff manifests and the submit "
                   "wire, so re-placed sequences resume at their tuned "
                   "window; acceptance counters surface through "
                   "/worker/probe into the parent's RemoteReplica "
                   "mirror and llmctl_fleet_spec_*.")
@click.option("--spec-tokens", default=8, show_default=True, type=int,
              help="Speculative verify window (drafts per dispatch + 1).")
@click.option("--seed", default=0, show_default=True, type=int,
              help="Engine sampling seed base.")
@click.option("--param-seed", default=-1, show_default=True, type=int,
              help="Initialise weights from this PRNG seed instead of "
                   "loading an artifact (cross-process determinism for "
                   "tests/dryrun; every worker and the reference must "
                   "use the same value). -1 = normal artifact/init "
                   "path.")
@click.option("--courier-codec", default="none", show_default=True,
              type=click.Choice(["none", "zlib", "delta-zlib"]),
              help="Wire codec this worker's OUTBOUND courier pushes "
                   "use (worker-to-worker ships, prefix-fetch serves); "
                   "inbound transfers accept any known codec. "
                   "delta-zlib delta-encodes quantized KV planes then "
                   "deflates per chunk — 2-4x fewer wire bytes.")
@click.option("--courier-chunk-bytes", default=256 * 1024,
              show_default=True, type=int)
@click.option("--courier-retries", default=4, show_default=True,
              type=int)
@click.option("--courier-deadline-ms", default=100.0, show_default=True,
              type=float)
@click.option("--courier-backoff-ms", default=2.0, show_default=True,
              type=float)
@click.option("--courier-backoff-max-ms", default=100.0,
              show_default=True, type=float)
@click.option("--ticket-ttl-ms", default=60_000.0, show_default=True,
              type=float,
              help="Evict unclaimed courier tickets after this long.")
@click.option("--restart-backoff", default=0.5, show_default=True,
              type=float,
              help="First local engine-rebuild delay after a crash; "
                   "doubles per consecutive crash.")
@click.option("--migrate-on-drain/--no-migrate-on-drain", default=True,
              show_default=True)
@click.option("--store-endpoint", default="",
              help="Base URL of a `llmctl fleet store` service. This "
                   "worker demotes evicted prefix pages there and "
                   "restores store-held pages from it (the networked "
                   "KV fabric).")
@click.option("--store-endpoints", default="",
              help="Comma-separated member URLs of a REPLICATED store "
                   "tier (overrides --store-endpoint). The worker's "
                   "store client retries transient errors, rotates to "
                   "a survivor when a member dies, and fans demotions "
                   "out to the write-ack floor.")
@click.option("--weights-from-store", is_flag=True, default=False,
              help="Bootstrap engine weights from the store service "
                   "instead of a local artifact — a bare host needs "
                   "only --store-endpoint. The fetch is chunk-CRC'd "
                   "and (with --weights-spool) resumable across a "
                   "mid-ship kill.")
@click.option("--weights-name", default="",
              help="Checkpoint name in the store (default: --model).")
@click.option("--weights-spool", default="",
              help="Directory where fetched weight chunks persist as "
                   "they arrive; a respawned worker RESUMES its fetch "
                   "from the verified spool instead of restarting.")
@click.option("--fault-plan", default="",
              help="JSON FaultPlan for deterministic chaos (testing): "
                   "e.g. '{\"seed\": 5, \"chunk_drop_rate\": 0.2}'.")
def worker(model_name, artifact, replica_id, role, host, port,
           max_batch_size, max_seq_len, prefill_chunk, kv_block_size,
           dtype, kv_quantization, speculative, spec_tokens, seed,
           param_seed, courier_codec, courier_chunk_bytes,
           courier_retries, courier_deadline_ms, courier_backoff_ms,
           courier_backoff_max_ms, ticket_ttl_ms, restart_backoff,
           migrate_on_drain, store_endpoint, store_endpoints,
           weights_from_store, weights_name, weights_spool, fault_plan):
    """Run ONE fleet replica as its own OS process behind an HTTP front.

    The cross-host half of `llmctl serve start --fleet-remote-replicas`:
    the parent fleet submits work and collects results over
    /worker/* RPCs, and KV payloads arrive by push at
    /fleet/courier/chunk (reassembled, CRC-verified, and attached by
    ticket locally — the remote restorer). The worker supervises its
    own engine; the parent only declares it dead when the process stops
    answering."""
    import json as _json

    import jax

    from ...config.presets import get_model_config
    from ...config.schema import FleetConfig, ServeConfig
    from ...serve.fleet.faults import FaultPlan
    from ...serve.fleet.worker import FleetWorker

    if dtype is None:
        dtype = "bfloat16" if jax.default_backend() == "tpu" else "float32"
    model_cfg = get_model_config(model_name)
    serve_kw = dict(
        model=model_name, artifact=artifact, host=host, port=port,
        max_batch_size=max_batch_size,
        max_seq_len=min(max_seq_len, model_cfg.max_position_embeddings),
        kv_block_size=kv_block_size, dtype=dtype,
        kv_quantization=kv_quantization,
        speculative=speculative, speculative_tokens=spec_tokens)
    if prefill_chunk > 0:
        serve_kw["prefill_chunk"] = prefill_chunk
    serve_cfg = ServeConfig(**serve_kw)
    serve_cfg.validate()
    fleet_cfg = FleetConfig(
        replicas=1, migrate_on_drain=migrate_on_drain,
        restart_backoff_s=restart_backoff,
        courier_codec=courier_codec,
        courier_chunk_bytes=courier_chunk_bytes,
        courier_max_retries=courier_retries,
        courier_chunk_deadline_ms=courier_deadline_ms,
        courier_retry_backoff_ms=courier_backoff_ms,
        courier_retry_backoff_max_ms=courier_backoff_max_ms,
        courier_ticket_ttl_ms=ticket_ttl_ms,
        kv_store_endpoint=store_endpoint,
        kv_store_endpoints=store_endpoints,
        # the fetch plane is how store-held pages restore locally
        prefix_fetch=bool(store_endpoint or store_endpoints))
    fleet_cfg.validate()
    plan = None
    if fault_plan:
        try:
            plan = FaultPlan(**_json.loads(fault_plan))
        except (TypeError, ValueError) as e:
            raise click.ClickException(f"bad --fault-plan JSON: {e}")
    params = None
    if param_seed >= 0:
        from ...models import init as model_init
        params = model_init(model_cfg, jax.random.PRNGKey(param_seed))
    elif weights_from_store:
        # bare-host bootstrap: the checkpoint arrives over the same
        # courier fabric the KV pages ride — chunk-CRC'd, end-to-end
        # verified, spool-resumable. A store that is down or does not
        # hold the name fails the BOOT loudly, naming the endpoint.
        if not (store_endpoint or store_endpoints):
            raise click.ClickException(
                "--weights-from-store needs --store-endpoint or "
                "--store-endpoints")
        import jax.numpy as jnp

        from ...serve.fleet.weights import WeightCourier, WeightShipError
        wc = WeightCourier(fleet_cfg, spool_dir=weights_spool)
        try:
            tree = wc.fetch(weights_name or model_name)
        except WeightShipError as e:
            raise click.ClickException(str(e))

        def _to_jax(node):
            if isinstance(node, dict):
                return {k: _to_jax(v) for k, v in node.items()}
            return jnp.asarray(node)

        params = _to_jax(tree)
    w = FleetWorker(replica_id, model_cfg, serve_cfg,
                    fleet_cfg=fleet_cfg, role=role, params=params,
                    seed=seed, fault_plan=plan)
    w.run_forever(host=host, port=port)


@app.command()
@click.option("--model", "model_name", default="gpt-125m",
              show_default=True, help="Model template name.")
@click.option("--artifact", default="",
              help="Checkpoint dir or exported weights file (tokenizer "
                   "source; fronts never load weights — replicas are "
                   "remote).")
@click.option("--front-id", default="", help="Stable front identity in "
              "the shared state store (empty = random).")
@click.option("--host", default="127.0.0.1", show_default=True)
@click.option("--port", default=0, show_default=True, type=int,
              help="0 binds an ephemeral port; the bound port is "
                   "printed as 'LLMCTL_FRONT_READY port=N front=ID'.")
@click.option("--replicas", default=1, show_default=True, type=int,
              help="Fleet size this front routes over (all remote).")
@click.option("--remote-replicas", default="", show_default=True,
              help="Comma-separated replica ids served by `llmctl "
                   "fleet worker` processes — for a stateless front "
                   "this must name EVERY replica.")
@click.option("--fleet-endpoint", "fleet_endpoints", multiple=True,
              help="replica=url courier/control endpoint map entries "
                   "(repeat per replica).")
@click.option("--state-store-dir", required=True,
              help="Shared file state store directory (stream logs + "
                   "router ledger journal; every front and the tier "
                   "must see the same path).")
@click.option("--max-batch-size", default=8, show_default=True, type=int)
@click.option("--max-seq-len", default=2048, show_default=True, type=int)
@kv_block_size_option
@click.option("--probe-interval", default=0.1, show_default=True,
              type=float, help="Supervisor poll cadence on this front "
              "(also the store heartbeat cadence).")
@click.option("--probe-failures", default=3, show_default=True, type=int)
@click.option("--remote-timeout", default=5.0, show_default=True,
              type=float)
@click.option("--max-pending", default=512, show_default=True, type=int)
@click.option("--stream-ttl-ms", default=60_000.0, show_default=True,
              type=float)
@click.option("--affinity-tokens", default=0, show_default=True,
              type=int, help="Prefix-affinity tokens (0 = pure "
              "least-outstanding-tokens — the HA default, since hot "
              "prefixes pin via the workers' own caches).")
@click.option("--courier-chunk-bytes", default=256 * 1024,
              show_default=True, type=int)
@click.option("--courier-retries", default=4, show_default=True,
              type=int)
@click.option("--courier-deadline-ms", default=100.0, show_default=True,
              type=float)
@click.option("--fault-plan", default="",
              help="JSON FaultPlan for deterministic chaos (testing).")
def front(model_name, artifact, front_id, host, port, replicas,
          remote_replicas, fleet_endpoints, state_store_dir,
          max_batch_size, max_seq_len, kv_block_size, probe_interval,
          probe_failures, remote_timeout, max_pending, stream_ttl_ms,
          affinity_tokens, courier_chunk_bytes, courier_retries,
          courier_deadline_ms, fault_plan):
    """Run ONE stateless fleet front as its own OS process.

    The HA front tier's unit (`llmctl serve start --fleet-fronts N`
    spawns these): an OpenAI-compatible HTTP/SSE front over all-remote
    replicas whose stream logs and router ledger live in the shared
    file state store — so killing this process mid-stream costs the
    client one reconnect (Last-Event-ID, to any sibling front), never
    a token. /health answers 503 until the front has attached to the
    store and read one supervisor snapshot."""
    import json as _json

    from ...config.presets import get_model_config
    from ...config.schema import (FleetConfig, ServeConfig,
                                  parse_fleet_endpoints)
    from ...serve.fleet.faults import FaultPlan
    from ...serve.fleet.front import run_front

    model_cfg = get_model_config(model_name)
    serve_cfg = ServeConfig(
        model=model_name, artifact=artifact, host=host, port=port,
        max_batch_size=max_batch_size,
        max_seq_len=min(max_seq_len, model_cfg.max_position_embeddings),
        kv_block_size=kv_block_size, dtype="float32")
    serve_cfg.validate()
    fleet_cfg = FleetConfig(
        replicas=replicas, remote_replicas=remote_replicas,
        fleet_endpoints=parse_fleet_endpoints(list(fleet_endpoints)),
        state_store="file", state_store_dir=state_store_dir,
        probe_interval_s=probe_interval, probe_failures=probe_failures,
        remote_timeout_s=remote_timeout, max_pending=max_pending,
        stream_log_ttl_ms=stream_ttl_ms,
        affinity_prefix_tokens=affinity_tokens,
        courier_chunk_bytes=courier_chunk_bytes,
        courier_max_retries=courier_retries,
        courier_chunk_deadline_ms=courier_deadline_ms)
    fleet_cfg.validate()
    plan = None
    if fault_plan:
        try:
            plan = FaultPlan(**_json.loads(fault_plan))
        except (TypeError, ValueError) as e:
            raise click.ClickException(f"bad --fault-plan JSON: {e}")
    run_front(model_cfg, serve_cfg, fleet_cfg,
              front_id=front_id or None, fault_plan=plan)


@app.command()
@click.option("--host", default="127.0.0.1", show_default=True)
@click.option("--port", default=0, show_default=True, type=int,
              help="0 binds an ephemeral port; the bound port is "
                   "printed as 'LLMCTL_STORE_READY port=N'.")
@click.option("--dram-mb", default=256.0, show_default=True, type=float,
              help="DRAM ring capacity, in MB of compressed frames "
                   "(LRU; overflow spills to --dir or drops the "
                   "oldest).")
@click.option("--dir", "spill_dir", default="", show_default=True,
              help="Disk-spill directory (empty = DRAM only).")
@click.option("--disk-mb", default=1024.0, show_default=True,
              type=float, help="Disk-spill capacity bound.")
@click.option("--ttl-ms", default=0.0, show_default=True, type=float,
              help="Expire entries nobody fetched for this long "
                   "(0 = keep until capacity pressure evicts).")
@click.option("--courier-codec", default="none", show_default=True,
              type=click.Choice(["none", "zlib", "delta-zlib"]),
              help="Codec newly-admitted frames are encoded with when "
                   "a client demotes raw pages ('none' stores zlib "
                   "anyway — a resident tier holding uncompressed "
                   "frames would waste its ring).")
@click.option("--courier-chunk-bytes", default=256 * 1024,
              show_default=True, type=int)
@click.option("--member-id", default="",
              help="This process's stable id in a REPLICATED store "
                   "tier (with --membership-dir). Attaching bumps the "
                   "tier epoch; a fenced or stale incarnation's "
                   "uploads are refused with a FATAL ack.")
@click.option("--membership-dir", default="",
              help="Shared directory holding the tier's fenced member "
                   "registry (every member must see the same path — "
                   "the SharedFileStateStore idiom). Members discover "
                   "each other's endpoints through it, so anti-entropy "
                   "needs no static --peer list.")
@click.option("--peer", "peers", multiple=True,
              help="Static peer member URL to anti-entropy against "
                   "(repeatable; usually unnecessary — the membership "
                   "registry advertises endpoints).")
@click.option("--sync-interval-ms", default=1000.0, show_default=True,
              type=float,
              help="Anti-entropy cadence: how often this member diffs "
                   "a peer's inventory and pulls what it lacks "
                   "(un-counted in the hit/serve ledgers).")
def store(host, port, dram_mb, spill_dir, disk_mb, ttl_ms,
          courier_codec, courier_chunk_bytes, member_id,
          membership_dir, peers, sync_interval_ms):
    """Run the fleet KV store as its own OS process — the networked
    KV fabric's hub.

    Serves the same tiered DRAM/disk page cache `--fleet-kv-store`
    embeds in a front, behind HTTP: replicas and fronts DEMOTE
    already-encoded courier frames here (per-frame CRC verified at
    admission), fetches replay them byte-identically through the
    caller's courier receiver, and checkpoints ship through the
    /store/weights/* surface so bare `--weights-from-store` workers
    bootstrap over the wire. Loses nothing on client death and no
    client loses correctness on ITS death — a dead store degrades
    every caller to plain re-prefill, counted."""
    from ...config.schema import FleetConfig
    from ...serve.fleet.store_service import StoreService

    cfg = FleetConfig(
        replicas=1, prefix_fetch=True, kv_store=True,
        kv_store_dram_mb=dram_mb, kv_store_dir=spill_dir,
        kv_store_disk_mb=disk_mb, kv_store_ttl_ms=ttl_ms,
        courier_codec=courier_codec,
        courier_chunk_bytes=courier_chunk_bytes)
    cfg.validate()
    # warm=False: the disk-tier scan happens behind the /health
    # readiness gate (503 "starting" until the frame index is warm) —
    # spawners poll that instead of sleeping
    StoreService(cfg, member_id=member_id,
                 membership_dir=membership_dir, peers=list(peers),
                 sync_interval_s=sync_interval_ms / 1e3,
                 warm=False).run_forever(host=host, port=port)


@app.command(name="ship-weights")
@click.option("--store-endpoint", required=True,
              help="Base URL of the `llmctl fleet store` service — "
                   "comma-separated member URLs for a replicated tier "
                   "(the ship fans out to every live member).")
@click.option("--write-ack", default=0, show_default=True, type=int,
              help="How many members must hold the complete payload "
                   "before the ship succeeds (0 = ALL live members — "
                   "the operator default: a ship that silently leaves "
                   "a member bare should fail loudly).")
@click.option("--model", "model_name", default="gpt-125m",
              show_default=True, help="Model template name.")
@click.option("--artifact", default="",
              help="Checkpoint dir or exported weights file to ship "
                   "(empty with --param-seed -1 errors — shipping "
                   "random weights must be asked for explicitly).")
@click.option("--name", "weights_name", default="",
              help="Name to register the checkpoint under (default: "
                   "the model name).")
@click.option("--param-seed", default=-1, show_default=True, type=int,
              help="Ship PRNG-initialised weights from this seed "
                   "instead of an artifact (cross-process determinism "
                   "for tests/dryrun).")
def ship_weights(store_endpoint, write_ack, model_name, artifact,
                 weights_name, param_seed):
    """Register a checkpoint in the store service over the wire.

    One immutable chunked payload under NAME: chunk-CRC'd in flight,
    end-to-end CRC at rest, upload-RESUMABLE (re-running after an
    interrupt ships only the chunks the service does not already
    hold). `llmctl fleet worker --weights-from-store` then bootstraps
    bare hosts from it — no shared artifact path anywhere."""
    import jax

    from ...config.presets import get_model_config
    from ...serve.fleet.weights import WeightCourier, WeightShipError

    model_cfg = get_model_config(model_name)
    if param_seed >= 0:
        from ...models import init as model_init
        params = model_init(model_cfg, jax.random.PRNGKey(param_seed))
    elif artifact:
        from ...config.schema import ServeConfig
        from ...serve.engine import InferenceEngine
        serve_cfg = ServeConfig(model=model_name, artifact=artifact)
        params, model_cfg, _ = InferenceEngine._load_params(
            model_cfg, serve_cfg, 0, serve_cfg.dtype)
    else:
        raise click.ClickException(
            "ship-weights needs --artifact or --param-seed")
    wc = WeightCourier(endpoint=store_endpoint, write_ack=write_ack)
    try:
        out = wc.ship(weights_name or model_name, params)
    except WeightShipError as e:
        raise click.ClickException(str(e))
    click.echo(f"weights {out['name']!r} registered on "
               f"{out['members']} member(s): {out['sent']} chunks "
               f"sent, {out['skipped']} already held "
               f"({out['total']} total)")
