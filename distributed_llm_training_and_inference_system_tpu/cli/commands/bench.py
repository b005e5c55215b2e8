"""`llmctl bench` — real benchmarks.

Un-stubs the entirely-"coming soon" reference bench command
(reference cli/commands/bench.py:13-75, SURVEY §2 row 19): kernels, e2e
train/serve, collectives, dataloader — every number measured on the live
backend.
"""

from __future__ import annotations

import json
import time

import click


from ...utils.timing import time_fn as _timed


def _open_chip_lock(path: str):
    """Open (creating if needed) the world-writable chip-lock file.

    ``os.open(..., 0o666)`` alone is not enough: the process umask
    (typically 022) strips the group/other WRITE bits at creation, so the
    next user on a shared host hits EACCES opening the lock O_RDWR — the
    exact failure the world-writable mode exists to prevent. chmod AFTER
    creation bypasses the umask; failure is ignored when the file already
    exists under another owner (they already widened it)."""
    import os
    fd = os.open(path, os.O_RDWR | os.O_CREAT, 0o666)
    try:
        os.chmod(path, 0o666)
    except OSError:
        pass
    return os.fdopen(fd, "w")


@click.group(name="bench", invoke_without_command=True)
@click.pass_context
def app(ctx):
    """Benchmarks (kernels, end-to-end, comms, dataloader)."""
    if ctx.invoked_subcommand is None:
        click.echo(ctx.get_help())


@app.command()
@click.option("--op", default="all", show_default=True,
              type=click.Choice(["attention", "flash", "matmul", "rmsnorm",
                                 "rope", "all"]))
@click.option("--seq-len", default=1024, show_default=True)
@click.option("--hidden", default=1024, show_default=True)
@click.option("--heads", default=8, show_default=True)
@click.option("--batch", default=4, show_default=True)
def kernels(op, seq_len, hidden, heads, batch):
    """Micro-benchmark core ops (parity: reference bench.py:13-33 flags)."""
    import jax
    import jax.numpy as jnp

    from ...models import layers

    D = hidden // heads
    key = jax.random.PRNGKey(0)
    results = {}

    if op in ("matmul", "all"):
        a = jax.random.normal(key, (seq_len * batch, hidden), jnp.bfloat16)
        w = jax.random.normal(key, (hidden, hidden), jnp.bfloat16)
        sec = _timed(jax.jit(lambda x, y: x @ y), a, w)
        results["matmul"] = {
            "time_ms": sec * 1e3,
            "tflops": 2 * a.shape[0] * hidden * hidden / sec / 1e12}

    if op in ("attention", "flash", "all"):
        shape = (batch, seq_len, heads, D)
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), shape,
                                     jnp.bfloat16) for i in range(3))
        pos = jnp.arange(seq_len, dtype=jnp.int32)[None].repeat(batch, 0)
        mask = layers.attention_mask(pos, pos)
        sec = _timed(jax.jit(
            lambda q, k, v: layers.dot_product_attention(q, k, v, mask)),
            q, k, v)
        results["attention_xla"] = {"time_ms": sec * 1e3}
        if jax.default_backend() == "tpu" and op in ("flash", "all"):
            from ...ops.attention import flash_attention
            sec_f = _timed(jax.jit(
                lambda q, k, v: flash_attention(q, k, v, causal=True)),
                q, k, v)
            results["attention_flash"] = {
                "time_ms": sec_f * 1e3,
                "speedup_vs_xla": sec / sec_f}

    if op in ("rmsnorm", "all"):
        x = jax.random.normal(key, (batch, seq_len, hidden), jnp.bfloat16)
        s = jnp.zeros((hidden,), jnp.bfloat16)
        sec = _timed(jax.jit(lambda x, s: layers.rms_norm(x, s)), x, s)
        results["rmsnorm"] = {"time_ms": sec * 1e3}

    if op in ("rope", "all"):
        x = jax.random.normal(key, (batch, seq_len, heads, D), jnp.bfloat16)
        pos = jnp.arange(seq_len, dtype=jnp.int32)[None].repeat(batch, 0)
        freqs = layers.rope_frequencies(D)
        sec = _timed(jax.jit(
            lambda x, p: layers.apply_rope(x, p, freqs)), x, pos)
        results["rope"] = {"time_ms": sec * 1e3}

    click.echo(json.dumps(results, indent=2))


@app.command()
@click.option("--model", "model_name", default="gpt-test", show_default=True)
@click.option("--mode", default="train", show_default=True,
              type=click.Choice(["train", "serve", "serve-load", "both"]))
@click.option("--steps", default=10, show_default=True)
@click.option("--batch", default=4, show_default=True)
@click.option("--seq-len", default=None, type=int)
@click.option("--prompt-len", default=128, show_default=True)
@click.option("--gen-len", default=64, show_default=True)
@click.option("--requests", default=8, show_default=True)
@click.option("--rps", default="2,8,32", show_default=True,
              help="serve-load: comma-separated offered requests/sec sweep.")
@click.option("--concurrency", default="4,16,64", show_default=True,
              help="serve-load: comma-separated closed-loop sweep.")
@click.option("--admission", default="ondemand", show_default=True,
              type=click.Choice(["ondemand", "reserve"]))
@click.option("--preemption", default="recompute", show_default=True,
              type=click.Choice(["recompute", "swap"]),
              help="serve-load: evicted-KV policy under ondemand.")
@click.option("--kv-blocks", default=0, show_default=True,
              help="serve-load: fixed KV pool size (0 = auto from budget).")
@click.option("--device-times/--no-device-times", default=True,
              show_default=True,
              help="serve-load: calibrate on-device prefill/decode times "
                   "and report ttft_device_ms (link RTT excluded).")
@click.option("--latency-dispatch-steps", default=0, show_default=True,
              type=int, help="serve-load: latency-adaptive short-dispatch "
                             "cap (0 disables).")
@click.option("--artifact", default="", help="serve-load: checkpoint dir or "
              "`llmctl export` file (pre-quantized exports load straight "
              "to device).")
@click.option("--quant", default="none", show_default=True,
              type=click.Choice(["none", "int8", "int4", "int4-awq"]),
              help="serve-load: weight quantization.")
@click.option("--kv-quant", "--serve-kv-quant", "kv_quant",
              default="none", show_default=True,
              type=click.Choice(["none", "fp", "int8", "int4"]),
              help="serve-load: KV page quantization ('fp' is an alias "
                   "for none — the A/B arm naming bench scripts use). "
                   "int4 packs two page slots per byte: 2x decode slots "
                   "per HBM byte over int8, 4x over bf16.")
@click.option("--slots", default=0, show_default=True, type=int,
              help="serve-load: decode slot count (max_batch_size); "
                   "0 = auto from --requests (capped at 16).")
@click.option("--pipelined/--no-pipelined", "pipelined", default=True,
              show_default=True,
              help="serve-load: pipelined decode dispatch (one un-fetched "
                   "dispatch in flight, chained on the device carry). "
                   "Default matches production serving (ON since round "
                   "5); pass --no-pipelined for the unpipelined control.")
@click.option("--int8-pallas/--no-int8-pallas", "int8_pallas",
              default=False, show_default=True,
              help="serve-load: route int8 decode matmuls through the "
                   "in-kernel-dequant Pallas kernel (A/B vs XLA's fused "
                   "dequant; see ServeConfig.int8_pallas_matmul).")
@click.option("--serve-max-retries", default=0, show_default=True, type=int,
              help="serve-load fleet: honor Retry-After on 429s with up "
                   "to this many resubmissions per request (0 = count "
                   "rejections as failures, the PR-2 behaviour); lets "
                   "saturation sweeps measure goodput under backpressure.")
@click.option("--serve-replicas", default=1, show_default=True, type=int,
              help="serve-load: drive a fleet of this many threaded "
                   "engine replicas through the serve/fleet router "
                   "instead of one engine; results gain the per-replica "
                   "requests/p99-TTFT/requeue breakdown.")
@click.option("--serve-disagg/--no-serve-disagg", default=False,
              show_default=True,
              help="serve-load fleet: disaggregated prefill/decode — the "
                   "first half of --serve-replicas take the prefill role, "
                   "the rest decode, and every sequence crosses the KV "
                   "handoff courier; results gain the per-phase TTFT/ITL "
                   "breakdown with handoff counts + stall percentiles.")
@click.option("--serve-courier-chaos", default=0.0, show_default=True,
              type=float,
              help="serve-load fleet: inject seeded courier chunk faults "
                   "at this rate (split evenly across drop/corrupt/"
                   "delay), with a 1 KiB chunk size so payloads span "
                   "many chunks — the resilience A/B: compare goodput "
                   "and transfer-stall percentiles against 0.0 (clean "
                   "link). Results always carry the courier section "
                   "(transfers/retries/aborts + p50/p99_transfer_ms).")
@click.option("--serve-courier-codec", default="none", show_default=True,
              type=click.Choice(["none", "zlib", "delta-zlib"]),
              help="serve-load fleet: courier wire codec A/B arm — "
                   "delta-zlib delta-encodes quantized KV page planes "
                   "then deflates per chunk (pipelined behind the "
                   "wire). Compare the courier section's bytes_wire / "
                   "bytes_raw / compression_ratio and transfer-ms "
                   "percentiles against none; combine with "
                   "--serve-disagg (handoff stall) or "
                   "--serve-hot-prefix (prefix-fetch latency).")
@click.option("--serve-hot-prefix", default=0, show_default=True,
              type=int,
              help="serve-load fleet: flash-crowd scenario — every "
                   "prompt shares a hot prefix of this many tokens "
                   "(tails random), so placements spilling off the "
                   "affinity owner exercise the fleet-global prefix "
                   "fetch; compare fleet prefill_tokens and the "
                   "prefix_fetch section against 0 (all-unique "
                   "prompts). 0 disables.")
@click.option("--serve-courier-zlib-level", default=-1, show_default=True,
              type=int,
              help="serve-load fleet: zlib level for the compressing "
                   "courier codecs and the tiered KV store's at-rest "
                   "frames (-1 = library default; 1 = fastest — the "
                   "right choice when frame replay competes with cheap "
                   "CPU prefill).")
@click.option("--serve-returning", default=0, show_default=True,
              type=int,
              help="serve-load fleet: returning-conversation scenario "
                   "(tiered fleet KV store) — this many multi-turn "
                   "conversations prefill a long history, go quiet "
                   "while filler traffic churns the KV pool past their "
                   "HBM residency, then return with the same history. "
                   "Runs a store-ON arm (evicted pages demote to the "
                   "host tier and the return turn restores them at "
                   "wire speed) AND a store-OFF recompute arm, "
                   "asserting the two produce token-identical output; "
                   "the headline is return-turn TTFT store-hit vs "
                   "recompute.")
@click.option("--serve-returning-history", default=96, show_default=True,
              type=int,
              help="Returning-conversation history length in tokens "
                   "(the shared prefix each conversation re-uses).")
@click.option("--serve-long-prompts", default=0, show_default=True,
              type=int,
              help="serve-load fleet: pipelined-prefill scenario — mix "
                   "this many long-context prompts into the short chat "
                   "traffic and run a pipelining-ON arm (the prompt is "
                   "split across the prefill pool, stage KV shipped "
                   "forward while the next chunk computes) against a "
                   "pipelining-OFF single-replica-prefill arm, plus a "
                   "chaos arm (stage kill + chunk faults, pipelining "
                   "on). Asserts token identity across all arms; the "
                   "headline is long-prompt TTFT vs stage count and "
                   "co-resident short-request TPOT p99 protection.")
@click.option("--serve-long-prompt-len", default=384, show_default=True,
              type=int,
              help="Long-context prompt length in tokens for "
                   "--serve-long-prompts.")
@click.option("--serve-scenario", default="", show_default=True,
              help="serve-load fleet: scenario matrix — comma-separated "
                   "names from {diurnal, flash-crowd, phase-shift, "
                   "returning-churn, long-context} or 'all'. Each cell "
                   "runs an autoscale-on/off A/B and reports per-SLO-"
                   "class TTFT/TPOT attainment, goodput under targets, "
                   "and the scaling events on the run timeline.")
@click.option("--serve-scenario-duration", default=10.0,
              show_default=True, type=float,
              help="serve-scenario: offered-load window per cell (s).")
@click.option("--serve-scenario-base-rps", default=3.0,
              show_default=True, type=float,
              help="serve-scenario: trough arrival rate.")
@click.option("--serve-scenario-peak-rps", default=12.0,
              show_default=True, type=float,
              help="serve-scenario: burst/peak arrival rate.")
@click.option("--serve-ttft-target-ms", default=2000.0,
              show_default=True, type=float,
              help="serve-scenario: interactive-class TTFT attainment "
                   "target (standard gets 3x; best-effort none).")
@click.option("--serve-stream/--no-serve-stream", default=False,
              show_default=True,
              help="serve-load fleet: streaming client mode — every "
                   "request is consumed as a live token stream off the "
                   "fleet stream hub; results gain the stream section "
                   "(streamed-token identity vs the final completion, "
                   "zero-gap/zero-dup assertion, per-token delivery-gap "
                   "percentiles). Combine with fault flags to measure "
                   "delivery jitter across crashes/migrations.")
def e2e(model_name, mode, steps, batch, seq_len, prompt_len, gen_len,
        requests, rps, concurrency, admission, kv_blocks, device_times,
        preemption, latency_dispatch_steps, artifact, quant, kv_quant,
        slots, pipelined, int8_pallas, serve_max_retries, serve_replicas,
        serve_disagg, serve_courier_chaos, serve_courier_codec,
        serve_courier_zlib_level, serve_hot_prefix, serve_returning,
        serve_returning_history, serve_long_prompts, serve_long_prompt_len,
        serve_scenario, serve_scenario_duration, serve_scenario_base_rps,
        serve_scenario_peak_rps, serve_ttft_target_ms, serve_stream):
    """End-to-end train step throughput / serve TTFT+throughput
    (parity: reference bench.py:35-49). ``serve-load`` runs open-loop
    (Poisson) and closed-loop sweeps with p50/p99 TTFT, per-token latency,
    goodput, and preemption counts (serve/loadgen.py) — the queueing
    regime the reference's scheduler could not survive (SURVEY §2.4.1)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ...config.presets import get_model_config
    from ...config.schema import OptimizerConfig, ParallelConfig, ServeConfig

    from ...utils.platform import device_summary

    cfg = get_model_config(model_name)
    on_tpu = jax.default_backend() == "tpu"
    seq_len = seq_len or min(1024 if on_tpu else 128,
                             cfg.max_position_embeddings)
    # every result names the device it ran on: a CPU run (tests, host
    # simulation) is wall clock of the CPU backend, never a chip number
    results = {"device": device_summary()}

    if mode in ("train", "both"):
        from ...exec.train_step import TrainState, make_train_step
        from ...models import init
        from ...models.gpt import flops_per_token

        par = ParallelConfig(micro_batch_size=batch, global_batch_size=batch,
                             activation_checkpoint="selective")
        step_fn, tx, _ = make_train_step(
            cfg, OptimizerConfig(lr=1e-4), par,
            attn_impl="flash" if on_tpu else "xla")
        state = TrainState.create(init(cfg, jax.random.PRNGKey(0)), tx)
        tokens = jnp.ones((batch, seq_len), jnp.int32)
        batch_d = {"tokens": tokens}
        state, _ = jax.block_until_ready(step_fn(state, batch_d))  # compile
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step_fn(state, batch_d)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        tok_s = steps * batch * seq_len / dt
        results["train"] = {
            "tokens_per_sec": tok_s,
            "step_ms": dt / steps * 1e3,
            "model_tflops_per_sec": tok_s * flops_per_token(cfg, seq_len) / 1e12,
        }

    if mode in ("serve", "both"):
        from ...serve import InferenceEngine, SamplingParams

        eng = InferenceEngine(cfg, ServeConfig(
            model=model_name, max_batch_size=min(requests, 8),
            max_seq_len=min(prompt_len + gen_len + 16,
                            cfg.max_position_embeddings),
            kv_block_size=64 if on_tpu else 16,
            dtype="bfloat16" if on_tpu else "float32"))
        rng = np.random.default_rng(0)
        prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size,
                                                 size=prompt_len)]
                   for _ in range(requests)]
        # warmup compile with one request
        eng.generate([prompts[0]], SamplingParams(temperature=0.0,
                                                  max_tokens=2))
        t0 = time.perf_counter()
        reqs = eng.generate(prompts, SamplingParams(temperature=0.0,
                                                    max_tokens=gen_len))
        dt = time.perf_counter() - t0
        ttfts = sorted(r.ttft_ms for r in reqs)
        total_tokens = sum(len(r.generated_tokens) for r in reqs)
        results["serve"] = {
            "p50_ttft_ms": ttfts[len(ttfts) // 2],
            "p99_ttft_ms": ttfts[-1],
            "tokens_per_sec": total_tokens / dt,
            "requests": requests,
        }

    if mode == "serve-load":
        from ...serve import InferenceEngine, SamplingParams
        from ...serve.loadgen import run_closed_loop, run_poisson

        def point_serve_cfg():
            return ServeConfig(
                model=model_name,
                max_batch_size=slots or min(max(requests, 8), 16),
                max_seq_len=min(prompt_len + gen_len + 16,
                                cfg.max_position_embeddings),
                kv_block_size=64 if on_tpu else 16,
                kv_num_blocks=kv_blocks,
                admission=admission, preemption=preemption,
                latency_dispatch_steps=latency_dispatch_steps,
                pipelined_decode=pipelined,
                int8_pallas_matmul=int8_pallas,
                artifact=artifact, quantization=quant,
                kv_quantization="none" if kv_quant == "fp" else kv_quant,
                dtype="bfloat16" if on_tpu else "float32")

        def fresh_engine():
            return InferenceEngine(cfg, point_serve_cfg())

        last_engine: list = []

        def warmed_fleet():
            """Fleet sweep point: each replica's programs are compiled
            BEFORE its threads start (stepping an engine from two threads
            is undefined), then counters reset and the fleet goes live."""
            import gc

            from ...config.schema import FleetConfig
            from ...serve.fleet import ServeFleet
            if last_engine:
                last_engine.pop().shutdown()
                gc.collect()
                jax.clear_caches()
            fc_kw = dict(replicas=serve_replicas,
                         courier_codec=serve_courier_codec)
            if serve_disagg and serve_replicas >= 2:
                n_pre = max(serve_replicas // 2, 1)
                fc_kw["roles"] = ",".join(
                    ["prefill"] * n_pre
                    + ["decode"] * (serve_replicas - n_pre))
            fault_plan = None
            if serve_courier_chaos > 0:
                # lossy-link A/B: small chunks so every payload spans
                # many frames, generous retry budget so the run measures
                # degradation (stall), not abort-to-re-prefill
                from ...serve.fleet import FaultPlan
                fc_kw.update(courier_chunk_bytes=1024,
                             courier_max_retries=12,
                             courier_retry_backoff_ms=0.5,
                             courier_retry_backoff_max_ms=8.0,
                             courier_chunk_deadline_ms=50.0)
                rate = serve_courier_chaos / 3.0
                fault_plan = FaultPlan(seed=0, chunk_drop_rate=rate,
                                       chunk_corrupt_rate=rate,
                                       chunk_delay_rate=rate,
                                       chunk_delay_ms=60.0)
            fleet = ServeFleet(cfg, point_serve_cfg(),
                               FleetConfig(**fc_kw),
                               fault_plan=fault_plan)
            for r in fleet.replicas:
                r.engine.generate([list(range(1, prompt_len + 1))],
                                  SamplingParams(temperature=0.0,
                                                 max_tokens=2))
                r.engine.reset_counters()
            fleet.start()
            last_engine.append(fleet)
            return fleet

        def warmed_engine():
            if serve_replicas > 1:
                return warmed_fleet()
            # jitted prefill/decode closures are PER-ENGINE (bound methods
            # key jax's trace cache), so every sweep point's engine must
            # compile its own programs BEFORE its timed window — a shared
            # warmup engine would leave compilation inside the measured
            # TTFT (round-3 review). The PREVIOUS point's engine must be
            # released first: dead engines' weights/pool/executables
            # otherwise stack up until the chip RESOURCE_EXHAUSTs.
            if last_engine:
                import gc
                last_engine.pop().release()
                gc.collect()        # the popped ref is gone — cycle dies now
                jax.clear_caches()  # whole-process: fine here, engines are
                #                     built strictly one-at-a-time in bench
            eng = fresh_engine()
            eng.generate([list(range(1, prompt_len + 1))],
                         SamplingParams(temperature=0.0, max_tokens=2))
            eng.reset_counters()
            last_engine.append(eng)
            return eng

        def engine_counters() -> dict:
            if not last_engine:
                return {}
            target = last_engine[0]
            engines = ([r.engine for r in target.replicas]
                       if hasattr(target, "router") else [target])
            keys = ("short_dispatches", "decode_steps",
                    "padded_slot_steps", "prefill_tokens", "preemptions",
                    "requeue_cached_tokens", "prefix_cached_tokens",
                    "prefix_fetched_tokens")
            stats = [e.stats() for e in engines]
            agg = {k: sum(s.get(k) or 0 for s in stats) for k in keys}
            # what the slot steps were (useful, overrun, prompt_wait and
            # empty add up to decode_steps x slots) and the tokens credited
            agg["slot_steps"] = {k: sum(s["slot_steps"][k] for s in stats)
                                 for k in stats[0]["slot_steps"]}
            B = engines[0].serve_cfg.max_batch_size
            agg["decode_slot_utilization"] = round(
                1.0 - agg["padded_slot_steps"]
                / max(agg["decode_steps"] * B, 1), 4)
            return agg

        results["serve_load"] = {"admission": admission,
                                 "preemption": preemption,
                                 "open_loop": [], "closed_loop": []}
        for r in [float(x) for x in str(rps).split(",") if x]:
            out = run_poisson(warmed_engine(), offered_rps=r,
                              num_requests=requests, prompt_len=prompt_len,
                              max_tokens=gen_len, seed=0,
                              max_retries=serve_max_retries,
                              hot_prefix_len=serve_hot_prefix,
                              stream=serve_stream,
                              device_times=device_times)
            s = out.summary()
            s["engine"] = engine_counters()
            results["serve_load"]["open_loop"].append(s)
        for c in [int(x) for x in str(concurrency).split(",") if x]:
            out = run_closed_loop(warmed_engine(), concurrency=c,
                                  num_requests=requests,
                                  prompt_len=prompt_len,
                                  max_tokens=gen_len, seed=0,
                                  max_retries=serve_max_retries,
                                  hot_prefix_len=serve_hot_prefix,
                                  stream=serve_stream,
                                  device_times=device_times)
            s = out.summary()
            s["concurrency"] = c
            # engine counters for the sweep point (short dispatches,
            # decode steps, padded-slot waste, preemptions) — the
            # adaptive-dispatch A/B was undiagnosable without them
            s["engine"] = engine_counters()
            results["serve_load"]["closed_loop"].append(s)

        if serve_returning > 0:
            # returning-conversation A/B (tiered fleet KV store): one
            # fleet per arm, KV pool sized so the filler phase MUST
            # recycle the conversations' cached pages — the store-on
            # arm then demotes them down a tier, the store-off arm
            # destroys them (recompute). Token identity between arms is
            # the degrade proof; TTFT split is the headline.
            from ...config.schema import FleetConfig
            from ...serve.fleet import ServeFleet
            from ...serve.loadgen import run_returning
            import gc
            if last_engine:
                eng = last_engine.pop()
                (eng.shutdown if hasattr(eng, "router")
                 else eng.release)()
                gc.collect()
                jax.clear_caches()
            hist = serve_returning_history
            B = slots or 4
            ps = 64 if on_tpu else 16
            per_req = -(-(hist + 4 + gen_len + 16) // ps)   # ceil pages
            blocks = (B + 1) * per_req + 2

            def returning_arm(store_on: bool):
                scfg = point_serve_cfg()
                scfg.max_batch_size = B
                scfg.max_seq_len = min(hist + 4 + gen_len + 16,
                                       cfg.max_position_embeddings)
                scfg.kv_num_blocks = blocks
                fleet = ServeFleet(
                    cfg, scfg,
                    FleetConfig(replicas=max(serve_replicas, 1),
                                kv_store=store_on,
                                kv_store_dram_mb=256.0,
                                courier_codec=serve_courier_codec,
                                courier_zlib_level=(
                                    serve_courier_zlib_level)),
                    supervise=False)
                import numpy as np
                for r in fleet.replicas:
                    warm_p = list(range(1, hist + 5))
                    r.engine.generate([warm_p],
                                      SamplingParams(temperature=0.0,
                                                     max_tokens=2))
                    # second pass over the same history compiles the
                    # TAIL-ONLY extend-prefill program (small suffix
                    # bucket) the store-hit return turn dispatches —
                    # compile time stays outside the timed window
                    r.engine.generate([warm_p[:hist] + [9, 8, 7, 6]],
                                      SamplingParams(temperature=0.0,
                                                     max_tokens=2))
                    # compile the page-restore scatter (the store-hit
                    # import path) OUTSIDE the timed window, same rule
                    # as the prefill/decode warmup above: write zeros
                    # into scratch page 0 at the bucket the scenario's
                    # fetches will hit (a documented no-op)
                    kvp = r.engine.kv

                    def zero_pages(bucket):
                        shape = (cfg.num_layers, bucket,
                                 cfg.num_kv_heads, ps, cfg.head_dim)
                        if kvp.quant_kind == "int4":
                            return {"values": np.zeros(
                                (*shape[:-2], shape[-2] // 2,
                                 shape[-1]), np.uint8),
                                "scale": np.zeros(shape[:-1],
                                                  np.float32)}
                        if kvp.quant_kind == "int8":
                            return {"values": np.zeros(shape, np.int8),
                                    "scale": np.zeros(shape[:-1],
                                                      np.float32)}
                        return np.zeros(shape, np.float32)

                    bucket = 1
                    while bucket <= 2 * per_req:
                        z = zero_pages(bucket)
                        kvp._write_pages_idx(
                            np.zeros(bucket, np.int32), z, z)
                        bucket <<= 1
                    r.engine.reset_counters()
                    r.engine.kv.flush_prefix_cache()
                fleet.start()
                try:
                    return run_returning(
                        fleet, conversations=serve_returning,
                        history_len=hist, tail_len=4,
                        max_tokens=gen_len,
                        filler_requests=max(2 * serve_returning,
                                            2 * B, 8),
                        filler_len=hist, seed=0)
                finally:
                    fleet.shutdown()
                    gc.collect()
                    jax.clear_caches()

            off = returning_arm(False)
            on = returning_arm(True)
            results["serve_load"]["returning"] = {
                "store_on": on.summary(),
                "store_off": off.summary(),
                # the degrade contract: store hits must never change
                # output — both arms' returning turns token-identical
                "token_identical": (
                    on.returning["token_lists"]
                    == off.returning["token_lists"]),
                "ttft_speedup_p50": (
                    round(off.returning["return_p50_ttft_ms"]
                          / on.returning["return_p50_ttft_ms"], 3)
                    if on.returning["return_p50_ttft_ms"]
                    and off.returning["return_p50_ttft_ms"] else None),
            }
            # token_lists proved identity; they are bulky and
            # uninteresting in the recorded artifact
            for arm in ("store_on", "store_off"):
                results["serve_load"]["returning"][arm].get(
                    "returning", {}).pop("token_lists", None)

        if serve_long_prompts > 0:
            # pipelined multi-replica prefill A/B: one fleet per arm,
            # same traffic. The ON arm splits each long prompt across
            # the prefill pool (stage KV pre-shipped forward while the
            # next chunk computes); the OFF arm prefills on one replica.
            # Both arms run a warm lap first (compiles every stage /
            # tail bucket the pipeline dispatches), then a measured lap
            # from a clean ledger. Token identity between arms is the
            # degrade proof; the headline is long-prompt TTFT plus
            # co-resident short-request TPOT p99 protection. A third
            # chaos arm (stage kill + chunk faults, pipelining on) must
            # collapse to single-replica prefill, counted, tokens still
            # identical.
            import gc

            from ...config.schema import FleetConfig
            from ...serve.fleet import FaultPlan, ServeFleet
            if last_engine:
                eng = last_engine.pop()
                (eng.shutdown if hasattr(eng, "router")
                 else eng.release)()
                gc.collect()
                jax.clear_caches()
            L = serve_long_prompt_len
            n_reps = max(serve_replicas, 2)
            chunk = 64
            pl_rps = [float(x) for x in str(rps).split(",") if x][0]
            min_on = max(prompt_len + 1, L // 2)

            def pipeline_arm(min_tokens, fault_plan=None, warm_lap=True):
                scfg = point_serve_cfg()
                scfg.max_seq_len = min(L + gen_len + 16,
                                       cfg.max_position_embeddings)
                scfg.chunked_prefill_tokens = chunk
                # interleave decode between chunks: the tax the pipeline
                # divides across stages (and the reason the OFF arm's
                # co-resident decodes stall for the whole prefill)
                scfg.prefill_budget_tokens = chunk
                fleet = ServeFleet(
                    cfg, scfg,
                    FleetConfig(replicas=n_reps, prefix_fetch=True,
                                pipeline_prefill_min_tokens=min_tokens,
                                pipeline_prefill_max_stages=min(n_reps, 4),
                                # cold-lap stage chunks pay XLA compiles
                                # (minutes on small CPU hosts); the default
                                # 30 s timeout would collapse every warm-up
                                # pipeline and leave the measured lap cold
                                pipeline_prefill_stage_timeout_ms=240_000.0,
                                courier_codec=serve_courier_codec,
                                courier_zlib_level=(
                                    serve_courier_zlib_level)),
                    fault_plan=fault_plan, supervise=False)
                for r in fleet.replicas:
                    for n in (L, prompt_len):
                        r.engine.generate(
                            [list(range(1, n + 1))],
                            SamplingParams(temperature=0.0, max_tokens=2))
                fleet.start()
                try:
                    def lap(seed):
                        return run_poisson(
                            fleet, offered_rps=pl_rps,
                            num_requests=requests,
                            prompt_len=prompt_len, max_tokens=gen_len,
                            seed=seed, max_retries=serve_max_retries,
                            long_prompts=serve_long_prompts,
                            long_prompt_len=L)
                    if warm_lap:
                        lap(0)
                        fleet.pipeline.reset_counters()
                        for r in fleet.replicas:
                            r.engine.reset_counters()
                            with r.engine.lock:
                                r.engine.kv.flush_prefix_cache()
                    return lap(1)
                finally:
                    fleet.shutdown()
                    gc.collect()
                    jax.clear_caches()

            off = pipeline_arm(0)
            on = pipeline_arm(min_on)
            # chaos arm: no warm lap (the injected crash fires exactly
            # once — a warm lap would absorb it; compile noise is fine
            # here, this arm measures correctness, not latency). The
            # crash is keyed on a pipeline STAGE request id (every stage
            # rid carries "::stage"), so the collapse path fires
            # deterministically no matter which replica the planner put
            # stage work on — crash_replica=0 only sometimes hit a
            # stage host.
            chaos = pipeline_arm(
                min_on, warm_lap=False,
                fault_plan=FaultPlan(seed=0, chunk_drop_rate=0.1,
                                     chunk_corrupt_rate=0.1,
                                     crash_request_substr="::stage",
                                     crash_request_after_steps=4))
            ref_tokens = off.pipeline.get("token_lists")
            pl = {
                "replicas": n_reps,
                "stages_planned": min(n_reps, 4),
                "long_prompts": serve_long_prompts,
                "long_prompt_len": L,
                "pipeline_on": on.summary(),
                "pipeline_off": off.summary(),
                "chaos": chaos.summary(),
                # the degrade contract: pipelining (and its collapse
                # path) must never change output
                "token_identical": (
                    on.pipeline.get("token_lists") == ref_tokens),
                "chaos_token_identical": (
                    chaos.pipeline.get("token_lists") == ref_tokens),
            }
            on_t = on.pipeline.get("p50_long_ttft_ms")
            off_t = off.pipeline.get("p50_long_ttft_ms")
            if on_t and off_t:
                pl["long_ttft_speedup_p50"] = round(off_t / on_t, 3)
            on_d = on.pipeline.get("p99_short_tpot_ms")
            off_d = off.pipeline.get("p99_short_tpot_ms")
            if on_d and off_d:
                pl["short_tpot_p99_ratio_on_vs_off"] = round(
                    on_d / off_d, 3)
            # token_lists proved identity; bulky in the artifact
            for arm in ("pipeline_on", "pipeline_off", "chaos"):
                pl[arm].get("pipeline", {}).pop("token_lists", None)
            results["serve_load"]["pipeline"] = pl

        if serve_scenario:
            # scenario matrix (elastic autoscaler + SLO tiers): per
            # cell, an autoscale-on/off A/B over the SAME seeded
            # offered plan. The ON arm may grow the fleet toward the
            # ceiling under pressure and drain-retire back on the fade
            # (store flush — no re-prefill); the OFF arm holds the
            # provisioned size. Per-class attainment is the headline;
            # token identity over commonly-completed requests is the
            # degrade proof (admission shedding differs by design).
            import gc

            from ...config.schema import FleetConfig
            from ...serve.fleet import ServeFleet
            from ...serve.loadgen import SCENARIOS, run_scenario
            if last_engine:
                eng = last_engine.pop()
                (eng.shutdown if hasattr(eng, "router")
                 else eng.release)()
                gc.collect()
                jax.clear_caches()
            names = [s.strip() for s in str(serve_scenario).split(",")
                     if s.strip()]
            if names == ["all"]:
                names = list(SCENARIOS)
            bad = [n for n in names if n not in SCENARIOS]
            if bad:
                raise click.UsageError(
                    f"unknown --serve-scenario {bad}; "
                    f"choose from {SCENARIOS}")
            ttft_targets = {"interactive": serve_ttft_target_ms,
                            "standard": serve_ttft_target_ms * 3}

            def scenario_arm(name, autoscale_on):
                L = (serve_long_prompt_len if name == "long-context"
                     else 0)
                scfg = point_serve_cfg()
                scfg.max_seq_len = min(
                    max(prompt_len * 3, L, prompt_len * 5)
                    + 2 * gen_len + 16, cfg.max_position_embeddings)
                base = max(serve_replicas, 2)
                # the A/B toggles the WHOLE new subsystem: the OFF arm
                # is the pre-elastic fleet (fixed size, class-blind
                # admission, no TTFT guard); the ON arm adds elastic
                # scaling AND the SLO tier plane. max_pending is bound
                # identically in both arms so saturation actually
                # sheds — the arms differ only in WHO gets shed: the
                # ON arm reserves nearly the whole queue for
                # interactive (standard/best-effort take the
                # Retry-After), which is what holds interactive TTFT
                # under the burst on a fixed CPU budget.
                fleet = ServeFleet(
                    cfg, scfg,
                    FleetConfig(
                        replicas=base,
                        kv_store=True,
                        max_pending=96,
                        autoscale=autoscale_on,
                        # floor at the provisioned size: elasticity is
                        # proven upward (grow into the burst, retire
                        # the extra on the fade) — letting the fleet
                        # dip below base during a lull just re-buys
                        # the capacity mid-window
                        autoscale_min_replicas=base,
                        autoscale_max_replicas=base + 1,
                        autoscale_up_queue_per_replica=2.0,
                        autoscale_down_queue_per_replica=0.25,
                        # at the 0.05s probe these put scale decisions
                        # on an O(seconds) cadence — pressure must
                        # hold 0.5s to act, then 2s of quiet before
                        # the next move. Tighter windows flap: buy a
                        # replica into a blip, retire one 1s later
                        autoscale_hysteresis_polls=10,
                        autoscale_cooldown_polls=40,
                        priority_headroom_requests=(
                            80 if autoscale_on else 0),
                        interactive_ttft_target_ms=(
                            serve_ttft_target_ms if autoscale_on
                            else 0.0),
                        probe_interval_s=0.05,
                        courier_codec=serve_courier_codec))
                # supervised (background poll thread), unlike the other
                # serve-load arms: a scale-up's warm-compile runs on the
                # supervisor thread, so the open-loop arrival clock and
                # the replica step threads never stall behind XLA
                for r in fleet.replicas:
                    # pow-2 warm lap covers every prompt bucket the
                    # scenario geometries dispatch (incl. the phase
                    # shift's 3x prompts and long-context mix)
                    n = 8
                    while n <= min(512, scfg.max_seq_len - 4):
                        r.engine.generate(
                            [list(range(1, n + 1))],
                            SamplingParams(temperature=0.0,
                                           max_tokens=2))
                        n <<= 1
                    r.engine.reset_counters()
                    with r.engine.lock:
                        r.engine.kv.flush_prefix_cache()
                fleet.start()
                # the standby pool's XLA compiles must not contend
                # with serving inside the measured window (this host
                # may be a single core); a production spare pre-warms
                # before entering rotation for the same reason
                fleet.wait_warm_spares()
                try:
                    return run_scenario(
                        fleet, scenario=name,
                        duration_s=serve_scenario_duration,
                        base_rps=serve_scenario_base_rps,
                        peak_rps=serve_scenario_peak_rps,
                        prompt_len=prompt_len, max_tokens=gen_len,
                        long_prompt_len=serve_long_prompt_len,
                        seed=0, max_retries=serve_max_retries,
                        ttft_targets_ms=ttft_targets)
                finally:
                    fleet.shutdown()
                    gc.collect()
                    jax.clear_caches()

            matrix = {}
            for name in names:
                off = scenario_arm(name, False)
                on = scenario_arm(name, True)
                tl_on = on.scenario.pop("token_lists", [])
                tl_off = off.scenario.pop("token_lists", [])
                both = [i for i in
                        range(min(len(tl_on), len(tl_off)))
                        if tl_on[i] is not None
                        and tl_off[i] is not None]
                cell = {
                    "autoscale_on": on.summary(),
                    "autoscale_off": off.summary(),
                    "token_identical": all(
                        tl_on[i] == tl_off[i] for i in both),
                    "common_completed": len(both),
                }
                ia_on = on.scenario.get("classes", {}).get(
                    "interactive", {})
                ia_off = off.scenario.get("classes", {}).get(
                    "interactive", {})
                if ia_on.get("attainment") is not None \
                        and ia_off.get("attainment") is not None:
                    cell["interactive_attainment_on"] = \
                        ia_on["attainment"]
                    cell["interactive_attainment_off"] = \
                        ia_off["attainment"]
                # scale-down store-flush credit: pages the retiring
                # replica pushed into the fleet store — the ~0
                # re-prefill proof for elastic shrink
                downs = [e for e in on.scenario.get(
                    "scaling", {}).get("events", [])
                    if e.get("kind") == "scale_down"]
                if downs:
                    cell["scale_down_flushed_pages"] = sum(
                        e.get("flushed_pages", 0) for e in downs)
                matrix[name] = cell
            results["serve_load"]["scenario_matrix"] = matrix

    click.echo(json.dumps(results, indent=2))


@app.command(name="kv-decode")
@click.option("--slots", default=16, show_default=True,
              help="Decode slots (batch rows).")
@click.option("--kv-heads", default=32, show_default=True)
@click.option("--head-dim", default=128, show_default=True)
@click.option("--q-heads", default=0, show_default=True,
              help="Query heads (0 = same as --kv-heads).")
@click.option("--page-size", default=64, show_default=True)
@click.option("--context", default=512, show_default=True,
              help="Live tokens per slot at measurement.")
@click.option("--layers", default=32, show_default=True,
              help="Layer count for the per-model traffic ledger "
                   "(the timed kernel runs ONE layer; ms/step scales).")
@click.option("--steps", default=50, show_default=True)
@click.option("--write-mode", default="paged", show_default=True,
              type=click.Choice(["paged", "scatter"]),
              help="KV append path: staged tile or page merge (fused "
                   "quantize-on-write for int8) vs per-row scatter.")
def kv_decode(slots, kv_heads, head_dim, q_heads, page_size, context,
              layers, steps, write_mode):
    """Quantized-KV decode A/B: one layer's paged attention + KV append
    per step over bf16 pages, int8 QuantPages, and packed-int4 Int4Pages
    — same shapes (the round-5-named 7B 16-slot wall,
    BASELINE.md:205-218, plus the round-14 int4 capacity arm). Reports
    ms/step per mode, an HBM-traffic ledger (bytes the decode step must
    stream per token), and a CAPACITY ledger (bytes/slot at this
    context, slots/GB) — the Mooncake-style fleet-economics number:
    decode replicas needed scale with bytes per resident slot, and int4
    must show >= 1.9x decode slots per HBM byte over int8."""
    import jax
    import jax.numpy as jnp

    from ...ops.paged_attention import (
        Int4Pages, QuantPages, _window_tile_rows, paged_attention,
        quantize_kv_token, write_token_to_pages, write_window_to_pages)
    from ...ops.quantization import pack_int4_rows, quantize_int4_rows

    q_heads = q_heads or kv_heads
    B, Nkv, Nq, D, PS = slots, kv_heads, q_heads, head_dim, page_size
    maxP = (context + PS - 1) // PS
    NP = B * maxP + 1
    on_tpu = jax.default_backend() == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    key = jax.random.PRNGKey(0)
    kf = jax.random.normal(key, (NP, Nkv, PS, D), dtype)
    tables = jnp.arange(1, NP, dtype=jnp.int32).reshape(B, maxP)
    lengths = jnp.full((B,), context, jnp.int32)
    q = jax.random.normal(key, (B, Nq, D), dtype)
    new_kv = jax.random.normal(key, (B, 1, Nkv, D), dtype)

    def build(kind):
        if kind == "int8":
            qv, sc = quantize_kv_token(kf)
            return QuantPages(qv, sc)
        if kind == "int4":
            qv, sc = quantize_int4_rows(kf)
            return Int4Pages(pack_int4_rows(qv, axis=-2), sc)
        return jnp.array(kf)     # copy: the step donates its page buffer

    def step(pages, q, new_kv):
        if write_mode == "paged":
            pages = write_window_to_pages(pages, new_kv, tables,
                                          lengths - 1)
        else:
            pages = write_token_to_pages(pages, new_kv[:, 0], tables,
                                         lengths - 1)
        out = paged_attention(q, pages, pages, tables, lengths)
        return pages, out

    # bytes one K-or-V token row costs in HBM per mode (scales included:
    # fp32 per-(token, kv-head) for both quantized modes — the int4 win
    # is the D/2 packed nibbles)
    row_bytes = {
        "bf16": Nkv * D * jnp.dtype(dtype).itemsize,
        "int8": Nkv * (D + 4),
        "int4": Nkv * (D // 2 + 4),
    }
    results = {}
    for name in ("bf16", "int8", "int4"):
        pages = build(name)
        fn = jax.jit(step, donate_argnums=(0,))
        pages, out = jax.block_until_ready(fn(pages, q, new_kv))  # compile
        t0 = time.perf_counter()
        for _ in range(steps):
            pages, out = fn(pages, q, new_kv)
        jax.block_until_ready(out)
        sec = (time.perf_counter() - t0) / steps
        # per-token HBM ledger at this shape, whole model (layers x):
        # attention must stream every live K/V row once; the append
        # stages (reads, then writes back) the row's sublane tile of a
        # full-precision pool, its whole page of quantized pages
        row = row_bytes[name]
        read_attn = 2 * B * context * row
        if write_mode == "paged":
            staged = _window_tile_rows(pages, 1) or PS
            write_rw = 2 * B * 2 * staged * row    # K+V staging gather+scatter
        else:
            write_rw = 2 * B * row                 # K+V row scatter (ideal)
        # capacity ledger: a resident decode slot at this context costs
        # K+V x layers x context rows — the fleet sizes decode replica
        # counts off slots/GB (Mooncake: serving is KV-capacity-bound)
        slot_bytes = 2 * layers * context * row
        results[name] = {
            "ms_per_layer_step": round(sec * 1e3, 3),
            "est_model_decode_ms": round(sec * 1e3 * layers, 1),
            "hbm_ledger_per_step_mb": {
                "attn_kv_read": round(layers * read_attn / 1e6, 4),
                "kv_append_rw": round(layers * write_rw / 1e6, 4),
            },
            "capacity": {
                "bytes_per_slot": slot_bytes,
                "mb_per_slot": round(slot_bytes / 1e6, 3),
                "slots_per_gb": round(1e9 / slot_bytes, 2),
            },
        }
    b, i8 = (results["bf16"]["ms_per_layer_step"],
             results["int8"]["ms_per_layer_step"])
    results["int8_vs_bf16_speedup"] = round(b / i8, 3) if i8 else None
    i4 = results["int4"]["ms_per_layer_step"]
    results["int4_vs_bf16_speedup"] = round(b / i4, 3) if i4 else None
    # the acceptance number: decode slots per HBM byte, int4 over int8
    # (pure layout arithmetic at this shape — row bytes, not wall time)
    results["int4_vs_int8_slots_per_hbm_byte"] = round(
        results["int8"]["capacity"]["bytes_per_slot"]
        / results["int4"]["capacity"]["bytes_per_slot"], 3)
    results["int4_vs_bf16_slots_per_hbm_byte"] = round(
        results["bf16"]["capacity"]["bytes_per_slot"]
        / results["int4"]["capacity"]["bytes_per_slot"], 3)

    # courier wire-codec A/B (serve/fleet/transport.py): what one
    # extracted page payload of each KV kind costs ON THE WIRE under
    # none / zlib / delta-zlib, plus host encode+frame and
    # decompress+decode time. Pages here are ACTIVATION-SHAPED (channel-
    # static structure + a few massive stable outlier channels + AR(1)
    # per-token drift — the correlation CacheGen exploits), not iid
    # noise, which would make every codec look useless.
    import numpy as np

    from ...serve.fleet.transport import (ChunkReassembler, encode_payload,
                                          make_chunks)
    rng = np.random.default_rng(0)
    n_pages = min(maxP, 8)
    *lead, _PS, _D = shp = (2, n_pages, max(Nkv // 8, 1), PS, D)

    def activation_planes():
        base = rng.standard_normal((*lead, 1, _D)).astype(np.float32)
        hot = rng.choice(_D, size=max(_D // 16, 1), replace=False)
        base[..., hot] *= 10.0
        drift = np.zeros(shp, np.float32)
        drift[..., 0, :] = 0.1 * rng.standard_normal((*lead, _D))
        for t in range(1, _PS):
            drift[..., t, :] = (0.99 * drift[..., t - 1, :]
                                + 0.1 * rng.standard_normal((*lead, _D)))
        return base + drift

    def extract_payload(kind):
        k, v = activation_planes(), activation_planes()

        def quant(x, levels):
            scale = np.abs(x).max(-1) / levels + 1e-9
            return (np.clip(np.round(x / scale[..., None]), -levels,
                            levels).astype(np.int8), scale)
        if kind == "bf16":
            pages = {"k": k, "v": v}
        elif kind == "int8":
            pages = {}
            for name, x in (("k", k), ("v", v)):
                q8, sc = quant(x, 127)
                pages[name] = {"values": q8,
                               "scale": sc.astype(np.float32)}
        else:                                  # packed int4
            pages = {}
            for name, x in (("k", k), ("v", v)):
                q4, sc = quant(x, 7)
                packed = ((q4[..., 0::2, :] & 0xF)
                          | ((q4[..., 1::2, :] & 0xF) << 4)).astype(
                              np.uint8)
                pages[name] = {"values": packed,
                               "scale": sc.astype(np.float32)}
        return {"pages": {**pages, "num_pages": n_pages},
                "positions": n_pages * PS, "last_token": 1}

    codec_ab: dict = {}
    for kind in ("bf16", "int8", "int4"):
        payload = extract_payload(kind)
        arms = {}
        for codec in ("none", "zlib", "delta-zlib"):
            t0 = time.perf_counter()
            manifest, blob = encode_payload(payload, codec=codec)
            chunks = make_chunks("bench", manifest, blob, 256 * 1024)
            enc_ms = (time.perf_counter() - t0) * 1e3
            wire = sum(len(c.data) for c in chunks)
            t0 = time.perf_counter()
            r = ChunkReassembler(len(chunks))
            for c in chunks:
                r.add(c)
            r.payload()
            dec_ms = (time.perf_counter() - t0) * 1e3
            arms[codec] = {
                "bytes_raw": manifest["nbytes"],
                "bytes_wire": wire,
                "compression_ratio": round(manifest["nbytes"]
                                           / max(wire, 1), 3),
                "encode_ms": round(enc_ms, 3),
                "decode_ms": round(dec_ms, 3),
            }
        codec_ab[kind] = arms
    results["courier_codec_ab"] = codec_ab
    results["delta_zlib_vs_none_int8_wire"] = round(
        codec_ab["int8"]["none"]["bytes_wire"]
        / max(codec_ab["int8"]["delta-zlib"]["bytes_wire"], 1), 3)
    results["write_mode"] = write_mode
    results["backend"] = jax.default_backend()
    click.echo(json.dumps(results, indent=2))


@app.command()
@click.option("--pattern", default="all", show_default=True,
              type=click.Choice(["allreduce", "all_gather", "reduce_scatter",
                                 "ppermute", "all_to_all", "all"]))
@click.option("--size-mb", default=16.0, show_default=True, type=float)
@click.option("--devices", "n_devices", default=None, type=int,
              help="Mesh size (default: all available).")
def comms(pattern, size_mb, n_devices):
    """Measure real collectives over the live mesh
    (parity: reference bench.py:51-64, which was a stub; the reference's
    comm 'tuner' was simulated, autotuning.py:222-245)."""
    import jax
    from jax.sharding import Mesh

    from ...comms.bench import bench_all, bench_collective

    devs = jax.devices()[:n_devices] if n_devices else jax.devices()
    if len(devs) < 2:
        raise click.ClickException(
            "need >=2 devices for collectives; run under "
            "JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8")
    mesh = Mesh(devs, ("x",))
    if pattern == "all":
        rows = bench_all(mesh, "x", size_mb)
    else:
        rows = [bench_collective(mesh, "x", pattern, size_mb)]
    click.echo(json.dumps(rows, indent=2))


@app.command()
@click.option("--path", default="synthetic", show_default=True,
              help="'synthetic', a shard dir, or a remote scheme:// URI.")
@click.option("--batch", default=8, show_default=True)
@click.option("--seq-len", default=1024, show_default=True)
@click.option("--batches", default=50, show_default=True)
@click.option("--prefetch", default=0, show_default=True,
              help="PrefetchLoader depth (0 = synchronous).")
@click.option("--workers", default=2, show_default=True,
              help="Remote shard download pool size.")
@click.option("--step-ms", default=0.0, show_default=True,
              help="Simulated device step between fetches: reports loader "
                   "STALL (time the step loop waits on data) — ~0 means "
                   "the loader keeps up at this step width.")
def dataloader(path, batch, seq_len, batches, prefetch, workers, step_ms):
    """Dataset streaming throughput + stall under a simulated step cadence
    (parity: reference bench.py:66-75)."""
    from ...io.data import PrefetchLoader, make_dataset

    ds = make_dataset(path, batch, seq_len, vocab_size=50304, seed=0,
                      num_workers=workers, prefetch=prefetch)
    next(ds)  # warm
    stall0 = ds.stall_seconds if isinstance(ds, PrefetchLoader) else None
    t0 = time.perf_counter()
    stall_sync = 0.0
    for _ in range(batches):
        f0 = time.perf_counter()
        next(ds)
        stall_sync += time.perf_counter() - f0
        if step_ms > 0:
            time.sleep(step_ms / 1e3)      # the simulated device step
    dt = time.perf_counter() - t0
    toks = batches * batch * seq_len
    out = {
        "tokens_per_sec": toks / dt,
        "batches_per_sec": batches / dt,
        "MB_per_sec": toks * 4 / dt / 1e6,
    }
    if isinstance(ds, PrefetchLoader):
        out["stall_ms_per_batch"] = (ds.stall_seconds - stall0) / batches * 1e3
    else:
        out["fetch_ms_per_batch"] = stall_sync / batches * 1e3
    if hasattr(ds, "close"):    # PrefetchLoader closes its inner dataset
        ds.close()
    if step_ms > 0:
        out["step_ms_simulated"] = step_ms
    click.echo(json.dumps(out, indent=2))


@app.command()
@click.option("--spec", required=True, type=click.Path(exists=True),
              help="Battery spec: TOML/JSON listing [[item]] entries with "
                   "name, cmd, timeout (see docs/USER_GUIDE.md).")
@click.option("--out", "out_dir", default="battery_results",
              show_default=True, help="Per-item logs + manifest dir.")
@click.option("--resume/--no-resume", default=True, show_default=True,
              help="Skip items whose log already records rc=0.")
@click.option("--wait-for-chip/--no-wait-for-chip", default=True,
              show_default=True,
              help="Probe until the TPU backend answers before each item "
                   "(and re-probe after a failure — an unreachable chip "
                   "parks the battery instead of burning the remaining "
                   "items).")
@click.option("--probe-interval", default=420, show_default=True,
              help="Seconds between chip probes while waiting.")
@click.option("--max-probes", default=200, show_default=True,
              help="Give up after this many failed probes.")
@click.option("--guard/--no-guard", "tpu_guard", default=True,
              show_default=True,
              help="--no-guard runs items without requiring a TPU backend "
                   "(CPU smoke tests of the battery machinery).")
@click.option("--dry-run", is_flag=True,
              help="Parse and validate the spec, list the items and which "
                   "would be skipped by --resume, run nothing.")
@click.option("--chip-lock", default="/tmp/llmctl_chip.lock",
              show_default=True,
              help="flock() this path for the duration of the battery so "
                   "concurrent batteries serialize instead of sharing the "
                   "chip mid-measurement (a concurrent probe contaminated "
                   "one round-5 A/B with 27 s step outliers). '' disables.")
def battery(spec, out_dir, resume, wait_for_chip, probe_interval,
            max_probes, tpu_guard, dry_run, chip_lock):
    """Run a config-listed measurement battery with per-item timeouts,
    resume-from-partial, and chip-outage parking.

    Promotes the round-4 pending-runner pattern (probe every few minutes
    through a chip outage, then run batteries in value order) from a
    hand-written recovery script into the CLI: the next outage costs
    waiting hours, not a rewrite. The reference has no bench runner at
    all (its bench command is a stub, reference cli/commands/bench.py:
    35-49); per-item timeouts follow this repo's bench.py watchdog — a
    hung dispatch records a self-describing failure instead of hanging
    the battery.
    """
    import shlex
    import subprocess
    import sys
    from pathlib import Path

    spec_path = Path(spec)
    if spec_path.suffix == ".json":
        items_spec = json.loads(spec_path.read_text())
    else:
        from ...utils.tomlio import loads_toml
        items_spec = loads_toml(spec_path.read_text())
    items = items_spec.get("item") or items_spec.get("items") or []
    if not items:
        raise click.ClickException(f"{spec}: no [[item]] entries")
    # spec-level [env] table: exported to every item's subprocess. The
    # compile cache is not declared there: cli/main.py has already put
    # its directory in this process's environment, which items inherit.
    import os as _os
    spec_env = {str(k): str(v)
                for k, v in (items_spec.get("env") or {}).items()}
    item_env = None
    if spec_env:
        item_env = {**_os.environ, **spec_env}
    def plan_item(i, it):
        """Validated (argv, timeout_s, done-under-resume) for one item —
        the ONE place the resume predicate lives, so --dry-run's preview
        cannot drift from what the run loop actually skips."""
        if not it.get("name") or not it.get("cmd"):
            raise click.ClickException(
                f"{spec}: item {i} needs 'name' and 'cmd'")
        cmd = it["cmd"]
        try:
            argv = shlex.split(cmd) if isinstance(cmd, str) else \
                [str(a) for a in cmd]
            timeout_s = float(it.get("timeout", 900))
        except ValueError as e:
            raise click.ClickException(
                f"{spec}: item {i} ({it['name']!r}): {e}")
        prior = manifest["items"].get(it["name"], {})
        # resume keys on (name, cmd): an edited item is a DIFFERENT
        # measurement — its stale rc=0 must not stand in for the new one
        done = (resume and prior.get("rc") == 0
                and prior.get("cmd") == argv)
        return argv, timeout_s, done

    out = Path(out_dir)
    manifest_path = out / "battery_manifest.json"
    manifest = {"spec": str(spec_path), "items": {}}
    if resume and manifest_path.exists():
        try:
            manifest = json.loads(manifest_path.read_text())
        except json.JSONDecodeError:
            pass
        if not isinstance(manifest, dict):
            manifest = {"spec": str(spec_path)}
        manifest.setdefault("items", {})

    if dry_run:
        # validate + preview only: no output dir, no subprocesses
        for i, it in enumerate(items):
            argv, timeout_s, done = plan_item(i, it)
            click.echo(f"{'skip' if done else 'run '}  {it['name']}  "
                       f"(timeout {timeout_s:.0f}s)  "
                       f"{' '.join(argv[:6])}{' ...' if len(argv) > 6 else ''}")
        if spec_env:
            click.echo("env: " + ", ".join(f"{k}={v}"
                                           for k, v in spec_env.items()))
        return
    out.mkdir(parents=True, exist_ok=True)

    def probe_chip() -> bool:
        """True when the ACTIVE backend is TPU. The probe runs in a child
        that has exited before any item starts: this parent must never
        touch jax itself (a process that has holds the chip, and every
        item's child would then fail or hang), and a hung jax.devices()
        is bounded by the child's own timeout."""
        code = ("import sys, jax; "
                "sys.exit(0 if jax.default_backend() == 'tpu' else 1)")
        try:
            return subprocess.run(
                [sys.executable, "-c", code], timeout=90,
                stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL).returncode == 0
        except subprocess.TimeoutExpired:
            return False

    def wait_chip() -> bool:
        if not tpu_guard:
            return True
        for attempt in range(1, max_probes + 1):
            if probe_chip():
                return True
            if not wait_for_chip or attempt == max_probes:
                return False
            click.echo(f"chip probe {attempt}/{max_probes} failed; "
                       f"sleeping {probe_interval}s", err=True)
            time.sleep(probe_interval)
        return False

    # validate the WHOLE spec before any item runs (and before the lock
    # wait, which can be hours) — a malformed item at position 9 must
    # not surface after 8 items of chip time
    plans = [plan_item(i, it) for i, it in enumerate(items)]

    lock_fh = None
    if chip_lock:
        # machine-global measurement mutex: the chip (and the host's
        # wall clock, which the kernel costings difference) must be
        # quiet during a battery — waiting here is always cheaper than
        # re-running a contaminated A/B. O_CREAT + world-writable mode
        # so a lock file created by another user on a shared host still
        # opens (a plain open('w') raised PermissionError and killed
        # the battery the mutex exists to protect)
        import fcntl
        lock_fh = _open_chip_lock(chip_lock)
        try:
            fcntl.flock(lock_fh, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError:
            click.echo(f"waiting for chip lock {chip_lock} "
                       "(another battery is running)...", err=True)
            fcntl.flock(lock_fh, fcntl.LOCK_EX)

    try:
        ran = skipped = failed = 0
        parked = False
        for it, (argv, timeout_s, done) in zip(items, plans):
            name = it["name"]
            if done:
                click.echo(f"=== {name}: already done (rc=0), skipping ===")
                skipped += 1
                continue
            if not wait_chip():
                parked = True
                click.echo(f"=== {name}: chip unavailable — battery parked "
                           "(resume with the same command) ===", err=True)
                break
            log_path = out / f"{name}.log"
            click.echo(f"=== {name} (timeout {timeout_s:.0f}s) ===")
            t0 = time.time()
            with open(log_path, "w") as log:
                try:
                    rc = subprocess.run(argv, stdout=log,
                                        stderr=subprocess.STDOUT,
                                        env=item_env,
                                        timeout=timeout_s).returncode
                except subprocess.TimeoutExpired:
                    rc = -9
                    log.write(f"\nbattery watchdog: item exceeded "
                              f"{timeout_s:.0f}s and was killed\n")
                except FileNotFoundError as e:
                    rc = 127
                    log.write(f"\n{e}\n")
            dt = time.time() - t0
            with open(log_path, "r+b") as log:
                # a killed item's stdout can end mid-line — keep the rc
                # marker on its own line so log parsers see it
                log.seek(0, 2)
                if log.tell() > 0:
                    log.seek(-1, 2)
                    if log.read(1) != b"\n":
                        log.write(b"\n")
                log.write(f"rc={rc}\n".encode())
            # bounded tail: a verbose 40-min item can write a huge log —
            # don't load it all just to echo three lines
            with open(log_path, "rb") as log:
                log.seek(0, 2)
                log.seek(max(log.tell() - 4096, 0))
                tail = log.read().decode(errors="replace").splitlines()[-4:-1]
            for line in tail:
                click.echo(f"  {line}")
            manifest["items"][name] = {"rc": rc, "seconds": round(dt, 1),
                                       "cmd": argv, "log": str(log_path)}
            manifest_path.write_text(json.dumps(manifest, indent=2))
            if rc == 0:
                ran += 1
            else:
                failed += 1
                click.echo(f"  item {name} rc={rc}", err=True)
        click.echo(json.dumps({"ran": ran, "skipped": skipped,
                               "failed": failed, "parked": parked,
                               "manifest": str(manifest_path)}))
        if parked:
            # distinct from item failure: nothing is wrong with the battery,
            # the chip never answered — wrappers should retry, not give up
            raise SystemExit(2)
        if failed:
            raise SystemExit(1)
    finally:
        if lock_fh is not None:
            # explicit release: a SystemExit traceback held by the
            # caller (test runners, wrappers) keeps this frame —
            # and with it the flock'd fd — alive, deadlocking the
            # next battery in the same process
            lock_fh.close()

