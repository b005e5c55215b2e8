"""The serving runner: one process holds the chip and runs the server users
run (``serve.server.InferenceServer``: aiohttp front, engine thread,
scheduler, paged cache, programs, kernels); a child that never imports JAX
sends the traffic over HTTP and stamps it (``benchmark/loadgen.py``).

Order of a run: weights made on the device from the seed in one jitted call
-> server up on a localhost port -> 4 seeded prompts served and
teacher-forced through the plain reference (``correct``) -> one request per
prefill bucket the traffic can reach -> the traffic's own ``warmup_s``
unmeasured -> the window. Set-up ends where the window starts.
"""

from __future__ import annotations

import asyncio
import json
import math
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from importlib import import_module
from pathlib import Path

import numpy as np

from benchmark import harness, traffic as traffic_mod
from benchmark.reference import dense_decoder

ROOT = Path(__file__).resolve().parents[2]
CHECK_PROMPTS, CHECK_PROMPT_TOKENS, CHECK_NEW_TOKENS = 4, 128, 16
# A served token's reference logit may lie this many reference-logit
# standard deviations under the reference's largest. The engine computes in
# bfloat16 (8 bits of mantissa) and rounds the residual stream in every one
# of its layers; where the two largest logits are closer than that rounding,
# the engine's argmax is the reference's runner-up. Measured on the chip at
# these widths (PR 24): the worst of 64 served tokens lay 0.107 under the
# reference's largest at a logit standard deviation of 1.26 (0.085 std), and
# PR 22 saw near-ties of 0.03 at 0.9. 0.25 std is three times the worst seen.
# A token chosen by another function of the input is a draw from the other
# 32,000 and lies about 4 std down (the largest of 32,000 normal draws):
# with the reference's rope base wrong by 100x all 64 served tokens left its
# argmax and the worst lay 7.8 (6.2 std) down; with a wrong norm epsilon 8.6
# (PR 24, chip call 3).
CHECK_TOLERANCE_STD = 0.25


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post(url: str, body: dict, timeout: float = 600.0) -> dict:
    req = urllib.request.Request(
        url + "/v1/completions", json.dumps(body).encode(),
        {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


class Served:
    """The server under test, up on a localhost port, with the benchmark's
    two hooks on the engine's callbacks: how many tokens each streamed batch
    carried, and how each request ended. (A streamed chunk carries text, and
    the byte tokenizer drops ids above 255 from it, so the client cannot
    count tokens itself.)"""

    def __init__(self, config: dict, seed: int):
        import jax
        import jax.numpy as jnp
        schema = import_module(f"{harness.PKG}.config.schema")
        gpt = import_module(f"{harness.PKG}.models.gpt")
        server_mod = import_module(f"{harness.PKG}.serve.server")

        self.config = config
        self.model_cfg = schema.ModelConfig.from_dict(
            harness.model_dict(config))
        self.serve_cfg = schema.ServeConfig(
            model=config["name"], host="127.0.0.1", port=_free_port(),
            **config["serve"])
        dtype = jnp.dtype(self.serve_cfg.dtype)
        t0 = time.monotonic()
        self.params = jax.jit(
            lambda key: gpt.init(self.model_cfg, key, dtype))(
                jax.random.PRNGKey(seed % (2 ** 31 - 1)))
        jax.block_until_ready(self.params)
        self.init_s = time.monotonic() - t0
        self.server = server_mod.InferenceServer(
            self.model_cfg, self.serve_cfg, params=self.params)
        self.batch_sizes: dict = {}
        self.finished: dict = {}
        engine = self.server.engine
        on_token, on_finish = engine.on_token, engine.on_finish

        def token_hook(req, tokens):
            self.batch_sizes.setdefault(req.request_id, []).append(
                len(tokens))
            on_token(req, tokens)

        def finish_hook(req):
            self.finished[req.request_id] = (
                len(req.generated_tokens), req.finish_reason)
            on_finish(req)

        engine.on_token, engine.on_finish = token_hook, finish_hook
        self.url = f"http://127.0.0.1:{self.serve_cfg.port}"
        self._loop = asyncio.new_event_loop()
        self._up = threading.Event()
        self._error: BaseException | None = None
        self._thread = threading.Thread(target=self._serve, daemon=True,
                                        name="bench-http")
        self._thread.start()
        if not self._up.wait(120) or self._error:
            raise RuntimeError(f"server did not come up: {self._error}")

    def _serve(self) -> None:
        asyncio.set_event_loop(self._loop)
        try:
            runner = self._loop.run_until_complete(self.server.start_async())
        except BaseException as e:       # reported by the constructor
            self._error = e
            self._up.set()
            return
        self._up.set()
        self._loop.run_forever()
        self._loop.run_until_complete(runner.cleanup())

    def stats(self) -> dict:
        with self.server.engine.lock:
            return self.server.engine.stats()

    def close(self) -> None:
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=30)
        self.server.stop_engine()

    # -- set-up steps --------------------------------------------------------

    def check_against_reference(self, seed: int, config: dict | None = None
                                ) -> dict:
        """Serve CHECK_PROMPTS seeded prompts greedily, teacher-force prompt
        and served tokens through the plain reference, and hold every served
        token's reference logit to the reference's largest. (``config``
        gives the reference another configuration than the server's: how
        one shows that the check fails when it should.)"""
        rng = np.random.default_rng([seed, 1])
        vocab = self.model_cfg.vocab_size
        gaps, std_sum, early = [], 0.0, 0
        for _ in range(CHECK_PROMPTS):
            prompt = rng.integers(258, vocab, CHECK_PROMPT_TOKENS).tolist()
            out = _post(self.url, {"prompt": prompt, "temperature": 0.0,
                                   "max_tokens": CHECK_NEW_TOKENS})
            served = out["choices"][0]["token_ids"]
            early += len(served) < CHECK_NEW_TOKENS
            n = len(served)
            lg = np.asarray(dense_decoder.logits(
                self.params, prompt + served[:-1], config or self.config,
                positions=range(len(prompt) - 1, len(prompt) - 1 + n)))
            gaps.extend((lg.max(-1) - lg[np.arange(n), served]).tolist())
            std_sum += float(lg.std())
        std = std_sum / CHECK_PROMPTS
        tol = CHECK_TOLERANCE_STD * std
        return {"ok": bool(max(gaps) <= tol), "worst_gap": max(gaps),
                "tol": tol, "logit_std": std, "stopped_early": early,
                "tokens": len(gaps),
                "tokens_off_the_reference_argmax": sum(g > 0 for g in gaps)}

    def prefill_buckets(self, lo: int, hi: int) -> list:
        """Prompt lengths that between them touch every dense prefill bucket
        prompts of lo..hi tokens can reach (the engine pads a prompt to a
        multiple of ``prefill_chunk``, itself a multiple of the page)."""
        c = self.serve_cfg
        chunk = max(c.prefill_chunk, c.kv_block_size)
        chunk = math.ceil(chunk / c.kv_block_size) * c.kv_block_size
        first, last = math.ceil(lo / chunk), math.ceil(hi / chunk)
        return [min(k * chunk, hi) for k in range(first, last + 1)]

    def warm(self, traffic: dict, seed: int) -> None:
        """One request per reachable prefill bucket, long enough to run the
        decode program: every shape the window uses has then compiled."""
        rng = np.random.default_rng([seed, 2])
        spec = traffic["prompt_tokens"]
        lo, hi = (spec["value"],) * 2 if spec["dist"] == "fixed" else (
            spec["min"], spec["max"])
        for n in self.prefill_buckets(lo, hi):
            _post(self.url, {
                "prompt": rng.integers(258, self.model_cfg.vocab_size,
                                       n).tolist(),
                "temperature": 0.0, "max_tokens": 16})

    # -- the window ----------------------------------------------------------

    def drive(self, traffic_path: str, seed: int, seconds: float,
              trace: bool, rate: float | None = None) -> dict:
        """Start the load generator's child, wait out its warm-up, measure
        the window, wait for the drain, and return the stamps joined with
        the engine's token counts and counter deltas."""
        traffic = traffic_mod.load(traffic_path)
        warm_s = float(traffic.get("warmup_s", 0.0))
        with harness.scratch_dir("bench_loadgen_") as tmp:
            out = os.path.join(tmp, "loadgen.json")
            cmd = [sys.executable, "-m", "benchmark.loadgen",
                   "--traffic", str(traffic_path), "--seed", str(seed),
                   "--seconds", str(seconds), "--url", self.url,
                   "--vocab", str(self.model_cfg.vocab_size),
                   "--out", out]
            if rate is not None:
                cmd += ["--rate", repr(rate)]
            child = subprocess.Popen(cmd, cwd=ROOT, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, text=True)
            try:
                if child.stdout.readline().strip() != "READY":
                    raise RuntimeError("the load generator did not start")
                start_at = time.monotonic() + 0.5
                child.stdin.write(f"{start_at!r}\n")
                child.stdin.flush()
                win0, win1 = start_at + warm_s, start_at + warm_s + seconds
                time.sleep(max(win0 - time.monotonic(), 0.0))
                before, t_before = self.stats(), time.monotonic()
                # trace a stretch that starts a second into the window
                time.sleep(min(1.0, seconds / 4))
                with harness.Trace(trace) as tr:
                    t_before_tr = self.stats()
                    time.sleep(min(harness.TRACE_SECONDS, seconds / 2))
                    t_after_tr = self.stats()
                time.sleep(max((win0 + win1) / 2 - time.monotonic(), 0.0))
                mid = self.stats()
                time.sleep(max(win1 - time.monotonic(), 0.0))
                after = self.stats()
                child.wait(timeout=float(traffic.get("drain_s", 20.0)) + 60)
            finally:
                if child.poll() is None:
                    child.kill()
                    child.wait()
            if child.returncode != 0:
                raise RuntimeError(
                    f"load generator exited with {child.returncode}")
            with open(out) as f:
                stamps = json.load(f)
        for rec in stamps["records"]:
            n, reason = self.finished.get(rec["id"], (None, None))
            rec["tokens"] = n
            rec["engine_finish_reason"] = reason
            rec["batch_sizes"] = self.batch_sizes.get(rec["id"], [])
        return {"window": (win0, win1), "setup_end": t_before,
                "stamps": stamps, "traffic": traffic,
                "stats": {"before": before, "mid": mid, "after": after},
                "trace": tr.result,
                "trace_listing": tr.listing,
                "trace_stats": {"before": t_before_tr, "after": t_after_tr}}


def measure(served: Served, cell: dict, traffic_path: str, seed: int,
            seconds: float, trace: bool, t_process_start: float,
            device: dict) -> dict:
    """Check, warm and drive a server that is up; the raw run."""
    check = served.check_against_reference(seed)
    print(f"[bench] reference check {check}", file=sys.stderr)
    harness.mark("reference check", t_process_start)
    served.warm(traffic_mod.load(traffic_path), seed)
    harness.mark("prefill buckets warm", t_process_start)
    raw = served.drive(traffic_path, seed, seconds, trace)
    compiled = (raw["stats"]["after"]["compiled_programs"]["total"]
                - raw["stats"]["before"]["compiled_programs"]["total"])
    print(f"[bench] programs compiled inside the window: {compiled}",
          file=sys.stderr)
    raw.update({
        "kind": "serve", "config": served.config, "cell": cell,
        "check": check, "device": device, "chips": cell["chips"],
        "setup_s": raw["setup_end"] - t_process_start,
        "weights_init_s": served.init_s,
        "compiled_in_window": compiled,
        "serve_cfg": {"decode_steps_per_dispatch":
                      served.serve_cfg.latency_dispatch_steps
                      or served.serve_cfg.decode_steps_per_dispatch,
                      "max_batch_size": served.serve_cfg.max_batch_size},
        "memory_peak_bytes": harness.memory_peak_bytes(cell["chips"]),
    })
    return raw


def run(cell: dict, config: dict, traffic_path: str, seed: int,
        seconds: float, trace: bool, t_process_start: float,
        require_tpu: bool = True) -> dict:
    """One run of a serving cell. ``require_tpu=False`` is the tests'
    rehearsal of the control flow on the CPU at a tiny size; what it returns
    is never printed as a result."""
    device = harness.start(cell["chips"], require_tpu)
    harness.mark("imports and device", t_process_start)
    served = Served(config, seed)
    harness.mark(f"weights ({served.init_s:.1f}s) and server up",
                 t_process_start)
    try:
        return measure(served, cell, traffic_path, seed, seconds, trace,
                       t_process_start, device)
    finally:
        served.close()
