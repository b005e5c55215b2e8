#!/usr/bin/env python3
"""The quickest proof that `llmctl serve` and `llmctl train` still start on
the chip: both main paths, through the entry points a user would call, at
the full width of a model the repo supports (random weights from the seed).

    python chip_smoke.py              one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4    the four-chip paths ONLY: fsdp x tp
                                      training against one device, and
                                      tensor-parallel serving against tp=1
                                      (over HTTP as users run it, reported;
                                      the engine in float32, judged)

One chip, in this order (each phase is a child process that has exited
before the next starts; this parent never imports jax, so it never holds
the chip):

  kernels   every Pallas kernel of the main path once beside its XLA
            reference, largest difference held to a bf16 tolerance — the
            only place a COMPILED kernel's result is checked (the tests
            run interpret mode)
  serve     `cli.main serve start --model gpt-1b`, defaults otherwise;
            requests (a)-(f) over HTTP; /health must show no engine error;
            then 8 seeded replies of the engine (temperature 0.8, top-k 40,
            top-p 0.9, a seed a request) against the direct reference that
            samples with fold_in(PRNGKey(seed), context length); with
            `--parent DIR` (the parent commit, unpacked by `git archive`)
            also greedy and seeded replies of 8 prompts x 64 tokens at
            mistral-7b-16l, this tree against that one, token for token;
            then four prompts served to a BUSY engine (17 of 32 slots
            resident, mistral-7b's widths, 4 layers, float32: they ride the
            decode dispatches) and again to the drained one (the cold
            program): equal first tokens and equal 32 greedy tokens; and
            the same at Xing4.0's widths (latent attention, 2 layers, 9 of
            16 slots) behind a document's cached pages (the suffix program)
            and at Kimi-Linear's widths (one period K K K *, 65 of 128
            slots) from position 0 (the chunk and final-chunk programs);
            then JoyAI-LLM-Flash's widths (latent attention, the dense and
            one expert layer, 128 of 256 experts, the prediction module,
            float32) DRAFTING for itself on constructed weights on which
            every draft stands and on seeded ones: 2.0 and ~1.0 tokens a
            slot-step, streams equal to plain greedy decoding's
  train     `cli.main train launch --model gpt-750m --max-steps 8`,
            sequence 2048, micro-batch 4, flash attention, fused AdamW
  launcher  `train launch --restart-on-failure 1 --max-steps 2` at
            gpt-test size: the SPAWNED child and the flags the launcher
            hands it (not full width — it tests process start-up)

Every phase prints which implementation each hot op resolved to (read
from the `impl ...` log lines of the process that made the choice), the
seconds spent compiling (JAX's own compile log) and seconds per request or
step. A phase that resolved a hot op to a reference or interpret
implementation on the chip fails.

The LAST line of stdout is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
with the device as the child that held the chip saw it. Exit code 0 only
with "ok": true; with no TPU it fails at the first phase.

CHIP_SMOKE_REHEARSAL=1 (an environment variable, not an option) runs the
same control flow at gpt-test size so it can be rehearsed on the CPU; a
rehearsal always ends `"ok": false` and a non-zero exit.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PKG = "distributed_llm_training_and_inference_system_tpu"
CLI = [sys.executable, "-m", f"{PKG}.cli.main"]
OUT = ROOT / "chiprun_out" / "chip_smoke"      # logs (small; git-ignored)
SCRATCH = ROOT / ".chip_smoke_scratch"         # checkpoints; removed again
REHEARSAL = os.environ.get("CHIP_SMOKE_REHEARSAL") == "1"
PHASE_ENV = "CHIP_SMOKE_PHASE"                 # set for a child of this file

# implementations that mean "the kernel was swapped for its reference"
REFERENCE_IMPLS = ("gather", "xla", "jnp", "numpy", "xla-dequant")
# ... except where the main path's own choice IS compiled XLA code
XLA_BY_DESIGN = {
    "rms_norm": "the main path fuses RMSNorm in XLA; the Pallas kernel is "
                "`llmctl tune kernels`' target, checked in the kernels phase",
    "prefill_attention": "a cold prompt's dense prefill attends over its own "
                         "[1, bucket] cache with XLA's dot-product "
                         "attention; the repo has no kernel for that path",
}


class SmokeFailure(Exception):
    pass


# the device as the last child that reported one saw it (the final JSON
# carries it even when a phase fails)
DEVICE = {"platform": None, "kind": None, "count": 0}


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# parent side: children, logs, HTTP
# ---------------------------------------------------------------------------

def child_env(cache_dir: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT) + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["JAX_LOG_COMPILES"] = "1"      # "Finished XLA compilation of ..."
    env["PYTHONUNBUFFERED"] = "1"
    return env


def run_child(name: str, cmd: list, env: dict, timeout: float) -> str:
    """Run one child to its end, output to OUT/<name>.log; returns the log
    text. Raises on a non-zero exit or a timeout."""
    log = OUT / f"{name}.log"
    t0 = time.monotonic()
    with open(log, "w") as fh:
        proc = subprocess.Popen(cmd, env=env, cwd=ROOT, stdout=fh,
                                stderr=subprocess.STDOUT)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise SmokeFailure(f"{name}: no end after {timeout:.0f}s "
                               f"(log: {log})")
    text = log.read_text(errors="replace")
    for rec in smoke_records(text, "device"):
        DEVICE.update(rec)
    if rc != 0:
        raise SmokeFailure(f"{name}: exit code {rc} after "
                           f"{time.monotonic() - t0:.0f}s; log tail:\n"
                           + text[-3000:])
    return text


def smoke_records(text: str, kind: str) -> list:
    """The `SMOKE <kind> {json}` lines a child of this file printed."""
    out = []
    for line in text.splitlines():
        if line.startswith(f"SMOKE {kind} "):
            out.append(json.loads(line[len(f"SMOKE {kind} "):]))
    return out


_COMPILE_RE = re.compile(r"Finished XLA compilation of (.+?) in ([0-9.eE+-]+) sec")
_IMPL_RE = re.compile(r"\bimpl (\w+)=([\w-]+)(?: \((.*)\))?$")
_DEVICE_RE = re.compile(r"device: platform=(\w+) kind='([^']*)' count=(\d+)")


def compile_seconds(text: str) -> tuple[float, int]:
    """Seconds JAX spent compiling (or loading from the persistent cache),
    from its own log. A process that configured logging prints each
    record twice (jax's handler and the root's): the duration is logged to
    the nanosecond, so equal (program, duration) pairs are one event."""
    events = {m.groups() for m in _COMPILE_RE.finditer(text)}
    return sum(float(secs) for _, secs in events), len(events)


def impl_lines(text: str) -> list:
    seen, out = set(), []
    for line in text.splitlines():
        m = _IMPL_RE.search(line.rstrip())
        if m and m.groups() not in seen:
            seen.add(m.groups())
            out.append(m.groups())
    return out


def require_tile_write(text: str, rows: int, what: str) -> None:
    """Fail unless the log holds ``impl window_page_write=tiles`` for a
    window of ``rows`` rows: ``what`` did not stage sublane tiles."""
    if not any(op == "window_page_write" and impl == "tiles"
               and (detail or "").startswith(f"T={rows} ")
               for op, impl, detail in impl_lines(text)):
        raise SmokeFailure(f"{what} did not stage tiles: no line `impl "
                           f"window_page_write=tiles (T={rows} ...)` in the "
                           "log")


def report_impls(phase: str, text: str, required: tuple, on_tpu: bool,
                 allowed: dict | None = None) -> None:
    """Print every implementation choice of a phase and fail it when, on
    the chip, a hot op resolved to a reference or interpret
    implementation (``allowed``: op -> reason such a choice is this
    phase's design and only has to be printed)."""
    allowed = {**XLA_BY_DESIGN, **(allowed or {})}
    impls = impl_lines(text)
    bad = []
    for op, impl, detail in impls:
        note = ""
        if on_tpu and (impl.endswith("interpret") or impl in REFERENCE_IMPLS):
            if op in allowed:
                note = f"   [by design: {allowed[op]}]"
            else:
                bad.append(f"{op}={impl}")
                note = "   [FAIL: not a compiled kernel]"
        say(f"  {phase}: impl {op}={impl}"
            + (f" ({detail})" if detail else "") + note)
    missing = [op for op in required
               if not any(op == i[0] for i in impls)]
    if missing:
        raise SmokeFailure(f"{phase}: no implementation line for "
                           f"{missing} — the op did not run")
    if bad:
        raise SmokeFailure(f"{phase}: resolved to a reference or interpret "
                           f"implementation on the chip: {bad}")


def compile_cache_entries(path: str) -> int:
    try:
        return sum(1 for e in os.scandir(path) if e.is_file())
    except FileNotFoundError:
        return 0


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, body: dict | None = None, timeout: float = 600):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


class Server:
    """`cli.main serve start ...` as a child, stopped on exit."""

    def __init__(self, name: str, args: list, env: dict):
        self.name, self.port = name, free_port()
        self.log = OUT / f"{name}.log"
        self._fh = open(self.log, "w")
        self.proc = subprocess.Popen(
            CLI + ["serve", "start", "--host", "127.0.0.1",
                   "--port", str(self.port)] + args,
            env=env, cwd=ROOT, stdout=self._fh, stderr=subprocess.STDOUT)
        self.base = f"http://127.0.0.1:{self.port}"

    def wait_ready(self, timeout: float) -> float:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"{self.name}: server exited {self.proc.returncode} "
                    "before it was ready; log tail:\n" + self.text()[-3000:])
            try:
                status, _ = http_json(f"{self.base}/health", timeout=5)
                if status == 200:
                    return time.monotonic() - t0
            except (urllib.error.URLError, OSError, ValueError):
                pass
            time.sleep(1.0)
        raise SmokeFailure(f"{self.name}: not ready after {timeout:.0f}s; "
                           "log tail:\n" + self.text()[-3000:])

    def text(self) -> str:
        if not self._fh.closed:
            self._fh.flush()
        return self.log.read_text(errors="replace")

    def complete(self, prompt: list, max_tokens: int, **extra) -> dict:
        t0 = time.monotonic()
        status, body = http_json(f"{self.base}/v1/completions", {
            "prompt": prompt, "max_tokens": max_tokens, "temperature": 0.0,
            **extra})
        if status != 200:
            raise SmokeFailure(f"{self.name}: HTTP {status}: {body}")
        choice = body["choices"][0]
        tokens = choice["token_ids"]
        if len(tokens) != max_tokens and choice["finish_reason"] == "length":
            raise SmokeFailure(f"{self.name}: asked {max_tokens} tokens, "
                               f"got {len(tokens)}")
        if len(tokens) != max_tokens:
            say(f"  note: {len(tokens)}/{max_tokens} tokens, finish_reason="
                f"{choice['finish_reason']} (random weights drew EOS)")
        body["_seconds"] = time.monotonic() - t0
        return body

    def stream(self, prompt: list, max_tokens: int) -> tuple[int, str, float]:
        t0 = time.monotonic()
        req = urllib.request.Request(
            f"{self.base}/v1/completions",
            data=json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                             "temperature": 0.0, "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        chunks, finish, done = 0, None, False
        with urllib.request.urlopen(req, timeout=600) as r:
            if r.status != 200:
                raise SmokeFailure(f"{self.name}: stream HTTP {r.status}")
            for raw in r:
                line = raw.decode().strip()
                if not line.startswith("data: "):
                    continue
                if line == "data: [DONE]":
                    done = True
                    break
                ev = json.loads(line[6:])
                chunks += 1
                finish = ev["choices"][0].get("finish_reason") or finish
        if not done or chunks == 0:
            raise SmokeFailure(f"{self.name}: stream ended without [DONE] "
                               f"({chunks} chunks)")
        return chunks, finish, time.monotonic() - t0

    def health(self) -> dict:
        status, body = http_json(f"{self.base}/health", timeout=30)
        body["_status"] = status
        return body

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._fh.close()


def device_from_log(text: str) -> dict:
    m = _DEVICE_RE.search(text)
    if not m:
        raise SmokeFailure("the child printed no `device: platform=...` line")
    return {"platform": m.group(1), "kind": m.group(2),
            "count": int(m.group(3))}


def prompt_tokens(seed: int, n: int, vocab: int) -> list:
    """Deterministic token ids in [1000, vocab) — clear of the byte
    tokenizer's specials — from a tiny LCG (no numpy in the parent)."""
    lo = min(1000, vocab // 2)
    out, x = [], seed * 2654435761 % 2**32 or 1
    for _ in range(n):
        x = (x * 1664525 + 1013904223) % 2**32
        out.append(lo + x % (vocab - lo))
    return out


# ---------------------------------------------------------------------------
# phases, one chip
# ---------------------------------------------------------------------------

def phase_kernels(env: dict) -> dict:
    say("== kernels: each Pallas kernel beside its XLA reference ==")
    t0 = time.monotonic()
    text = run_child("kernels", [sys.executable, str(ROOT / "chip_smoke.py")],
                     {**env, PHASE_ENV: "kernels"}, timeout=900)
    [device] = smoke_records(text, "device")
    on_tpu = device["platform"] == "tpu"
    for rec in smoke_records(text, "kernel"):
        if "normalised" not in rec:     # a record of another kind of check
            rest = {k: v for k, v in rec.items() if k != "name"}
            say(f"  kernel {rec['name']}: {rest}")
            continue
        say(f"  kernel {rec['name']}: max_abs_diff {rec['max_abs_diff']:.3e} "
            f"(reference max {rec['ref_max']:.3e}; normalised "
            f"{rec['normalised']:.3e} <= {rec['tol']})")
    for rec in smoke_records(text, "packer"):
        say(f"  data packer: {rec['impl']} ({rec['detail']})")
        if on_tpu and rec["impl"] != "native":
            raise SmokeFailure("data packer fell back to numpy on the chip "
                               f"machine: {rec['detail']}")
    # (PR 50) the one-token update of the state pools is the kernel at both
    # mixers' widths (ops/ssm.py step_pools picks by what it can see)
    updates = [(impl, detail) for op, impl, detail in impl_lines(text)
               if op == "ssm_decode"]
    say(f"  kernels: impl ssm_decode={updates}")
    if on_tpu and (len(updates) < 2
                   or any(impl != "pallas" for impl, _ in updates)):
        raise SmokeFailure("kernels: the state pools' decode step is not "
                           f"ssm_decode=pallas at both widths: {updates}")
    secs, n = compile_seconds(text)
    say(f"  kernels: {n} programs compiled in {secs:.1f}s; phase "
        f"{time.monotonic() - t0:.1f}s")
    return device


def phase_serve(env: dict, device: dict) -> None:
    model = "gpt-test" if REHEARSAL else "gpt-1b"
    say(f"== serve: cli.main serve start --model {model} ==")
    on_tpu = device["platform"] == "tpu"
    vocab = 256 if REHEARSAL else 50304
    # rehearsal: gpt-test holds 128 positions, so pages of 16 and short
    # prompts; on the chip every option is the default
    extra = ["--kv-block-size", "16"] if REHEARSAL else []
    n_a, new_a, n_b, n_pre, n_tail = ((16, 8, 100, 64, 30) if REHEARSAL
                                      else (32, 32, 1500, 1024, 400))
    srv = Server("serve", ["--model", model] + extra, env)
    try:
        ready = srv.wait_ready(900)
        dev = device_from_log(srv.text())
        say(f"  server ready after {ready:.1f}s; it holds {dev}")
        if dev != device:
            raise SmokeFailure(f"server saw {dev}, kernels phase {device}")

        pa = prompt_tokens(1, n_a, vocab)
        a = srv.complete(pa, new_a)
        say(f"  (a) {n_a}-token prompt, {new_a} new: {a['_seconds']:.2f}s "
            "(first request: includes compiles)")
        pb = prompt_tokens(2, n_b, vocab)
        b = srv.complete(pb, 16)
        say(f"  (b) {n_b}-token prompt: {b['_seconds']:.2f}s")
        before = srv.health()["engine"]
        c = srv.complete(pb[:n_pre] + prompt_tokens(3, n_tail, vocab), 16)
        after = srv.health()["engine"]
        cached = (after["prefix_cached_tokens"]
                  - before["prefix_cached_tokens"])
        hits = after["kv"]["prefix_hits"] - before["kv"]["prefix_hits"]
        say(f"  (c) first {n_pre} of (b) + {n_tail} new: {c['_seconds']:.2f}s"
            f"; prefix hit: {hits} pages, {cached} prompt tokens from cache; "
            f"suffix-prefill programs: "
            f"{after['compiled_programs']['prefill_extend_buckets']}")
        if hits < 1 or cached < n_pre // 2:
            raise SmokeFailure("(c) was not served from the prefix cache")
        if after["compiled_programs"]["prefill_extend_buckets"] < 1:
            raise SmokeFailure("(c) did not go through the suffix window")

        results: list = [None] * 4
        errors: list = []

        def one(i):
            try:
                results[i] = srv.complete(
                    prompt_tokens(10 + i, n_a + 8 * i, vocab), new_a)
            except Exception as e:   # surfaced below, in the main thread
                errors.append(e)
        t0 = time.monotonic()
        threads = [threading.Thread(target=one, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        say(f"  (d) four requests at once: {time.monotonic() - t0:.2f}s")
        chunks, finish, secs = srv.stream(prompt_tokens(20, n_a, vocab),
                                          new_a)
        say(f"  (e) stream: {chunks} chunks, finish_reason={finish}, "
            f"{secs:.2f}s")
        f = srv.complete(pa, new_a)
        same = (f["choices"][0]["token_ids"]
                == a["choices"][0]["token_ids"])
        say(f"  (f) (a) again: {f['_seconds']:.2f}s (warm); token-identical: "
            f"{same}")
        if not same:
            raise SmokeFailure("(f) greedy tokens differ from (a)")

        h = srv.health()
        say(f"  /health: status={h['status']} engine_error_count="
            f"{h['engine_error_count']} last_engine_error="
            f"{h['last_engine_error']}")
        if (h["_status"] != 200 or h["engine_error_count"] != 0
                or h["last_engine_error"] is not None):
            raise SmokeFailure("the engine reported errors: see "
                               f"{srv.log}")
    finally:
        srv.stop()
    say(f"  server stopped by SIGINT (exit code {srv.proc.returncode})")
    text = srv.text()
    report_impls("serve", text,
                 ("prefill_attention", "paged_attention",
                  "paged_attention_multi", "rms_norm"), on_tpu)
    require_tile_write(text, 1, "a decode step's one row a slot")
    secs, n = compile_seconds(text)
    say(f"  serve: {n} programs compiled in {secs:.1f}s")
    # the server is gone and the chip free: one more child builds the same
    # engine and reads what the compiler holds beside the decode program's
    # arguments. The pools are donated and updated in place, so a pool-
    # sized temporary is a copy that came back
    text = run_child("decode_memory",
                     [sys.executable, str(ROOT / "chip_smoke.py")],
                     {**env, PHASE_ENV: "decode_memory",
                      "CHIP_SMOKE_MODEL": model}, timeout=900)
    [mem] = smoke_records(text, "decode_memory")
    say(f"  decode program ({mem['program']}, {mem['steps']} steps, "
        f"{mem['slots']} slots): temp {mem['temp_bytes'] / 1e6:.1f} MB, "
        f"arguments {mem['argument_bytes'] / 1e6:.1f} MB (aliased to "
        f"results: {mem['alias_bytes'] / 1e6:.1f} MB); the K pool is "
        f"{mem['k_pool_bytes'] / 1e6:.1f} MB, one layer of it "
        f"{mem['k_pool_bytes'] / mem['layers'] / 1e6:.1f} MB")
    # (gpt-test's whole pool is smaller than its logits: not judged)
    if mem["temp_bytes"] >= mem["k_pool_bytes"] and not REHEARSAL:
        raise SmokeFailure("the decode program holds a pool-sized "
                           "temporary: the KV pool is copied again")


def seeded_reference(next_logits, prompt: list, sampling, n_new: int) -> list:
    """What a seeded request must be served, computed directly: token after
    token, the next token's logits over the whole context
    (``next_logits(context) -> [V]``) sampled with the key
    ``fold_in(PRNGKey(seed), len(context))``. That is the engine's contract
    for a stream's keys whichever program draws the token (prefill's first,
    a decode step, a verification window) and wherever the slot's key was
    made; ``tests/test_slot_keys.py`` holds every prefill path to it on the
    CPU, the serve phase the chip's programs."""
    import jax
    import jax.numpy as jnp
    from distributed_llm_training_and_inference_system_tpu.serve.sampling import (
        sample_tokens)
    key = jax.random.PRNGKey(sampling.seed)
    context = list(prompt)
    for _ in range(n_new):
        token = sample_tokens(
            next_logits(context)[None],
            jax.random.fold_in(key, len(context))[None],
            jnp.asarray([sampling.temperature], jnp.float32),
            jnp.asarray([sampling.top_k], jnp.int32),
            jnp.asarray([sampling.top_p], jnp.float32))
        context.append(int(token[0]))
    return context[len(prompt):]


def phase_seeded_replies(env: dict, parent: str | None) -> None:
    text = run_child("seeded", [sys.executable, str(ROOT / "chip_smoke.py")],
                     {**env, PHASE_ENV: "seeded"}, timeout=900)
    [rec] = smoke_records(text, "seeded")
    say(f"  seeded replies ({rec['model']}, float32, full-precision "
        f"matmuls): {rec['requests']} requests x {rec['new']} tokens, "
        f"tokens equal to the direct reference: {rec['identical']}")
    if any(n != rec["new"] for n in rec["identical"]):
        raise SmokeFailure("a seeded reply differs from fold_in(PRNGKey("
                           f"seed), context length): {rec}")
    if parent is None:
        return
    if REHEARSAL:
        say("  --parent: experiments/served_tokens.py has no CPU mode; "
            "not run in a rehearsal")
        return
    served = {}
    for side, root in (("this", ROOT), ("parent", Path(parent).resolve())):
        out = OUT / f"served_tokens_{side}.json"
        run_child(f"served_tokens_{side}",
                  [sys.executable, str(root / "experiments"
                                       / "served_tokens.py"),
                   "--out", str(out)],
                  {**env, "PYTHONPATH": str(root)}, timeout=1500)
        served[side] = json.loads(out.read_text())
    for mode in ("greedy", "seeded"):
        same = [a == b for a, b in zip(served["this"][mode],
                                       served["parent"][mode])]
        say(f"  {mode} replies equal to {parent}'s: {sum(same)} of "
            f"{len(same)} (x {len(served['this'][mode][0])} tokens)")
        if not all(same) or len(same) != 8:
            raise SmokeFailure(f"{mode} tokens differ from the parent's")


def phase_ride(env: dict) -> None:
    text = run_child("ride", [sys.executable, str(ROOT / "chip_smoke.py")],
                     {**env, PHASE_ENV: "ride"}, timeout=1500)
    recs = smoke_records(text, "ride")
    if len(recs) != 3:
        raise SmokeFailure(f"{len(recs)} of the 3 riding arms reported")
    for rec in recs:
        riding, cold = rec["cached_tokens"]
        say(f"  riding prompts ({rec['model']}, {rec['layers']} layers, "
            f"float32; {rec['resident']} of {rec['slots']} slots resident; "
            f"{riding} prompt tokens from the prefix cache): "
            f"{rec['rode_tokens']} of {rec['prompt_tokens']} prompt tokens "
            f"rode {rec['ride_steps']} decode steps, "
            f"{rec['cold_rode_tokens']} on the drained engine; tokens equal "
            f"to the prefill program's: {rec['identical']} of {rec['new']}")
        if (rec["rode_tokens"] != rec["prompt_tokens"]
                or rec["cold_rode_tokens"] or riding != cold):
            raise SmokeFailure("the prompts did not ride, rode on the "
                               "drained engine, or the two passes hit the "
                               f"cache differently: {rec}")
        if any(n != rec["new"] for n in rec["identical"]):
            raise SmokeFailure("a riding prompt's tokens differ from the "
                               f"prefill program's: {rec}")


def phase_selfdraft(env: dict) -> None:
    text = run_child("selfdraft", [sys.executable, str(ROOT / "chip_smoke.py")],
                     {**env, PHASE_ENV: "selfdraft"}, timeout=1200)
    recs = {r["weights"]: r for r in smoke_records(text, "selfdraft")}
    if set(recs) != {"all-stand", "seeded"}:
        raise SmokeFailure(f"the self-drafting arms reported {sorted(recs)}")
    for name, rec in recs.items():
        say(f"  self-drafting ({rec['model']}, {rec['layers']} layers + the "
            f"module, float32; {name} weights): {rec['tokens']} tokens in "
            f"{rec['slot_steps']} slot-steps = {rec['tokens_per_slot_step']:.3f}"
            f" a step, {rec['accepted']} of {rec['drafts']} drafts stood; "
            f"tokens equal to plain greedy decoding's: {rec['identical']} of "
            f"{rec['new']}")
        if any(n != rec["new"] for n in rec["identical"]):
            raise SmokeFailure("a self-drafted stream differs from plain "
                               f"greedy decoding: {rec}")
    require_tile_write(text, 2, "the window of two rows")
    if recs["all-stand"]["tokens_per_slot_step"] != 2.0:
        raise SmokeFailure("the two-token branch did not run in every step "
                           f"of the constructed weights: {recs['all-stand']}")
    if recs["seeded"]["tokens_per_slot_step"] > 1.05:
        raise SmokeFailure("seeded drafts stand far above chance: "
                           f"{recs['seeded']}")


def phase_shortconv(env: dict) -> None:
    """LFM2-8B-A1B at its published widths and a tiny depth (the table's
    first period ``c c a c``: both dense layers, two of 32 experts' layers)
    through ``cli.main serve start``: cold prompts, decode, and a prompt
    that rides two residents' decode steps. Heads of 64 in PAIRS on the
    pool's 128 lanes: fails if any attention program of the server reports
    ``gather`` on the chip (``report_impls``)."""
    say("== shortconv: cli.main serve start --model <lfm2, 4 layers> ==")
    config = json.loads((ROOT / "benchmark" / "configs"
                         / "lfm2-8b-a1b-16l.json").read_text())
    depth = 4
    model = {k: v for k, v in config.items() if not isinstance(v, dict)
             and k not in ("assumed", "deployment", "serve_why", "source")}
    if REHEARSAL:       # the CPU rehearses the control flow at a small width
        model.update(hidden_size=256, intermediate_size=192,
                     moe_intermediate_size=128, num_attention_heads=4,
                     num_key_value_heads=2, num_experts=8,
                     num_experts_per_tok=2, vocab_size=512)
    model.update(name="lfm2-smoke", num_hidden_layers=depth,
                 layer_types=config["layer_types"][:depth])
    SCRATCH.mkdir(parents=True, exist_ok=True)
    path = SCRATCH / "lfm2-smoke.json"
    path.write_text(json.dumps(model))
    vocab = model["vocab_size"]
    # (the residents' replies are long enough that the third prompt finds
    # them decoding: ~3 s of steps on the chip)
    page, n_long, n_new = (16, 40, 64) if REHEARSAL else (256, 700, 600)
    srv = Server("shortconv", ["--model", str(path), "--max-batch-size", "4",
                               "--max-seq-len", str(8 * page),
                               "--kv-block-size", str(page)], env)
    try:
        ready = srv.wait_ready(900)
        dev = device_from_log(srv.text())
        say(f"  server ready after {ready:.1f}s; it holds {dev}")
        DEVICE.update(dev)
        a = srv.complete(prompt_tokens(31, n_long, vocab), 8)
        say(f"  (a) {n_long}-token prompt, 8 new: {a['_seconds']:.2f}s "
            "(first request: includes compiles)")
        # two residents decode (half the slots), a third prompt rides them
        results: list = [None] * 3
        errors: list = []

        steps0 = srv.health()["engine"]["decode_steps"]

        def one(i, behind):
            try:
                # the third prompt is sent once the residents decode
                while behind and (srv.health()["engine"]["decode_steps"]
                                  < steps0 + 8):
                    time.sleep(0.05)
                results[i] = srv.complete(
                    prompt_tokens(40 + i, n_long + 8 * i, vocab), n_new)
            except Exception as e:   # surfaced below, in the main thread
                errors.append(e)
        threads = [threading.Thread(target=one, args=(i, i == 2))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        eng = srv.health()["engine"]
        say(f"  (b) two residents and a third prompt behind them: "
            f"{eng['prefill_ride_tokens']} prompt tokens rode decode steps; "
            f"conv pool {eng['shortconv']['state_bytes'] / 1e6:.2f} MB, "
            f"{eng['shortconv']['slot_steps']} slot-steps")
        if eng["prefill_ride_tokens"] < 1:
            raise SmokeFailure("the third prompt did not ride the residents' "
                               "decode steps: the piece's window kernel and "
                               "conv did not run")
        again = srv.complete(prompt_tokens(31, n_long, vocab), 8)
        if (again["choices"][0]["token_ids"]
                != a["choices"][0]["token_ids"]):
            raise SmokeFailure("(a) again, in a reused slot: greedy tokens "
                               "differ (a window a former occupant left?)")
        h = srv.health()
        if (h["_status"] != 200 or h["engine_error_count"] != 0
                or h["last_engine_error"] is not None):
            raise SmokeFailure(f"the engine reported errors: see {srv.log}")
    finally:
        srv.stop()
    report_impls("shortconv", srv.text(), ("paged_attention",),
                 dev["platform"] == "tpu")
    require_tile_write(srv.text(), 1, "a decode step's one row a slot")


_STEP_RE = re.compile(r"step (\d+) \| loss ([0-9.naninf-]+) \|.*?"
                      r"\| ([0-9.]+) tok/s")


def training_overrides(ckpt: Path) -> list:
    sets = {"checkpoint.path": str(ckpt), "training.log_interval": 1}
    if REHEARSAL:
        sets.update({"data.max_length": 64, "parallel.micro_batch_size": 2,
                     "parallel.global_batch_size": 4,
                     "parallel.gradient_accumulation_steps": 2})
    else:
        # bench.py's gpt-750m recipe: micro-batch 4 at sequence 2048 with
        # bf16 Adam moments (what fits 16 GB); 2 accumulation steps keep
        # the smoke short
        sets.update({"data.max_length": 2048,
                     "parallel.micro_batch_size": 4,
                     "parallel.global_batch_size": 8,
                     "parallel.gradient_accumulation_steps": 2,
                     "optimizer.moment_dtype": "bfloat16",
                     "optimizer.nu_dtype": "bfloat16"})
    return [a for k, v in sets.items() for a in ("--set", f"{k}={v}")]


def phase_train(env: dict, device: dict) -> None:
    import math
    model = "gpt-test" if REHEARSAL else "gpt-750m"
    vocab = 256 if REHEARSAL else 50304
    say(f"== train: cli.main train launch --model {model} --max-steps 8 ==")
    ckpt = SCRATCH / "train_ckpt"
    try:
        t0 = time.monotonic()
        text = run_child(
            "train", CLI + ["train", "launch", "--model", model,
                            "--max-steps", "8", "--no-resume"]
            + training_overrides(ckpt), env, timeout=1000)
        wall = time.monotonic() - t0
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    dev = device_from_log(text)
    if dev != device:
        raise SmokeFailure(f"trainer saw {dev}, kernels phase {device}")
    steps = [(int(m.group(1)), float(m.group(2)), float(m.group(3)))
             for m in _STEP_RE.finditer(text)]
    losses = [s[1] for s in steps]
    say(f"  trainer holds {dev}; losses: "
        + " ".join(f"{l:.4f}" for l in losses))
    if [s[0] for s in steps] != list(range(1, 9)):
        raise SmokeFailure(f"expected steps 1..8, saw {[s[0] for s in steps]}")
    if not all(math.isfinite(l) for l in losses):
        raise SmokeFailure("a loss is not finite")
    if abs(losses[0] - math.log(vocab)) > 0.7:
        raise SmokeFailure(f"first loss {losses[0]:.3f} is not near "
                           f"ln {vocab} = {math.log(vocab):.3f}")
    secs, n = compile_seconds(text)
    tokens_per_step = (4 * 64) if REHEARSAL else (8 * 2048)
    warm = [tokens_per_step / s[2] for s in steps[1:] if s[2] > 0]
    say(f"  train: {n} programs compiled in {secs:.1f}s; "
        f"{sorted(warm)[len(warm) // 2]:.2f}s per step (median of steps "
        f"2-8); phase {wall:.1f}s incl. the final checkpoint")
    report_impls("train", text, ("attention", "optimizer_update"),
                 device["platform"] == "tpu")


def phase_launcher(env: dict, device: dict) -> None:
    say("== launcher: train launch --restart-on-failure 1 --max-steps 2 "
        "(gpt-test: a SPAWNED child with the launcher's flags) ==")
    ckpt = SCRATCH / "launcher_ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    try:
        text = run_child(
            "launcher", CLI + [
                "train", "launch", "--model", "gpt-test", "--max-steps", "2",
                "--restart-on-failure", "1",
                "--set", f"checkpoint.path={ckpt}",
                "--set", "data.max_length=64",
                "--set", "training.log_interval=1",
                # head_dim 16 cannot take the flash kernel (Mosaic tiles
                # head_dim onto 128 lanes); this phase is about start-up
                "--set", "training.attn_impl=xla"], env, timeout=600)
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    dev = device_from_log(text)
    if dev != device:
        raise SmokeFailure(f"spawned child saw {dev}, not {device}")
    if "restart 1/1" in text or "finished:" not in text:
        raise SmokeFailure("the spawned child did not finish at its first "
                           "start; log tail:\n" + text[-2000:])
    say(f"  spawned child held {dev} and finished 2 steps at its first start")


# ---------------------------------------------------------------------------
# phases, four chips
# ---------------------------------------------------------------------------

def phase_mesh_train(env: dict) -> dict:
    say("== 4 chips: 8 steps on a fsdp=2 x tp=2 mesh against one device ==")
    text = run_child("mesh_train",
                     [sys.executable, str(ROOT / "chip_smoke.py")],
                     {**env, PHASE_ENV: "mesh_train"}, timeout=1500)
    [device] = smoke_records(text, "device")
    [rec] = smoke_records(text, "mesh_train")
    say("  1 device : " + " ".join(f"{l:.4f}" for l in rec["loss_1"]))
    say("  4 devices: " + " ".join(f"{l:.4f}" for l in rec["loss_4"]))
    say(f"  largest loss difference {rec['max_diff']:.4f} (tolerance "
        f"{rec['tol']}); collectives in the compiled step: "
        f"{rec['collectives']}")
    say(f"  parameter bytes per device: {rec['param_bytes_per_device']} of "
        f"{rec['param_bytes_total']} total "
        f"(largest share {rec['largest_share']:.3f})")
    report_impls("mesh_train", text, ("attention", "optimizer_update"),
                 device["platform"] == "tpu",
                 allowed={"optimizer_update": "on a multi-device mesh the "
                          "AdamW update is fused XLA code (GSPMD cannot "
                          "partition the Mosaic kernel)"})
    secs, n = compile_seconds(text)
    say(f"  mesh_train: {n} programs compiled in {secs:.1f}s")

    # the same eight steps through the entry point a user calls: the
    # child above drives TrainingEngine itself (it needs the compiled
    # step's text and the shards); the CLI's losses must be the child's
    model = "gpt-test" if REHEARSAL else "gpt-750m"
    say(f"== 4 chips: cli.main train launch --model {model} --max-steps 8 "
        "--set parallel.fsdp=2 --set parallel.tensor_parallel=2 ==")
    ckpt = SCRATCH / "mesh_cli_ckpt"
    try:
        text = run_child(
            "mesh_train_cli", CLI + [
                "train", "launch", "--model", model, "--max-steps", "8",
                "--no-resume", "--set", "parallel.fsdp=2",
                "--set", "parallel.tensor_parallel=2"]
            + training_overrides(ckpt), env, timeout=1000)
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    if device_from_log(text) != device:
        raise SmokeFailure(f"the CLI's trainer saw {device_from_log(text)}")
    losses = [float(m.group(2)) for m in _STEP_RE.finditer(text)]
    say("  CLI      : " + " ".join(f"{l:.4f}" for l in losses))
    if (len(losses) != 8 or max(abs(a - b) for a, b in
                                zip(losses, rec["loss_4"])) > 1e-3):
        raise SmokeFailure(f"the CLI's 4-device losses {losses} are not the "
                           f"child's {rec['loss_4']}")
    report_impls("mesh_train_cli", text, ("attention", "optimizer_update"),
                 device["platform"] == "tpu",
                 allowed={"optimizer_update": "as above"})
    return device


def tp_case() -> tuple:
    """(tp degree, model, prompt tokens, new tokens, extra serve options) of
    the tensor-parallel comparison; gpt-test has 2 kv heads, so the
    rehearsal can only split them in two."""
    if REHEARSAL:
        return 2, "gpt-test", 16, 8, {"kv_block_size": 16}
    return 4, "gpt-1b", 32, 32, {}


def phase_tp_serve(env: dict, device: dict) -> None:
    tp, model, n_a, new_a, extra = tp_case()
    vocab = 256 if REHEARSAL else 50304
    prompt = prompt_tokens(1, n_a, vocab)
    tokens = {}
    for degree in (tp, 1):
        say(f"== 4 chips: serve start --model {model} --tensor-parallel "
            f"{degree} ==")
        srv = Server(f"serve_tp{degree}", [
            "--model", model, "--tensor-parallel", str(degree)]
            + [a for k, v in extra.items()
               for a in ("--" + k.replace("_", "-"), str(v))], env)
        try:
            ready = srv.wait_ready(900)
            out = srv.complete(prompt, new_a)
            tokens[degree] = out["choices"][0]["token_ids"]
            h = srv.health()
            say(f"  ready after {ready:.1f}s; request (a) {out['_seconds']:.2f}"
                f"s; engine_error_count={h['engine_error_count']}")
            if h["engine_error_count"] != 0:
                raise SmokeFailure(f"tp={degree}: the engine reported errors")
        finally:
            srv.stop()
        text = srv.text()
        if device_from_log(text) != device:
            raise SmokeFailure(f"tp={degree} server saw "
                               f"{device_from_log(text)}")
        report_impls(f"serve_tp{degree}", text, ("paged_attention",),
                     device["platform"] == "tpu",
                     allowed={} if degree == 1 else {
                         op: "under tensor parallelism the engine asks for "
                             "the gather path (GSPMD cannot partition the "
                             "Mosaic kernel)"
                         for op in ("paged_attention",
                                    "paged_attention_multi")})
    # The servers compute in the model's bfloat16 (`--dtype` sets storage
    # only), where tp=4 and tp=1 differ by rounding, and over RANDOM
    # weights some greedy step is a near-tie that rounding decides: their
    # common prefix is reported, not judged. What is judged is the same
    # comparison where rounding cannot decide: the engine in float32 with
    # full-precision matmuls, all tokens identical.
    common = next((i for i, (a, b) in enumerate(zip(tokens[tp], tokens[1]))
                   if a != b), len(tokens[1]))
    say(f"  the servers' greedy tokens at tp={tp} and tp=1 are identical for "
        f"the first {common} of {len(tokens[1])} (reported, not judged)")
    say(f"== 4 chips: InferenceEngine tp={tp} against tp=1 in float32, "
        "matmul precision highest ==")
    text = run_child("tp_exact", [sys.executable, str(ROOT / "chip_smoke.py")],
                     {**env, PHASE_ENV: "tp_exact"}, timeout=1200)
    [rec] = smoke_records(text, "tp_exact")
    for degree in (tp, 1):
        say(f"  tp={degree}: {rec['tokens'][str(degree)]}")
    report_impls("tp_exact", text, ("paged_attention",),
                 device["platform"] == "tpu",
                 allowed={"paged_attention": "tp>1 asks for the gather path",
                          "paged_attention_multi": "tp>1 asks for the gather "
                                                   "path",
                          "attention": "this comparison's own dense forward "
                                       "(gpt.forward, XLA attention), not "
                                       "the engine's"})
    norm = rec["logit_diff"] / rec["logit_max"]
    say(f"  identical: {rec['identical']} of {new_a}; dense logits along "
        f"tp=1's tokens, sharded against unsharded parameters: largest "
        f"difference {rec['logit_diff']:.2e} of {rec['logit_max']:.2f} "
        f"(normalised {norm:.1e}, tolerance 1e-4); smallest top-two margin "
        f"{rec['min_margin']:.5f} at token {rec['min_margin_at']}")
    if rec["identical"] != new_a or norm > 1e-4:
        raise SmokeFailure(f"tp={tp} differs from tp=1 in float32: sharding, "
                           "not rounding")


def phase_replica_placement(env: dict) -> None:
    say("== 4 chips: where `serve start --replicas 4` puts its engines "
        "(gpt-test: placement does not depend on size) ==")
    text = run_child("replicas", [sys.executable, str(ROOT / "chip_smoke.py")],
                     {**env, PHASE_ENV: "replicas"}, timeout=600)
    [rec] = smoke_records(text, "replicas")
    for i, devs in enumerate(rec["devices"]):
        say(f"  replica {i}: parameters on device(s) {devs}")
    say(f"  distinct devices used: {rec['distinct']} of {rec['available']} "
        "(a finding for ROADMAP.md, not a failure)")


# ---------------------------------------------------------------------------
# child side (these import jax; the parent never calls them)
# ---------------------------------------------------------------------------

def emit(kind: str, rec: dict) -> None:
    print(f"SMOKE {kind} {json.dumps(rec)}", flush=True)


def child_setup():
    import logging
    logging.basicConfig(level="INFO", stream=sys.stdout,
                        format="%(name)s %(levelname)s %(message)s")
    from distributed_llm_training_and_inference_system_tpu.utils.platform import (
        device_summary, enable_compile_cache)
    enable_compile_cache()
    device = device_summary()
    emit("device", device)
    if device["platform"] != "tpu" and not REHEARSAL:
        print(f"no TPU: jax reports {device}", file=sys.stderr)
        sys.exit(2)
    return device


def child_kernels() -> None:
    device = child_setup()
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_training_and_inference_system_tpu.exec.fused_update import (
        fused_adamw_apply)
    from distributed_llm_training_and_inference_system_tpu.models.layers import (
        attention_mask, dot_product_attention, rms_norm)
    from distributed_llm_training_and_inference_system_tpu.ops.attention import (
        flash_attention)
    from distributed_llm_training_and_inference_system_tpu.ops.int4_matmul_pallas import (
        matmul_w4)
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        QuantPages, paged_attention, paged_attention_multi, quantize_kv_token)
    from distributed_llm_training_and_inference_system_tpu.ops.quantization import (
        dequantize_int4_groupwise, quantize_int4_groupwise)

    on_tpu = device["platform"] == "tpu"
    failures = []
    small = REHEARSAL

    def check(name, got, ref, tol=2e-2):
        got = [np.asarray(g, np.float32) for g in jax.tree_util.tree_leaves(got)]
        ref = [np.asarray(r, np.float32) for r in jax.tree_util.tree_leaves(ref)]
        worst = {"normalised": 0.0, "max_abs_diff": 0.0, "ref_max": 0.0}
        for g, r in zip(got, ref, strict=True):
            if not np.isfinite(g).all():
                failures.append(f"{name}: non-finite output")
            diff, rmax = float(np.abs(g - r).max()), float(np.abs(r).max())
            norm = diff / max(rmax, 1e-6)
            if norm >= worst["normalised"]:
                worst = {"normalised": norm, "max_abs_diff": diff,
                         "ref_max": rmax}
        emit("kernel", {"name": name, "tol": tol, **worst})
        if worst["normalised"] > tol:
            failures.append(f"{name}: normalised difference "
                            f"{worst['normalised']:.3e} > {tol}")

    key = iter(jax.random.split(jax.random.PRNGKey(0), 256))
    D = 128
    PS, MAXP = (16, 8) if small else (64, 32)
    layouts = ({"mha4": (4, 4), "gqa4x2": (4, 2)} if small
               else {"mha16": (16, 16), "gqa32x8": (32, 8)})

    # the kernel gets the pools as the serve programs carry them, stacked
    # [LAYERS, NP, Nkv, PS, D], with a traced non-zero layer index; the
    # gather reference gets that layer's own [NP, Nkv, PS, D] pages
    LAYERS, LAYER = 3, 2

    def pages(nkv, slots, kv):
        kp = jax.random.normal(
            next(key), (LAYERS, slots * MAXP + 1, nkv, PS, D), jnp.bfloat16)
        vp = jax.random.normal(next(key), kp.shape, jnp.bfloat16)
        if kv == "int8":
            # [L, NP, Nkv, PS, D] -> per-token rows, as the engine writes them
            kp, vp = (QuantPages(*quantize_kv_token(p)) for p in (kp, vp))
        tables = 1 + jax.random.permutation(
            next(key), slots * MAXP).reshape(slots, MAXP).astype(jnp.int32)
        return kp, vp, tables

    def one_layer(pool):
        return jax.tree_util.tree_map(lambda a: a[LAYER], pool)

    def kernel_and_gather(fn):
        """(the Pallas kernel on the stacked pool at LAYER, the gather
        baseline on that layer's pages), both jitted."""
        kernel = jax.jit(functools.partial(fn, impl="pallas"))
        gather = jax.jit(functools.partial(fn, impl="gather"))
        return (lambda q, kp, vp, *at: kernel(q, kp, vp, *at,
                                              layer=jnp.int32(LAYER)),
                lambda q, kp, vp, *at: gather(q, one_layer(kp),
                                              one_layer(vp), *at))

    # -- paged attention, single query -------------------------------------
    B = 2 if small else 8
    for lname, (nq, nkv) in layouts.items():
        for kv in ("bf16", "int8"):
            kp, vp, tables = pages(nkv, B, kv)
            q = jax.random.normal(next(key), (B, nq, D), jnp.bfloat16)
            lengths = jax.random.randint(next(key), (B,), 1, MAXP * PS + 1)
            kernel, gather = kernel_and_gather(paged_attention)
            check(f"paged_attention decode {lname} {kv}-pages",
                  kernel(q, kp, vp, tables, lengths),
                  gather(q, kp, vp, tables, lengths))

    # -- paged attention, the serving cells' batch --------------------------
    # 32 slots of ragged lengths (the benchmark's 32-1,408 tokens), every
    # fourth one idle (position 0, a table of scratch pages), one ending on
    # a page boundary and one filling its table: the lengths the kernel's
    # page loop and its copies across slots are bounded by
    B = 4 if small else 32
    for lname, (nq, nkv) in layouts.items():
        kp, vp, tables = pages(nkv, B, "bf16")
        q = jax.random.normal(next(key), (B, nq, D), jnp.bfloat16)
        lengths = jax.random.randint(next(key), (B,), PS // 2,
                                     min(22, MAXP) * PS + 1)
        lengths = lengths.at[1].set(2 * PS).at[2].set(MAXP * PS)
        idle = jnp.arange(B) % 4 == 3
        lengths = jnp.where(idle, 1, lengths)
        tables = jnp.where(idle[:, None], 0, tables)
        kernel, gather = kernel_and_gather(paged_attention)
        check(f"paged_attention decode {lname} {B} ragged slots, "
              f"{int(idle.sum())} idle",
              kernel(q, kp, vp, tables, lengths),
              gather(q, kp, vp, tables, lengths))

    # -- paged attention, multi query --------------------------------------
    windows = ([(8, "mha4", "bf16"), (32, "mha4", "bf16"),
                (32, "gqa4x2", "int8")] if small else
               [(8, "mha16", "bf16"), (64, "mha16", "bf16"),
                (128, "mha16", "bf16"), (256, "mha16", "bf16"),
                (256, "gqa32x8", "bf16"), (256, "mha16", "int8"),
                (512, "mha16", "bf16"), (512, "gqa32x8", "bf16")])
    for T, lname, kv in windows:
        nq, nkv = layouts[lname]
        B = (2 if small else 8) if T == 8 else 1
        kp, vp, tables = pages(nkv, B, kv)
        q = jax.random.normal(next(key), (B, T, nq, D), jnp.bfloat16)
        starts = jax.random.randint(next(key), (B,), 0,
                                    MAXP * PS - T).astype(jnp.int32)
        kernel, gather = kernel_and_gather(paged_attention_multi)
        got = kernel(q, kp, vp, tables, starts)
        # the gather reference re-materialises the whole prefix per query
        # row: feed it the window in slices of 64 rows
        step = min(T, 64)
        ref = jnp.concatenate([
            gather(q[:, j:j + step], kp, vp, tables, starts + j)
            for j in range(0, T, step)], axis=1)
        check(f"paged_attention multi-query T={T} {lname} {kv}-pages",
              got, ref)

    # -- paged attention under the block rule: the denoise window ------------
    # (generation by diffusion, serve/decode.py denoise_scan) two blocks a
    # slot from one block BEFORE the slot's current one, written and then
    # attended as the program does it. First half dead: its rows write
    # nothing and the second half sees the K/V that stand in the pages (a
    # slot whose current block is its first starts at -Bd). First half
    # live: its K/V are written first and the second half sees them.
    # Against the gather path on float32 copies, over the live rows
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        write_window_to_pages)
    Bd, (nq, nkv) = 4, ((4, 2) if small else (32, 4))
    B = 2 if small else 8
    for half in ("dead", "live"):
        kp, vp, tables = pages(nkv, B, "bf16")
        q = jax.random.normal(next(key), (B, 2 * Bd, nq, D), jnp.bfloat16)
        k_new, v_new = (jax.random.normal(next(key), (B, 2 * Bd, nkv, D),
                                          jnp.bfloat16) for _ in range(2))
        blocks = jax.random.randint(next(key), (B,), 1, MAXP * PS // Bd - 1)
        # a block that starts a page behind one that ends the page before
        blocks = blocks.at[1].set(2 * PS // Bd)
        if half == "dead":
            blocks = blocks.at[0].set(0)
        starts = (blocks * Bd - Bd).astype(jnp.int32)
        ok = jnp.repeat(jnp.asarray([[half == "live", True]] * B), Bd, axis=1)

        def window(q, k_new, v_new, kp, vp, impl32):
            cast = (lambda a: a.astype(jnp.float32)) if impl32 else (
                lambda a: a)
            kp, vp = (write_window_to_pages(cast(p), cast(n), tables, starts,
                                            ok, jnp.int32(LAYER))
                      for p, n in ((kp, k_new), (vp, v_new)))
            out = paged_attention_multi(
                cast(q), kp, vp, tables, starts, layer=jnp.int32(LAYER),
                impl="gather" if impl32 else "pallas", block=Bd)
            return jnp.where(ok[:, :, None, None], out, 0)
        window = functools.partial(jax.jit(window, static_argnums=5),
                                   q, k_new, v_new, kp, vp)
        check(f"paged_attention_blk two-block window, first half {half}, "
              f"gqa{nq}x{nkv}", window(False), window(True))

    # -- flash attention, forward and backward -----------------------------
    S = 256 if small else 2048
    for name, B, (nq, nkv) in (
            [("mha4 b2", 2, (4, 4))] if small else
            [("gpt-750m b4", 4, (16, 16)), ("gqa32x8 b2", 2, (32, 8))]):
        q = jax.random.normal(next(key), (B, S, nq, D), jnp.bfloat16)
        k = jax.random.normal(next(key), (B, S, nkv, D), jnp.bfloat16)
        v = jax.random.normal(next(key), (B, S, nkv, D), jnp.bfloat16)
        w = jax.random.normal(next(key), (B, S, nq, D), jnp.bfloat16)

        def flash(q, k, v):
            return flash_attention(q, k, v)

        def xla(q, k, v):
            pos = jnp.arange(S, dtype=jnp.int32)[None].repeat(B, axis=0)
            return dot_product_attention(
                q, k, v, attention_mask(pos, pos, None, None))

        def grads(fn):
            # w is an ARGUMENT: closed over, its 33 MB would be baked into
            # the executable (and the two such entries, 105 and 64 MB,
            # thrashed the chip machine's 192 MiB compile-cache cap)
            loss = lambda q, k, v, w: (fn(q, k, v).astype(jnp.float32)
                                       * w.astype(jnp.float32)).sum()
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v, w)
        check(f"flash_attention fwd {name} s{S}",
              jax.jit(flash)(q, k, v), jax.jit(xla)(q, k, v))
        check(f"flash_attention bwd {name} s{S}", grads(flash), grads(xla),
              tol=4e-2)

    # -- RMSNorm -------------------------------------------------------------
    H = 256 if small else 2048
    x = jax.random.normal(next(key), (4, S, H), jnp.bfloat16)
    scale = jax.random.normal(next(key), (H,), jnp.float32) * 0.1
    check(f"rms_norm [4, {S}, {H}]",
          jax.jit(functools.partial(rms_norm, impl="pallas"))(x, scale),
          jax.jit(functools.partial(rms_norm, impl="xla"))(x, scale))

    # -- fused AdamW update --------------------------------------------------
    for shape in ([(2, 256, 512)] if small else
                  [(12, 2048, 2048), (12, 2048, 5632), (50304, 2048)]):
        p = {"w": jax.random.normal(next(key), shape, jnp.float32) * 0.02}
        g = {"w": jax.random.normal(next(key), shape, jnp.float32) * 0.01}
        mu = {"w": (jax.random.normal(next(key), shape) * 1e-3
                    ).astype(jnp.bfloat16)}
        nu = {"w": (jnp.abs(jax.random.normal(next(key), shape)) * 1e-5
                    ).astype(jnp.bfloat16)}

        def update(use_pallas):
            return jax.jit(functools.partial(
                fused_adamw_apply, lr=1e-3, b1=0.9, b2=0.95, eps=1e-8,
                weight_decay=0.1, decay_mask={"w": True},
                use_pallas=use_pallas))(
                p, g, mu, nu, jnp.int32(3), clip_scale=jnp.float32(0.7))
        check(f"fused_adamw {list(shape)}", update(True), update(False),
              tol=1e-2)

    # -- W4A16 matmul ----------------------------------------------------------
    for rows, n_in, n_out in ([(8, 256, 512)] if small else
                              [(8, 2048, 5632), (8, 4096, 11008)]):
        wgt = jax.random.normal(next(key), (n_in, n_out), jnp.float32) * 0.05
        x = jax.random.normal(next(key), (rows, n_in), jnp.bfloat16)
        packed, sc, chan = quantize_int4_groupwise(wgt, group=128)
        ref = x.astype(jnp.float32) @ dequantize_int4_groupwise(
            packed, sc, chan, group=128).astype(jnp.float32)
        got = matmul_w4(x, packed, sc, chan, group=128,
                        interpret=not on_tpu)
        check(f"matmul_w4 {rows}x{n_in}x{n_out}", got, ref)

    # -- dropless MoE layer (ops/moe_gmm.py) and the q/k projection norm ------
    # OLMoE's published widths (64 experts of 1024, 8 a token, hidden 2048,
    # 16 heads of 128), the decode step's 32 rows and a 1,024-token prefill,
    # the expert stack three layers deep with a traced non-zero layer index,
    # against the same layer in float32 with every expert on every row
    import dataclasses

    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.models.layers import (
        moe_block, qk_project_norm)
    moe_cfg = get_model_config("olmoe-test" if small else "olmoe-1b-7b")
    moe_cfg = dataclasses.replace(moe_cfg, dtype="bfloat16")
    E, K = moe_cfg.moe.num_experts, moe_cfg.moe.experts_per_token
    H, F = moe_cfg.hidden_size, moe_cfg.ffn_size

    def moe_reference(x, layer):
        x = x.astype(jnp.float32)
        w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), layer)
        mm = functools.partial(jnp.matmul, precision="highest")
        p = jax.nn.softmax(mm(x, w["router"]["kernel"]), -1)
        top_p, top_e = jax.lax.top_k(p, K)
        weights = jnp.zeros_like(p).at[
            jnp.arange(x.shape[0])[:, None], top_e].set(top_p)
        out = jnp.zeros_like(x)
        for e in range(E):
            hidden = jax.nn.silu(mm(x, w["gate"]["kernel"][e])) * mm(
                x, w["up"]["kernel"][e])
            out += weights[:, e, None] * mm(hidden, w["down"]["kernel"][e])
        return out

    stack = {
        "router": {"kernel": jax.random.normal(next(key), (H, E)) * 0.05},
        **{n: {"kernel": (jax.random.normal(next(key), (LAYERS, E, *shape),
                                            jnp.float32) * 0.03
                          ).astype(jnp.bfloat16)}
           for n, shape in (("gate", (H, F)), ("up", (H, F)),
                            ("down", (F, H)))}}
    layer = {n: {"kernel": v["kernel"] if n == "router"
                 else v["kernel"][LAYER]} for n, v in stack.items()}
    # (the kernel on the chip; off it, moe_block's ragged_dot route)
    kernel = jax.jit(lambda x, st, li: moe_block(
        x, st, moe_cfg, layer_index=li)[0])
    for batch, seq in ((8, 1), (1, 16)) if small else ((32, 1), (1, 1024)):
        x = jax.random.normal(next(key), (batch, seq, H), jnp.bfloat16)
        got = kernel(x, stack, jnp.int32(LAYER))
        ref = jax.jit(moe_reference)(x.reshape(-1, H), layer)
        check(f"moe_block dropless [{batch * seq} rows, {E} experts of "
              f"{F}, top-{K}]", got.reshape(-1, H), ref)

    Nq, Dh = moe_cfg.num_heads, moe_cfg.head_dim
    norms = {n: {"scale": 0.3 * jax.random.normal(next(key), (Nq * Dh,))}
             for n in ("q_norm", "k_norm")}
    q = jax.random.normal(next(key), (32, 1, Nq * Dh), jnp.bfloat16) * 3.0
    k = jax.random.normal(next(key), (32, 1, Nq * Dh), jnp.bfloat16) * 0.2

    def norm_reference(x, scale):
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + moe_cfg.norm_eps) * (1.0 + scale)
    got = jax.jit(lambda q, k: (qk_project_norm(q, norms, "q", moe_cfg),
                                qk_project_norm(k, norms, "k", moe_cfg)))(q, k)
    check(f"qk_project_norm [{Nq * Dh}-wide projection]", got,
          (norm_reference(q, norms["q_norm"]["scale"]),
           norm_reference(k, norms["k_norm"]["scale"])))

    # -- the hybrid layers (Nemotron-3-Nano's widths): the state-space mixer
    # and the expert layer with HALF of the router's experts held and a
    # shared expert, each against float32 with full-precision matmuls
    from benchmark.reference import hybrid_decoder
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    from distributed_llm_training_and_inference_system_tpu.models.layers import (
        experts_mixer, rms_norm, ssm_mixer)
    from distributed_llm_training_and_inference_system_tpu.ops import ssm
    hy = get_model_config("nemotron-h-test" if small
                          else "nemotron-3-nano-30b-a3b")
    held = hy.moe.num_experts if small else hy.moe.num_experts // 2
    hy = dataclasses.replace(
        hy, dtype="bfloat16", num_layers=4, layer_pattern="MEME",
        moe=dataclasses.replace(hy.moe, num_experts=held,
                                router_experts=hy.moe.router_width))
    blocks = jax.jit(lambda k: gpt.init(hy, k, jnp.bfloat16)["blocks"])(
        next(key))
    sm, H = hy.ssm, hy.hidden_size
    mix = jax.tree_util.tree_map(lambda a: a[1], blocks["ssm"])
    mix["D"] = jax.random.uniform(next(key), mix["D"].shape, minval=0.5,
                                  maxval=1.5)
    mix["gate_norm"] = {"scale": jax.random.uniform(
        next(key), mix["gate_norm"]["scale"].shape, minval=-0.5, maxval=0.5
    ).astype(jnp.bfloat16)}
    S, n_live, steps, slots = (32, 21, 3, 4) if small else (256, 200, 4, 8)
    x = jax.random.normal(next(key), (1, S + steps, H), jnp.float32)
    h_norm = rms_norm(x, mix["norm"]["scale"], hy.norm_eps).astype(
        jnp.bfloat16)
    # the reference sees the live rows and then the decoded ones
    seq = jnp.concatenate([x[0, :n_live], x[0, S:]])
    w = {"norm": mix["norm"]["scale"], "in_proj": mix["in_proj"]["kernel"],
         "conv_kernel": mix["conv"]["kernel"],
         "conv_bias": mix["conv"]["bias"], "dt_bias": mix["dt_bias"],
         "A_log": mix["A_log"], "D": mix["D"],
         "gate_norm": mix["gate_norm"]["scale"],
         "out_proj": mix["out_proj"]["kernel"]}
    ref = hybrid_decoder._mamba(
        seq, w, jnp.ones((seq.shape[0],)), nh=sm.num_heads, p=sm.head_dim,
        n=sm.state_size, g=sm.n_groups, eps=hy.norm_eps, float8=False,
        norm_before_gate=False) - seq
    live = jnp.arange(S)[None] < n_live
    # (weights are ARGUMENTS of every jitted function here: closed over,
    # a gigabyte of experts becomes a constant of the program)
    got_w, (tail, hstate) = jax.jit(lambda h, p: ssm_mixer(
        h, p, hy, ssm.recur_window(hy, live)))(h_norm[:, :S], mix)
    check(f"ssm_mixer window [{S} rows, {n_live} live, {sm.num_heads} heads "
          f"of {sm.head_dim}, state {sm.state_size}]", got_w[0, :n_live],
          ref[:n_live])
    conv_pool = jnp.zeros((2, slots, *tail.shape[1:]), jnp.bfloat16
                          ).at[1, 2].set(tail[0].astype(jnp.bfloat16))
    ssm_pool = jnp.zeros((2, slots, *hstate.shape[1:]), jnp.float32
                         ).at[1, 2].set(hstate[0])
    ok = jnp.arange(slots)[:, None] == 2
    step = jax.jit(lambda h, c, s_, p: ssm_mixer(
        h, p, hy, ssm.recur_step(hy, c, s_, 1, ok)), donate_argnums=(1, 2))

    def decode_slot_2(conv_pool, ssm_pool):
        """(slot 2's outputs of ``steps`` decode steps, the pools)"""
        decoded = []
        for t in range(steps):
            h_t = jnp.zeros((slots, 1, H), jnp.bfloat16).at[2, 0].set(
                h_norm[0, S + t])
            out, (conv_pool, ssm_pool) = step(h_t, conv_pool, ssm_pool, mix)
            decoded.append(out[2, 0])
        return jnp.stack(decoded), conv_pool, ssm_pool
    decoded, conv_pool, ssm_pool = decode_slot_2(conv_pool, ssm_pool)
    check(f"ssm_mixer decode [{steps} steps over the state pools, slot 2 of "
          f"{slots} live]", decoded, ref[n_live:])
    if float(jnp.abs(ssm_pool[:, jnp.asarray([0, 1, 3])]).max()) != 0.0:
        failures.append("ssm decode wrote an idle slot's state")
    # (PR 44) the same window as the TWO pieces a decode step would carry:
    # each a chunk of one slot, run from what ``slot_state`` reads of slot 2
    # in pools that hold a former occupant's rows (the first piece takes
    # them as zero), written back; then decode over what the pieces left
    half = S // 2
    conv_pool = jax.random.normal(next(key), conv_pool.shape, jnp.bfloat16)
    ssm_pool = 0.3 * jax.random.normal(next(key), ssm_pool.shape)
    others = jnp.asarray([0, 1, 3])
    before = (conv_pool[:, others], ssm_pool[:, others])

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def piece(h, c, s_, p, start, n):
        tails, states = ssm.slot_state(c, s_, jnp.int32(2), start[None])
        out, after = ssm_mixer(h, p, hy, ssm.recur_chunk(
            hy, tails[1], states[1], jnp.arange(half)[None] < n))
        return out, ssm.write_slot_state(
            c, s_, jnp.int32(2), tails.at[1].set(after[0]),
            states.at[1].set(after[1]), True)
    rode = []
    for start in (0, half):
        n = min(half, n_live - start)
        out, (conv_pool, ssm_pool) = piece(
            h_norm[:, start:start + half], conv_pool, ssm_pool, mix,
            jnp.int32(start), jnp.int32(n))
        rode.append(out[0, :n])
    check(f"ssm_mixer pieces [{S} rows as 2 windows of {half} from the "
          f"slot's own state, {n_live} live]", jnp.concatenate(rode),
          ref[:n_live])
    if any(float(jnp.abs(a[:, others] - b).max()) != 0.0
           for a, b in zip((conv_pool, ssm_pool), before)):
        failures.append("a piece wrote another slot's state")
    decoded, conv_pool, ssm_pool = decode_slot_2(conv_pool, ssm_pool)
    check(f"ssm_mixer decode behind the pieces [{steps} steps, slot 2 of "
          f"{slots} live]", decoded, ref[n_live:])
    # (PR 50) the idle slots now HOLD states (a former occupant's): the
    # decode steps leave them bit for bit, and a step with NO live slot
    # leaves both pools, every layer of them, bit for bit
    if any(not bool(jnp.array_equal(a[:, others], b))
           for a, b in zip((conv_pool, ssm_pool), before)):
        failures.append("ssm decode wrote an idle slot's state")
    stood = (np.asarray(conv_pool), np.asarray(ssm_pool))
    _, (conv_pool, ssm_pool) = jax.jit(lambda h, c, s_, p: ssm_mixer(
        h, p, hy, ssm.recur_step(hy, c, s_, 1, jnp.zeros((slots, 1), bool))),
        donate_argnums=(1, 2))(h_norm[:, :1].repeat(slots, 0), conv_pool,
                               ssm_pool, mix)
    if not (np.array_equal(np.asarray(conv_pool), stood[0])
            and np.array_equal(np.asarray(ssm_pool), stood[1])):
        failures.append("an ssm decode step with no live slot wrote a state")

    moe_l = jax.tree_util.tree_map(lambda a: a[1], {
        k: v for k, v in blocks["moe"].items() if k not in ("up", "down")})
    moe_l["router"]["bias"] = jax.random.uniform(
        next(key), moe_l["router"]["bias"].shape, minval=-0.1, maxval=0.1)
    moe_l.update(up=blocks["moe"]["up"], down=blocks["moe"]["down"])
    Kx, Er = hy.moe.experts_per_token, hy.moe.router_width

    def experts_reference(u, moe_l):
        u = u.astype(jnp.float32)
        f32 = lambda a: a.astype(jnp.float32)
        mm = functools.partial(jnp.matmul, precision="highest")
        sc = jax.nn.sigmoid(mm(u, f32(moe_l["router"]["kernel"])))
        _, top_e = jax.lax.top_k(sc + moe_l["router"]["bias"], Kx)
        top_w = jnp.take_along_axis(sc, top_e, -1)
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20) \
            * hy.moe.routed_scaling_factor
        weights = jnp.zeros_like(sc).at[
            jnp.arange(u.shape[0])[:, None], top_e].set(top_w)
        act = lambda a: jnp.square(jax.nn.relu(a))
        out = mm(act(mm(u, f32(moe_l["shared"]["up"]["kernel"]))),
                 f32(moe_l["shared"]["down"]["kernel"]))
        for e in range(held):               # experts 0..held-1 are here
            out += weights[:, e, None] * mm(
                act(mm(u, f32(moe_l["up"]["kernel"][1, e]).T)),
                f32(moe_l["down"]["kernel"][1, e]))
        return out

    experts = jax.jit(lambda u, layer, li: experts_mixer(
        u, layer, hy, None, "dropless", li)[0])
    for batch, seq_len in ((8, 1), (1, 16)) if small else ((64, 1), (1, 512)):
        u = jax.random.normal(next(key), (batch, seq_len, H), jnp.bfloat16)
        got = experts(u, moe_l, jnp.int32(1))
        check(f"experts_mixer [{batch * seq_len} rows, {held} of {Er} "
              f"experts held, top-{Kx}, shared expert]",
              got.reshape(-1, H), jax.jit(experts_reference)(
                  u.reshape(-1, H), moe_l))

    # -- latent attention and the hyper-connection (Xing4.0's widths): the
    # mixer's window through the latent pages then decode steps over the
    # pool, and one hyper-connected dense layer over four streams, each
    # against float32 with full-precision matmuls
    from benchmark.reference import latent_decoder
    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        XING_TEST_PUBLISHED)
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    from distributed_llm_training_and_inference_system_tpu.models.layers import (
        decoder_block, latent_attention_mixer, model_rope_frequencies)
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        attend_latent_pages)
    pub = dict(XING_TEST_PUBLISHED if small else json.loads((
        ROOT / "benchmark/configs/xing4.0-29b-a4b-7l.json").read_text()),
        num_hidden_layers=1, first_k_dense_replace=1, dtype="bfloat16")
    # (the CPU's dot has no bfloat16 x bfloat16 -> float32: the rehearsal
    # runs this section in float32)
    xdt = jnp.float32 if small else jnp.bfloat16
    xg = ModelConfig.from_published(pub)
    blocks = jax.jit(lambda k: gpt.init(xg, k, xdt)["blocks"])(
        next(key))
    att = jax.tree_util.tree_map(lambda a: a[0], blocks["attn"])
    for name in ("q_a_norm", "kv_norm"):
        att[name] = {"scale": jax.random.uniform(
            next(key), att[name]["scale"].shape, minval=-0.5, maxval=0.5
        ).astype(xdt)}
    H, PSx = xg.hidden_size, (8 if small else 256)
    S, steps, slots = (24, 3, 3) if small else (512, 4, 4)
    h = jax.random.normal(next(key), (1, S + steps, H), xdt)
    w = {k_: att[k_]["kernel"] for k_ in ("q_a", "q_b", "kv_a", "kv_b", "o")}
    w.update(q_a_norm=att["q_a_norm"]["scale"], kv_norm=att["kv_norm"]["scale"])
    with jax.default_matmul_precision("highest"):
        ref = latent_decoder._latent_attention(
            h[0].astype(jnp.float32), w, pub, None)
    n_pages = (S + steps) // PSx + 2
    pool = jnp.zeros((1, 1 + slots * n_pages, 1, PSx, xg.mla.page_width),
                     xdt)
    tables = jnp.asarray(1 + np.arange(slots * n_pages, dtype=np.int32)
                         .reshape(slots, n_pages))
    inv_x = model_rope_frequencies(xg)

    def latent_step(hh, start, ok, pool, layer):
        T = hh.shape[1]
        pos = start[:, None] + jnp.arange(T, dtype=jnp.int32)
        out, (pool, _) = latent_attention_mixer(
            hh, layer, xg, pos, inv_x, attend_latent_pages(
                xg, pool, 0, tables, start, ok))
        return out, pool
    latent_step = jax.jit(latent_step, donate_argnums=(3,))
    hh = jnp.zeros((slots, S, H), xdt).at[1].set(h[0, :S])
    ok = jnp.zeros((slots, S), bool).at[1].set(True)
    got, pool = latent_step(hh, jnp.zeros((slots,), jnp.int32), ok, pool, att)
    check(f"latent_attention_mixer window [{S} rows through pages of {PSx}, "
          f"{xg.num_heads} heads, latent {xg.mla.latent_size} in "
          f"{xg.mla.page_width}]", got[1], ref[:S])
    decoded = []
    for t in range(steps):
        hh = jnp.zeros((slots, 1, H), xdt).at[1, 0].set(h[0, S + t])
        start = jnp.zeros((slots,), jnp.int32).at[1].set(S + t)
        out, pool = latent_step(hh, start, jnp.arange(slots)[:, None] == 1,
                                pool, att)
        decoded.append(out[1, 0])
    check(f"latent_attention_mixer decode [{steps} steps over the latent "
          f"pool, slot 1 of {slots} live]", jnp.stack(decoded), ref[S:])

    mlp_l = jax.tree_util.tree_map(lambda a: a[0], blocks["mlp"])
    hc = mlp_l["hc"]
    n_s = xg.hc_mult
    hc.update(
        norm={"scale": jax.random.uniform(next(key), hc["norm"]["scale"].shape,
                                          minval=-0.5, maxval=0.5)},
        b_pre=jax.random.uniform(next(key), (n_s,), minval=-1.0, maxval=1.0),
        b_post=jax.random.uniform(next(key), (n_s,), minval=-1.0, maxval=1.0),
        b_res=jnp.eye(n_s) + jax.random.uniform(next(key), (n_s, n_s),
                                                minval=-1.0, maxval=1.0))
    rows = 16 if small else 256
    X = jax.random.normal(next(key), (1, rows, n_s, H), xdt)
    got = jax.jit(lambda X, layer: decoder_block(
        X, layer, xg, jnp.zeros((1, rows), jnp.int32), inv_x, None,
        kind="D")[0])(X, mlp_l)
    with jax.default_matmul_precision("highest"):
        Xf = X[0].astype(jnp.float32)
        stack = jax.tree_util.tree_map(lambda a: a[None], mlp_l)
        pre, post, res = latent_decoder.hyper_connection_maps(
            Xf, stack["hc"], 0, pub)
        u = latent_decoder._rms_norm(
            jnp.einsum("sn,snc->sc", pre, Xf), mlp_l["norm"]["scale"],
            xg.norm_eps)
        out = latent_decoder._mlp(u, mlp_l["gate"]["kernel"],
                                  mlp_l["up"]["kernel"],
                                  mlp_l["down"]["kernel"])
        ref = (jnp.einsum("sij,sjc->sic", res, Xf)
               + post[:, :, None] * out[:, None, :])
    check(f"hyper-connected dense layer [{rows} rows, {n_s} streams of {H}, "
          f"{xg.hc_sinkhorn_iters} Sinkhorn iterations]", got[0], ref)

    # -- data packer: built here from native/dataloader.cpp -------------------
    from distributed_llm_training_and_inference_system_tpu.io import native
    from distributed_llm_training_and_inference_system_tpu.io.data import (
        MemmapDataset, write_token_shard)
    shard_dir = SCRATCH / "packer"
    shutil.rmtree(shard_dir, ignore_errors=True)
    rng = np.random.default_rng(0)
    write_token_shard(shard_dir / "a.bin", [
        rng.integers(1, 50000, size=int(n)) for n in
        rng.integers(20, 900, size=200)])
    t0 = time.monotonic()
    had_lib = native._LIB.exists()
    ds_native = MemmapDataset(shard_dir, 4, 512, seed=1)
    impl = "native" if ds_native._native is not None else "numpy"
    detail = (("library was already built" if had_lib else
               f"built from native/dataloader.cpp in "
               f"{time.monotonic() - t0:.1f}s")
              if impl == "native" else "g++ build or load failed")
    if impl == "native":
        os.environ["LLMCTL_NO_NATIVE"] = "1"
        ds_numpy = MemmapDataset(shard_dir, 4, 512, seed=1)
        del os.environ["LLMCTL_NO_NATIVE"]
        for _ in range(3):
            a, b = next(ds_native), next(ds_numpy)
            if any(not np.array_equal(a[k], b[k]) for k in a):
                failures.append("native packer batches differ from numpy")
        detail += "; 3 batches equal to the numpy packer's"
    emit("packer", {"impl": impl, "detail": detail})
    shutil.rmtree(shard_dir, ignore_errors=True)

    # (PR 46) ``solar_open2``: one GATED softmax layer without rope and one
    # delta-rule layer at 64 heads whose beta reaches 2, at
    # Solar-Open2-250B's widths, each against float32; then a SNAPSHOT of a
    # slot's state taken and armed
    from benchmark.reference import sessions_decoder
    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        SOLAR_OPEN2_PUBLISHED, SOLAR_OPEN2_TEST_PUBLISHED)
    from distributed_llm_training_and_inference_system_tpu.models.layers import (
        attend_fresh, attention_mixer, kda_mixer, model_rope_frequencies)
    from distributed_llm_training_and_inference_system_tpu.ops import kda
    pub = dict(SOLAR_OPEN2_TEST_PUBLISHED if small else SOLAR_OPEN2_PUBLISHED,
               num_hidden_layers=2, gqa_layers=[0])
    pub.update(n_routed_experts=4, router_experts=pub["n_routed_experts"])
    so = dataclasses.replace(ModelConfig.from_published(pub), dtype="bfloat16")
    blocks = jax.jit(lambda k: gpt.init(so, k, jnp.bfloat16)["blocks"])(
        next(key))
    kd, H = so.kda, so.hidden_size
    S, n_live, steps, slots = (32, 21, 3, 4) if small else (256, 200, 4, 8)
    x = jax.random.normal(next(key), (1, S + steps, H), jnp.float32)
    pos = jnp.arange(S)[None]
    att = jax.tree_util.tree_map(lambda a: a[0], blocks["attn"])
    h_att = rms_norm(x[:, :S], att["norm"]["scale"], so.norm_eps).astype(
        jnp.bfloat16)
    got_a, _ = jax.jit(lambda h, p: attention_mixer(
        h, p, so, pos, model_rope_frequencies(so),
        attend_fresh(pos, None)))(h_att, att)
    w_att = {k: att[k]["kernel"] for k in ("q", "k", "v", "gate", "o")}
    ref_gated, ref_plain = (sessions_decoder._attention(
        h_att[0].astype(jnp.float32), w_att, pub, wrong)
        for wrong in (None, "no_gate"))
    check(f"attention_mixer gated, no rope [{S} rows, {so.num_heads} query / "
          f"{so.num_kv_heads} key-value heads of {so.head_dim}]", got_a[0],
          ref_gated)
    if float(jnp.abs(ref_gated - ref_plain).max()) < 0.1 * float(
            jnp.abs(ref_gated).max()):
        failures.append("the gate moves the softmax layer's output by "
                        "under a tenth of it: the check cannot see it")
    mix = jax.tree_util.tree_map(lambda a: a[0], blocks["kda"])
    mix["gate_norm"] = {"scale": jax.random.uniform(
        next(key), mix["gate_norm"]["scale"].shape, minval=-0.5, maxval=0.5
    ).astype(jnp.bfloat16)}
    h_k = rms_norm(x, mix["norm"]["scale"], so.norm_eps).astype(jnp.bfloat16)
    seq = jnp.concatenate([h_k[0, :n_live], h_k[0, S:]]).astype(jnp.float32)
    w_k = {k: mix[k]["kernel"] for k in ("in_proj", "conv", "f_b", "g_b",
                                         "out_proj")}
    w_k.update(A_log=mix["A_log"], dt_bias=mix["dt_bias"],
               gate_norm=mix["gate_norm"]["scale"])
    ref_k, _ = sessions_decoder._kda(seq, w_k, pub, None, seq.shape[0], 0, 0)
    halved, _ = sessions_decoder._kda(seq, w_k, pub, "beta_unscaled",
                                      seq.shape[0], 0, 0)
    if float(jnp.abs(ref_k - halved).max()) < 0.05 * float(
            jnp.abs(ref_k).max()):
        failures.append("beta's factor 2 moves the K layer's output by "
                        "under a twentieth of it: the check cannot see it")
    live = jnp.arange(S)[None] < n_live
    got_w, (tail, kstate) = jax.jit(lambda h, p: kda_mixer(
        h, p, so, kda.recur_window(so, live)))(h_k[:, :S], mix)
    check(f"kda_mixer window, beta in (0, 2) [{S} rows, {n_live} live, "
          f"{kd.num_heads} heads of {kd.head_dim}]", got_w[0, :n_live],
          ref_k[:n_live], tol=3e-2)
    # the pools as the engine lays them: conv [Lk, K-1, slots, C], state
    # [Lk, slots, nh, dk, dv]; slot 2 holds the window's state in layer 1
    conv_pool = jnp.zeros((2, kd.conv_kernel - 1, slots, kd.conv_channels),
                          jnp.bfloat16).at[1, :, 2].set(
        tail[0].astype(jnp.bfloat16))
    state_pool = jnp.zeros((2, slots, *kstate.shape[1:]), jnp.float32
                           ).at[1, 2].set(kstate[0])
    snaps = kda.snapshot_pools(conv_pool, state_pool, 3)
    take = jax.jit(kda.kda_snapshot_take, donate_argnums=(2, 3))
    arm = jax.jit(kda.kda_snapshot_arm, donate_argnums=(0, 1))
    snap_conv, snap_state = take(conv_pool, state_pool, snaps["conv"],
                                 snaps["ssm"], jnp.int32(2), jnp.int32(1))

    def decode_slot(slot, conv_pool, state_pool):
        ok = jnp.arange(slots)[:, None] == slot
        step = jax.jit(lambda h, c, s_, p: kda_mixer(
            h, p, so, kda.recur_step(so, c, s_, 1, ok)),
            donate_argnums=(1, 2))
        decoded = []
        for t in range(steps):
            h_t = jnp.zeros((slots, 1, H), jnp.bfloat16).at[slot, 0].set(
                h_k[0, S + t])
            out, (conv_pool, state_pool) = step(h_t, conv_pool, state_pool,
                                                mix)
            decoded.append(out[slot, 0])
        return jnp.stack(decoded), conv_pool, state_pool
    decoded, conv_pool, state_pool = decode_slot(2, conv_pool, state_pool)
    check(f"kda_mixer decode, beta in (0, 2) [{steps} steps over the state "
          f"pools, slot 2 of {slots} live, {kd.num_heads} heads]", decoded,
          ref_k[n_live:], tol=3e-2)
    if float(jnp.abs(state_pool[:, jnp.asarray([0, 1, 3])]).max()) != 0.0:
        failures.append("kda decode wrote an idle slot's state")
    # slot 0 armed from the entry decodes the same tokens to the same bits,
    # whatever slot 2 did to its own rows since the snapshot was taken
    conv_pool, state_pool = arm(conv_pool, state_pool, snap_conv, snap_state,
                                jnp.int32(0), jnp.int32(1))
    again, conv_pool, state_pool = decode_slot(0, conv_pool, state_pool)
    emit("kernel", {"name": "kda snapshot taken from slot 2, armed into "
                            "slot 0: the decode steps repeat bit for bit",
                    "equal": bool(jnp.array_equal(again, decoded))})
    if not bool(jnp.array_equal(again, decoded)):
        failures.append("a slot armed from a snapshot decodes other values "
                        "than the slot the snapshot was taken from")
    if float(jnp.abs(snap_state[:, jnp.asarray([0, 2])]).max()) != 0.0:
        failures.append("a snapshot's take wrote another entry")

    # (PR 49) ``falcon_h1``: attention (a query group of FIVE) AND a Mamba-2
    # mixer (2 groups, state 256) under one norm, twelve muP multipliers,
    # at Falcon-H1-34B's widths (one published layer, 8,192 rows of the
    # vocabulary): cold prefill of a padded bucket writes the layer's pages
    # and arms its state, eight decode steps read and write both pools; the
    # logits beside the float32 reference's
    from benchmark.reference import parallel_decoder
    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        FALCON_H1_34B_PUBLISHED, FALCON_H1_TEST_PUBLISHED)
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        decode_step_forward)
    pub = dict(FALCON_H1_TEST_PUBLISHED if small else dict(
        FALCON_H1_34B_PUBLISHED, vocab_size=8192), num_hidden_layers=1)
    fh = dataclasses.replace(ModelConfig.from_published(pub),
                             dtype="bfloat16")
    fparams = jax.jit(lambda k: gpt.init(fh, k, jnp.bfloat16))(next(key))
    par = dict(fparams["blocks"]["par"])
    par["D"] = jax.random.uniform(next(key), par["D"].shape, minval=0.5,
                                  maxval=1.5)
    par["gate_norm"] = {"scale": jax.random.uniform(
        next(key), par["gate_norm"]["scale"].shape, minval=-0.5, maxval=0.5
    ).astype(jnp.bfloat16)}
    fparams = dict(fparams, blocks=dict(fparams["blocks"], par=par))
    S, n_live, steps, slots = (32, 21, 8, 4) if small else (256, 200, 8, 8)
    seq = jax.random.randint(next(key), (n_live + steps,), 1,
                             fh.vocab_size).tolist()
    want = parallel_decoder.logits(fparams, seq, pub,
                                   positions=range(n_live - 1, len(seq)))
    live = (jnp.arange(S)[None] < n_live).astype(jnp.int32)
    padded = jnp.asarray([seq[:n_live] + [7] * (S - n_live)], jnp.int32)
    first, (kd_, vd_), (tails, hs) = jax.jit(lambda p, t: gpt.forward(
        p, t, fh, kv_cache=gpt.init_kv_cache(fh, 1, S),
        cache_offset=jnp.zeros((1,), jnp.int32), segment_ids=live,
        unembed_positions=jnp.asarray([n_live - 1]),
        return_ssm_state=True))(fparams, padded)
    n_pages = S // PS + 2
    table = np.zeros((slots, n_pages), np.int32)
    table[2] = np.arange(1, n_pages + 1)

    def paged(d):
        return d[:, 0].reshape(1, S // PS, PS, fh.num_kv_heads, fh.head_dim
                               ).transpose(0, 1, 3, 2, 4)
    pool = jnp.zeros((1, n_pages + 1, fh.num_kv_heads, PS, fh.head_dim),
                     jnp.bfloat16)
    kp = pool.at[:, 1:S // PS + 1].set(paged(kd_))
    vp = pool.at[:, 1:S // PS + 1].set(paged(vd_))
    fs = fh.ssm
    fstate = {
        "conv": jnp.zeros((1, slots, fs.conv_kernel - 1, fs.conv_channels),
                          jnp.bfloat16).at[:, 2].set(
            tails[:, 0].astype(jnp.bfloat16)),
        "ssm": jnp.zeros((1, slots, fs.num_heads, fs.head_dim,
                          fs.state_size), jnp.float32).at[:, 2].set(hs[:, 0])}
    step_fn = jax.jit(lambda p, t, pos, kp, vp, st: decode_step_forward(
        p, t, pos, kp, vp, jnp.asarray(table), fh,
        active=jnp.arange(slots) == 2, ssm_state=st),
        donate_argnums=(3, 4, 5))
    got = [first[0, 0]]
    for t in range(steps):
        out = step_fn(fparams,
                      jnp.full((slots,), seq[n_live + t], jnp.int32),
                      jnp.full((slots,), n_live + t, jnp.int32), kp, vp,
                      fstate)
        kp, vp, fstate = out.k_pages, out.v_pages, out.state
        got.append(out.logits[2])
    check(f"falcon_h1 layer: cold prefill [{S} rows, {n_live} live] then "
          f"{steps} decode steps through pages AND state, {fh.num_heads} / "
          f"{fh.num_kv_heads} heads, {fs.n_groups} groups of state "
          f"{fs.state_size}, logits against the float32 reference",
          jnp.stack(got), want, tol=5e-2)
    if float(jnp.abs(fstate["ssm"][:, jnp.asarray([0, 1, 3])]).max()) != 0.0:
        failures.append("falcon_h1 decode wrote an idle slot's state")
    # (PR 50) ... and one step with NO live slot: both state pools bit for bit
    stood = jax.tree_util.tree_map(np.asarray, fstate)
    out = jax.jit(lambda p, t, pos, kp, vp, st: decode_step_forward(
        p, t, pos, kp, vp, jnp.asarray(table), fh,
        active=jnp.zeros((slots,), bool), ssm_state=st),
        donate_argnums=(3, 4, 5))(
        fparams, jnp.full((slots,), 7, jnp.int32),
        jnp.full((slots,), n_live + steps, jnp.int32), kp, vp, fstate)
    if not all(np.array_equal(np.asarray(out.state[k]), stood[k])
               for k in stood):
        failures.append("a falcon_h1 decode step with no live slot wrote a "
                        "state")
    for wrong in ("drop_attention", "drop_ssm"):
        moved = float(jnp.abs(want - parallel_decoder.logits(
            fparams, seq, pub, positions=range(n_live - 1, len(seq)),
            wrong=wrong)).max())
        if moved < 0.25 * float(jnp.abs(want).max()):
            failures.append(f"falcon_h1: {wrong} moves the logits by under "
                            "a quarter of them: the check cannot see it")

    # (PR 63) ``mellum``: ONE window layer (1,024 keys, plain rope) and ONE
    # full layer (YaRN, cos / sin x attention_factor) at Mellum2-12B-A2.5B's
    # widths (GQA 32 / 4 x 128, per-head q/k norms, 64 experts of 896, 8 a
    # token; 8,192 rows of the vocabulary), in FLOAT32 with full-precision
    # matmuls: a 1,536-row context goes through pages of 128 in chunks of
    # two pages, the window layer's rows into a ring of 10 pages (which the
    # context takes round once), then 300 decode steps write pages 12-14
    # over the ring's entries 2-4; every decode step's logits beside the
    # float32 reference's. In float32 the window's edge is visible: the
    # reference with a window of 1,023 or 1,025 keys must read further off
    # than the program does (no check on bfloat16 tokens can see that)
    from benchmark.reference import windowed_decoder
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        extend_step_forward)
    from distributed_llm_training_and_inference_system_tpu.serve.kv_cache import (
        PagedKVCache)
    base = get_model_config("mellum-test" if small else "mellum2-12b-a2.5b")
    mel = dataclasses.replace(
        base, num_layers=2, layer_types=("sliding", "full"), dtype="float32",
        vocab_size=base.vocab_size if small else 8192)
    mel.validate()
    r = mel.rope
    mpub = {
        "head_dim": mel.head_dim, "num_attention_heads": mel.num_heads,
        "num_key_value_heads": mel.num_kv_heads,
        "rms_norm_eps": mel.norm_eps, "sliding_window": mel.sliding_window,
        "num_experts_per_tok": mel.moe.experts_per_token,
        "norm_topk_prob": True,
        "layer_types": ["sliding_attention", "full_attention"],
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": r.base,
                "factor": r.scaling_factor, "beta_fast": r.beta_fast,
                "beta_slow": r.beta_slow,
                "original_max_position_embeddings": r.original_max_position,
                "attention_factor": r.attention_factor},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": mel.window_rope.base}}}
    mps, chunk, ctx, steps = (8, 16, 48, 30) if small else (128, 256, 1536,
                                                           300)
    with jax.default_matmul_precision("highest"):
        mparams = jax.jit(lambda k: gpt.init(mel, k, jnp.float32))(next(key))
        mparams = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jax.random.uniform(
                jax.random.fold_in(jax.random.PRNGKey(63), leaf.size),
                leaf.shape, leaf.dtype, -0.3, 0.3)
            if path[-1].key == "scale" else leaf, mparams)
        seq = jax.random.randint(next(key), (ctx + steps,), 1,
                                 mel.vocab_size).tolist()
        positions = range(ctx - 1, len(seq) - 1)
        want = windowed_decoder.logits(mparams, seq, mpub,
                                       positions=positions, round_to=2048)
        mkv = PagedKVCache(mel, num_slots=4, max_seq_len=2048 if not small
                           else 128, page_size=mps, num_pages=24,
                           dtype=jnp.float32, window_rows=chunk)
        mkv.allocate(2, len(seq))
        mtable = jnp.asarray(mkv.block_tables)
        chunk_fn = jax.jit(lambda p, t, s, kp, vp: extend_step_forward(
            p, t, s, kp, vp, mtable[2][None], mel), donate_argnums=(3, 4))
        kp, vp = mkv.k_pages, mkv.v_pages
        for start in range(0, ctx, chunk):
            out = chunk_fn(mparams, jnp.asarray([seq[start:start + chunk]]),
                           jnp.asarray([start], jnp.int32), kp, vp)
            kp, vp = out.k_pages, out.v_pages
        got = [out.logits[0, -1]]
        mstep = jax.jit(lambda p, t, pos, kp, vp: decode_step_forward(
            p, t, pos, kp, vp, mtable, mel, active=jnp.arange(4) == 2),
            donate_argnums=(3, 4))
        for t in range(ctx, len(seq) - 1):
            out = mstep(mparams, jnp.full((4,), seq[t], jnp.int32),
                        jnp.full((4,), t, jnp.int32), kp, vp)
            kp, vp = out.k_pages, out.v_pages
            got.append(out.logits[2])
        got = jnp.stack(got)
        check(f"mellum window + full layer: {ctx} rows through pages of "
              f"{mps} in chunks of {chunk} then {len(got) - 1} decode steps "
              f"over a ring of {mkv.ring_entries} pages, {mel.num_heads} / "
              f"{mel.num_kv_heads} heads, window {mel.sliding_window}, "
              "float32 logits against the float32 reference", got, want,
              tol=2e-3)
        off = float(jnp.abs(got - want).max())
        for wrong in ("window_minus_1", "window_plus_1", "all_full",
                      "no_attention_factor"):
            moved = float(jnp.abs(want - windowed_decoder.logits(
                mparams, seq, mpub, positions=positions, wrong=wrong,
                round_to=2048)).max())
            emit("kernel", {"name": f"mellum reference {wrong} moves the "
                            "logits by", "max_abs_diff": moved,
                            "program_off_by": off})
            if moved < 4 * off:
                failures.append(
                    f"mellum: {wrong} moves the logits by {moved:.2e}, the "
                    f"program is off by {off:.2e}: the check cannot see it")

    if failures:
        print("kernel check failures:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        sys.exit(1)


def child_mesh_train() -> None:
    device = child_setup()
    import gc

    import jax
    import numpy as np

    from distributed_llm_training_and_inference_system_tpu.config.loader import (
        load_run_config)
    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.parallel.sharding import (
        use_mesh)
    from distributed_llm_training_and_inference_system_tpu.runtime.engine import (
        TrainingEngine)
    from distributed_llm_training_and_inference_system_tpu.runtime.train_entry import (
        parse_overrides)

    if device["count"] < 4:
        print(f"--chips 4 needs four devices, jax reports {device}",
              file=sys.stderr)
        sys.exit(2)
    devices = jax.devices()[:4]
    sets = training_overrides(SCRATCH / "mesh_ckpt")[1::2]

    def run(devs, extra):
        cfg = load_run_config(None, cli_overrides=parse_overrides(
            sets + extra))
        cfg.model = get_model_config("gpt-test" if REHEARSAL else "gpt-750m")
        eng = TrainingEngine(cfg, devices=list(devs))
        eng.initialize(resume=False)
        tr = eng.trainer
        losses, text = [], None
        for _ in range(8):
            batch = next(eng.train_data)
            if text is None and len(devs) > 1:
                with use_mesh(tr.mesh):
                    text = tr.train_step.lower(
                        tr.state, tr.shard_batch(batch)).compile().as_text()
            losses.append(float(tr.step(batch)["loss"]))
        per_dev: dict = {}
        total = 0
        for leaf in jax.tree_util.tree_leaves(tr.state.params):
            total += leaf.nbytes
            for sh in leaf.addressable_shards:
                per_dev[sh.device.id] = (per_dev.get(sh.device.id, 0)
                                         + sh.data.nbytes)
        eng.close()
        tr.state = None
        del eng, tr
        gc.collect()
        return losses, per_dev, total, text

    loss_4, per_dev, total, text = run(
        devices, ["parallel.fsdp=2", "parallel.tensor_parallel=2"])
    loss_1, _, _, _ = run(devices[:1], [])
    collectives = {k: text.count(k) for k in (
        "all-reduce", "all-gather", "reduce-scatter", "collective-permute")}
    max_diff = float(np.abs(np.asarray(loss_4) - np.asarray(loss_1)).max())
    rec = {"loss_1": loss_1, "loss_4": loss_4, "max_diff": max_diff,
           "tol": 0.05, "collectives": collectives,
           "param_bytes_per_device": {str(k): v for k, v in
                                      sorted(per_dev.items())},
           "param_bytes_total": total,
           "largest_share": max(per_dev.values()) / total}
    emit("mesh_train", rec)
    bad = []
    if not np.isfinite(loss_4 + loss_1).all() or max_diff > rec["tol"]:
        bad.append(f"losses differ by {max_diff}")
    if not sum(collectives.values()):
        bad.append("no collective in the compiled 4-device step")
    if len(per_dev) != 4 or rec["largest_share"] > 0.35:
        bad.append(f"parameters are not spread over four devices: {per_dev}")
    if bad:
        print("mesh_train failures: " + "; ".join(bad), file=sys.stderr)
        sys.exit(1)


def child_decode_memory() -> None:
    """Lower and compile the engine's own decode function with its own
    arguments (the server child compiled the same program a moment ago:
    a read of the compile cache) and report its memory analysis."""
    child_setup()
    import jax
    import jax.numpy as jnp

    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ServeConfig)
    from distributed_llm_training_and_inference_system_tpu.serve.engine import (
        InferenceEngine)
    name = os.environ["CHIP_SMOKE_MODEL"]
    model = get_model_config(name)
    # what `serve start --model <name>` builds in phase_serve
    engine = InferenceEngine(model, ServeConfig(
        model=name, max_seq_len=min(2048, model.max_position_embeddings),
        **({"kv_block_size": 16, "dtype": "float32"} if REHEARSAL else {})))
    program = engine._decode_jit
    mem = program.lower(
        engine.params, engine.kv.k_pages, engine.kv.v_pages,
        jnp.asarray(engine.last_tokens), jnp.asarray(engine.positions),
        *engine._shared_decode_args(),
        *engine._decode_tail_args()).compile().memory_analysis()
    emit("decode_memory", {
        "program": program.name, "layers": model.num_layers,
        "steps": engine.serve_cfg.decode_steps_per_dispatch,
        "slots": engine.serve_cfg.max_batch_size,
        "temp_bytes": mem.temp_size_in_bytes,
        "argument_bytes": mem.argument_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "k_pool_bytes": sum(
            a.nbytes for a in jax.tree_util.tree_leaves(engine.kv.k_pages))})


def child_replicas() -> None:
    device = child_setup()
    import jax

    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        FleetConfig, ServeConfig)
    from distributed_llm_training_and_inference_system_tpu.serve.server import (
        create_server)
    model = get_model_config("gpt-test")
    server = create_server(
        model, ServeConfig(model="gpt-test", max_seq_len=128,
                           kv_block_size=16, dtype="float32"),
        fleet_cfg=FleetConfig(replicas=4))
    try:
        placed = []
        for rep in server.fleet.replicas:
            ids = set()
            for leaf in jax.tree_util.tree_leaves(rep.engine.params):
                ids |= {d.id for d in leaf.devices()}
            placed.append(sorted(ids))
    finally:
        server.fleet.shutdown()
    emit("replicas", {"devices": placed,
                      "distinct": len({i for p in placed for i in p}),
                      "available": device["count"]})


def child_tp_exact() -> None:
    """The engine at tp=1 and tp=N with float32 weights, activations and
    pages and full-precision matmuls: what is left between the two is the
    order of float32 sums. Greedy tokens must all agree, and the dense
    forward's logits along tp=1's tokens, with the engine's sharded
    parameters against the unsharded ones, must agree to float32 noise
    (their margins are printed: the smallest says how near a tie the
    comparison came)."""
    device = child_setup()
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ServeConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    from distributed_llm_training_and_inference_system_tpu.serve.engine import (
        InferenceEngine)
    from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
        SamplingParams)

    tp, model, n_a, new_a, extra = tp_case()
    if device["count"] < tp:
        print(f"tp={tp} needs {tp} devices, jax reports {device}",
              file=sys.stderr)
        sys.exit(2)
    jax.config.update("jax_default_matmul_precision", "highest")
    cfg = get_model_config(model)
    cfg.dtype = "float32"
    prompt = prompt_tokens(1, n_a, cfg.vocab_size)
    tokens, logits = {}, {}
    for degree in (1, tp):
        eng = InferenceEngine(cfg, ServeConfig(
            model=model, dtype="float32", tensor_parallel=degree,
            max_seq_len=min(2048, cfg.max_position_embeddings), **extra),
            seed=0)
        [req] = eng.generate([prompt], SamplingParams(temperature=0.0,
                                                      max_tokens=new_a))
        tokens[degree] = list(req.generated_tokens)
        forced = jnp.asarray([prompt + tokens[1]], jnp.int32)
        logits[degree] = np.asarray(jax.jit(
            lambda p, t: gpt.forward(p, t, cfg))(eng.params, forced)
            [0, n_a - 1:-1], np.float32)
        del eng, req
        gc.collect()
    same = next((i for i, (a, b) in enumerate(zip(tokens[tp], tokens[1]))
                 if a != b), min(len(tokens[tp]), len(tokens[1])))
    top2 = np.sort(logits[1], axis=-1)[:, -2:]
    margins = top2[:, 1] - top2[:, 0]
    emit("tp_exact", {
        "tokens": {str(k): v for k, v in tokens.items()}, "identical": same,
        "logit_diff": float(np.abs(logits[tp] - logits[1]).max()),
        "logit_max": float(np.abs(logits[1]).max()),
        "min_margin": float(margins.min()),
        "min_margin_at": int(margins.argmin())})


def child_seeded() -> None:
    """The engine's seeded replies against ``seeded_reference``, with
    float32 weights, activations and pages and full-precision matmuls, so
    that the paged programs and the dense forward differ in the order of
    float32 sums alone and a sample does not turn on rounding."""
    child_setup()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ServeConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    from distributed_llm_training_and_inference_system_tpu.serve.engine import (
        InferenceEngine)
    from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
        Request, SamplingParams)

    model, lengths, new, span, extra = (
        ("gpt-test", (9, 17, 26, 33, 40, 48, 57, 64), 24, 128,
         {"kv_block_size": 16}) if REHEARSAL
        else ("gpt-125m", (33, 64, 97, 190, 256, 411, 700, 900), 64, 1024,
              {}))
    jax.config.update("jax_default_matmul_precision", "highest")
    cfg = get_model_config(model)
    cfg.dtype = "float32"
    eng = InferenceEngine(cfg, ServeConfig(
        model=model, dtype="float32", max_batch_size=8, max_seq_len=span,
        **extra), seed=0)
    prompts = [prompt_tokens(30 + i, n, cfg.vocab_size)
               for i, n in enumerate(lengths)]
    reqs = [Request(request_id=f"seeded-{i}", prompt_tokens=p,
                    sampling=SamplingParams(temperature=0.8, top_k=40,
                                            top_p=0.9, max_tokens=new,
                                            seed=1000 + i))
            for i, p in enumerate(prompts)]
    for r in reqs:
        if not eng.scheduler.add_request(r):
            raise RuntimeError(r.error)
    eng.run_until_idle()
    forward = jax.jit(lambda p, t: gpt.forward(p, t, cfg))

    def next_logits(context):
        padded = np.zeros((1, span), np.int32)
        padded[0, :len(context)] = context
        return forward(eng.params, jnp.asarray(padded))[0, len(context) - 1]
    identical = []
    for r in reqs:
        want = seeded_reference(next_logits, r.prompt_tokens, r.sampling, new)
        got = list(r.generated_tokens)
        identical.append(next((i for i, (a, b) in enumerate(zip(got, want))
                               if a != b), min(len(got), len(want))))
    emit("seeded", {"model": model, "requests": len(reqs), "new": new,
                    "identical": identical})


def child_ride() -> None:
    """Four prompts served twice by one engine: to the BUSY engine (more
    than half its slots resident: the prompts ride the decode dispatches)
    and to the drained one (a prefill program), in float32 with
    full-precision matmuls as ``child_seeded``, so that the two paths
    differ in the order of float32 sums alone. The benchmark's own check
    posts its prompts to an idle engine: this is where the chip holds the
    riding path to the prefill programs' tokens. Two arms: mistral-7b's
    widths from position 0 (the cold program), then a latent-attention
    model at Xing4.0's widths (``doc-qa-64``'s configuration, the dense
    layer and one expert layer) behind a document's cached pages (the
    suffix program, the window through ``mla_paged_attention_mq`` in both),
    then Kimi-Linear's widths (``reason-docs-128``'s configuration, one
    period ``K K K *``): prompts of 3 and 2 pages from position 0, each
    piece a window from its slot's own ``K`` state, against the chunk and
    final-chunk programs (chunks of one page on the drained engine)."""
    child_setup()
    import dataclasses

    import jax

    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig, ServeConfig)
    from distributed_llm_training_and_inference_system_tpu.serve.engine import (
        InferenceEngine)
    from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
        Request, SamplingParams)

    jax.config.update("jax_default_matmul_precision", "highest")

    def arm(model, cfg, slots, lengths, new, span, prefix, extra):
        """``prefix`` > 0: the prompts share a document of that many
        tokens, put in the prefix cache before each pass."""
        eng = InferenceEngine(cfg, ServeConfig(
            model=model, dtype="float32", max_batch_size=slots,
            max_seq_len=span, **extra), seed=0)
        resident = slots // 2 + 1
        residents = [Request(request_id=f"resident-{i}",
                             prompt_tokens=prompt_tokens(50 + i, 24 + i,
                                                         cfg.vocab_size),
                             # (a prompt of 24 + i tokens: with the
                             # linear arm's 65 residents the longest and
                             # ``span - 64`` would pass ``max_seq_len``)
                             sampling=SamplingParams(
                                 temperature=0.0,
                                 max_tokens=span - 64 - resident))
                     for i in range(resident)]
        document = prompt_tokens(69, prefix, cfg.vocab_size)
        prompts = [document + prompt_tokens(70 + i, n, cfg.vocab_size)
                   for i, n in enumerate(lengths)]

        def submit(reqs):
            for r in reqs:
                if not eng.scheduler.add_request(r):
                    raise RuntimeError(r.error)

        def serve(tag):
            if prefix:      # the document's whole pages into the cache
                eng.generate([document + prompt_tokens(68, 9, cfg.vocab_size)],
                             SamplingParams(temperature=0.0, max_tokens=1))
            if tag == "riding":
                submit(residents)
                while int(eng.active.sum()) < resident:
                    eng.step()
            reqs = [Request(request_id=f"{tag}-{i}", prompt_tokens=p,
                            sampling=SamplingParams(temperature=0.0,
                                                    max_tokens=new))
                    for i, p in enumerate(prompts)]
            before = eng.stats()
            submit(reqs)
            while any(r.finish_time is None for r in reqs):
                eng.step()
            after = eng.stats()
            return ([list(r.generated_tokens) for r in reqs],
                    {k: after[k] - before[k]
                     for k in ("prefill_ride_tokens", "prefill_ride_steps",
                               "prefix_cached_tokens")})
        rode, counted = serve("riding")
        with eng.lock:
            for r in residents:
                eng.scheduler.cancel(r.request_id)
        eng.run_until_idle()
        # (or the second pass finds the first's pages and prefills less)
        eng.kv.flush_prefix_cache()
        cold, cold_counted = serve("cold")
        eng.release()
        emit("ride", {
            "model": model, "slots": slots,
            # (a layer table counts a decoder layer's two sub-layers)
            "layers": (len(cfg.layer_pattern) // 2 if cfg.layer_pattern
                       else cfg.num_layers),
            "resident": resident, "new": new,
            "prompt_tokens": sum(lengths),
            "cached_tokens": [counted["prefix_cached_tokens"],
                              cold_counted["prefix_cached_tokens"]],
            "rode_tokens": counted["prefill_ride_tokens"],
            "ride_steps": counted["prefill_ride_steps"],
            "cold_rode_tokens": cold_counted["prefill_ride_tokens"],
            "identical": [next((i for i, (a, b) in enumerate(zip(x, y))
                                if a != b), min(len(x), len(y)))
                          for x, y in zip(rode, cold)]})

    def float32(cfg, **over):
        return dataclasses.replace(cfg, dtype="float32", **over)
    if REHEARSAL:
        arm("gpt-test", float32(get_model_config("gpt-test"), num_layers=2),
            8, (9, 17, 40, 70), 8, 128, 0, {"kv_block_size": 8})
        arm("xing-test", float32(get_model_config("xing-test")),
            8, (9, 17, 40, 70), 8, 256, 64, {"kv_block_size": 8})
        arm("kimi-linear-test", float32(get_model_config("kimi-linear-test")),
            8, (20, 17, 40, 70), 8, 256, 0,
            {"kv_block_size": 8, "chunked_prefill_tokens": 8})
        return
    arm("mistral-7b", float32(get_model_config("mistral-7b"), num_layers=4),
        32, (70, 128, 333, 700), 32, 2048, 0, {"kv_hbm_budget_gb": 2.0})
    published = json.loads((ROOT / "benchmark" / "configs"
                            / "xing4.0-29b-a4b-7l.json").read_text())
    # a document of 3 pages of 256, tails of one and two pieces
    arm(published["name"], float32(ModelConfig.from_published(
        dict(published, num_hidden_layers=2))),
        16, (70, 250, 300, 500), 32, 2048, 768,
        {"kv_block_size": published["serve"]["kv_block_size"],
         "kv_hbm_budget_gb": 1.0})
    published = json.loads((ROOT / "benchmark" / "configs"
                            / "kimi-linear-48b-a3b-12l-ep8.json").read_text())
    one_period = {"kda_layers": [1, 2, 3], "full_attn_layers": [4]}
    arm(published["name"], float32(ModelConfig.from_published(dict(
        published, num_hidden_layers=4, linear_attn_config=dict(
            published["linear_attn_config"], **one_period)))),
        published["serve"]["max_batch_size"], (700, 300), 32, 2048, 0,
        {"kv_block_size": published["serve"]["kv_block_size"],
         "kv_hbm_budget_gb": 1.0, "prefix_caching": False,
         "chunked_prefill_tokens": published["serve"]["kv_block_size"]})


def child_selfdraft() -> None:
    """JoyAI-LLM-Flash's published widths (``agent-turns-64``'s
    configuration: the dense layer, one expert layer with 128 of 256 experts
    held, the prediction module) served twice by the same weights, drafting
    (``speculative: mtp``: every step a window of two rows a slot through
    ``mla_paged_attention_mq``, 1 or 2 tokens a slot) and plain
    (``decode_scan``), in float32 with full-precision matmuls, on two sets
    of weights: CONSTRUCTED ones on which every draft stands (every output
    projection zero, so the stream is the token's embedding; ``W_eh`` = [I |
    0]; plain norms: the module's logits for position i + 2 are the main
    stack's), where a step makes exactly 2 tokens a slot, and SEEDED ones,
    where a draft stands at chance. The benchmark cell runs seeded weights:
    this is where the chip takes the two-token branch."""
    child_setup()
    import dataclasses

    import jax
    import jax.numpy as jnp

    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig, ServeConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    from distributed_llm_training_and_inference_system_tpu.serve.engine import (
        InferenceEngine)
    from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
        Request, SamplingParams)

    jax.config.update("jax_default_matmul_precision", "highest")
    if REHEARSAL:
        name, cfg = "joyai-test", get_model_config("joyai-test")
        slots, lengths, new, span, extra = 4, (9, 17, 30, 41), 9, 128, {
            "kv_block_size": 8}
    else:
        published = json.loads((ROOT / "benchmark" / "configs"
                                / "joyai-llm-flash-8l-ep2.json").read_text())
        name = published["name"]
        cfg = ModelConfig.from_published({
            k: v for k, v in dict(published, num_hidden_layers=2).items()
            if not isinstance(v, (dict, list))})
        slots, lengths, new, span, extra = 8, (
            70, 128, 250, 333, 500, 700, 900, 1000), 65, 2048, {
            "kv_block_size": published["serve"]["kv_block_size"],
            "kv_hbm_budget_gb": 1.0}
    cfg = dataclasses.replace(cfg, dtype="float32")
    seeded = jax.jit(lambda k: gpt.init(cfg, k, jnp.float32))(
        jax.random.PRNGKey(0))

    def zeros(tree, *path):
        """``tree`` with the kernel at ``path`` zero."""
        if not path:
            return {"kernel": jnp.zeros_like(tree["kernel"])}
        return dict(tree, **{path[0]: zeros(tree[path[0]], *path[1:])})
    blocks = seeded["blocks"]
    blocks = dict(blocks, attn=zeros(blocks["attn"], "o"),
                  mlp=zeros(blocks["mlp"], "down"),
                  moe=zeros(zeros(blocks["moe"], "down"), "shared", "down"))
    H = cfg.hidden_size
    standing = dict(seeded, blocks=blocks, mtp=dict(
        seeded["mtp"], eh_proj={"kernel": jnp.concatenate(
            [jnp.eye(H, dtype=jnp.float32), jnp.zeros((H, H), jnp.float32)])}))
    prompts = [prompt_tokens(80 + i, n, cfg.vocab_size)
               for i, n in enumerate(lengths)]

    def serve(weights, speculative):
        eng = InferenceEngine(cfg, ServeConfig(
            model=name, dtype="float32", max_batch_size=slots,
            max_seq_len=span, speculative=speculative,
            speculative_min_acceptance=0.0, **extra), params=weights)
        reqs = [Request(request_id=f"{speculative}-{i}", prompt_tokens=p,
                        sampling=SamplingParams(temperature=0.0,
                                                max_tokens=new,
                                                ignore_eos=True))
                for i, p in enumerate(prompts)]
        for r in reqs:
            if not eng.scheduler.add_request(r):
                raise RuntimeError(r.error)
        eng.run_until_idle()
        stats = eng.stats()
        eng.release()
        return [list(r.generated_tokens) for r in reqs], stats

    for which, weights in (("all-stand", standing), ("seeded", seeded)):
        plain, _ = serve(weights, "off")
        drafted, stats = serve(weights, "mtp")
        emit("selfdraft", {
            "model": name, "weights": which, "new": new,
            "layers": len(cfg.layer_pattern) // 2,
            "tokens": stats["mtp_tokens"],
            "slot_steps": stats["mtp_slot_steps"],
            "drafts": stats["mtp_drafts"], "accepted": stats["mtp_accepted"],
            "tokens_per_slot_step": stats["mtp_tokens"] / max(
                stats["mtp_slot_steps"], 1),
            "identical": [next((i for i, (a, b) in enumerate(zip(x, y))
                                if a != b), min(len(x), len(y)))
                          for x, y in zip(drafted, plain)]})


CHILDREN = {"kernels": child_kernels, "mesh_train": child_mesh_train,
            "tp_exact": child_tp_exact, "replicas": child_replicas,
            "decode_memory": child_decode_memory, "seeded": child_seeded,
            "ride": child_ride, "selfdraft": child_selfdraft}


# ---------------------------------------------------------------------------

def main() -> int:
    phase = os.environ.get(PHASE_ENV)
    if phase:
        CHILDREN[phase]()
        return 0

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the four-chip paths (mesh training, "
                         "tensor-parallel serving) and what they are "
                         "compared with")
    ap.add_argument("--parent", metavar="DIR",
                    help="the parent commit unpacked beside this tree (git "
                         "archive): the serve phase also holds this tree's "
                         "greedy and seeded tokens equal to that one's")
    ap.add_argument("--only", choices=("shortconv",),
                    help="this phase of the one-chip smoke alone (a server "
                         "of its own: ~3 min)")
    args = ap.parse_args()

    ok = False
    try:
        # (no jax in this import: utils/platform.py stays off it)
        from distributed_llm_training_and_inference_system_tpu.utils.platform import (
            enable_compile_cache)
        OUT.mkdir(parents=True, exist_ok=True)
        SCRATCH.mkdir(parents=True, exist_ok=True)
        cache_dir = enable_compile_cache()
        env = child_env(cache_dir)
        say(f"compile cache: {cache_dir} "
            f"({compile_cache_entries(cache_dir)} entries before)")
        if REHEARSAL:
            say("REHEARSAL: gpt-test sizes; this run cannot end ok")
        t0 = time.monotonic()
        if args.only:
            phase_shortconv(env)
        elif args.chips == 1:
            device = phase_kernels(env)
            phase_serve(env, device)
            phase_seeded_replies(env, args.parent)
            phase_ride(env)
            phase_selfdraft(env)
            phase_shortconv(env)
            phase_train(env, device)
            phase_launcher(env, device)
        else:
            device = phase_mesh_train(env)
            phase_tp_serve(env, device)
            phase_replica_placement(env)
        say(f"compile cache: {cache_dir} "
            f"({compile_cache_entries(cache_dir)} entries after); all phases "
            f"{time.monotonic() - t0:.0f}s")
        ok = (DEVICE["platform"] == "tpu" and DEVICE["count"] == args.chips
              and not REHEARSAL)
        if not ok:
            say(f"not ok: needs {args.chips} TPU device(s), the children "
                f"saw {DEVICE}" + (" (rehearsal)" if REHEARSAL else ""))
    except SmokeFailure as e:
        say(f"FAILED: {e}")
    except Exception as e:     # a broken checkout must still end in JSON
        say(f"FAILED: {type(e).__name__}: {e}")
    finally:
        shutil.rmtree(SCRATCH, ignore_errors=True)
    print(json.dumps({"ok": ok, "device": DEVICE}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
