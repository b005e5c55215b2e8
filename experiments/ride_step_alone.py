"""The decode step alone on the chip with a prompt's piece riding it: ms a
step and the longest operations' us a call (PERF.md 6, PR 36: what
``InferenceEngine.RIDE_PAGES`` was chosen from).

    chiprun -- python experiments/ride_step_alone.py [--models dense moe]
    chiprun -- python experiments/ride_step_alone.py --models latent \
        --rows 256 512
    chiprun -- python experiments/ride_step_alone.py --models linear \
        --rows 256 --cached 0 4096 12288
    chiprun -- python experiments/ride_step_alone.py --models hybrid \
        --rows 128 --live 64 --cached 0 384

The benchmark's riding configurations (``benchmark/configs``) at their
cells' shapes, 8 steps a dispatch, weights made on the device.
``mistral-7b-16l`` and ``olmoe-1b-7b-10l``: 32 slots, 715 pages of 64
tokens a layer; 31 slots are resident at ``batch-64``'s lengths (~7 live
pages each); slot 31 holds the riding prompt. ``xing4.0-29b-a4b-7l`` (PR
41): 64 slots over ONE latent pool of 1,307 pages of 256; 63 slots are
resident behind one of 16 documents of 48 pages each (shared pages, as the
prefix cache leaves them) and a page of their own, ``doc-qa-64``'s ~12.9k
live tokens a slot; slot 63's prompt rides behind its document's pages, so
a piece is a window over ~49 pages. ``kimi-linear-48b-a3b-12l-ep8`` (PR
43): 128 slots over a latent pool of 1,525 pages of 256 and the two state
pools of its 9 ``K`` layers (2.5 GB); 127 slots are resident at
``reason-docs-128``'s lengths (a question and part of a reply, every 16th
behind a document of ~8k tokens); slot 127's prompt rides behind
``--cached`` tokens of itself (0: its first piece starts from a zero
state; 4,096 and 12,288: the middle and the end of a document, a window
over 17 and 49 pages from the slot's own state).
``nemotron-3-nano-30b-a3b-14l-ep2`` (PR 44): 64 slots over K/V pools of
1,537 pages of 64 (its 2 attention layers) and the two state pools of its 6
``M`` layers (0.82 GB); 63 slots are resident at ``reason-batch-128``'s
lengths (a prompt and half a reply); slot 63's prompt rides behind
``--cached`` tokens of itself (0: from a zero state; 384: its fourth piece,
from the slot's own conv tail and state). Cases: C = 0 (the program
without ``ride``: the parent's), and C = 64 / 128 / 256 rows with

- ``0 live, branch``: no step carries a piece; each step BRANCHES to the
  plain step (what ``chat`` runs; a step that computed its C dead rows
  instead was measured once, PERF.md 6, PR 36, and is no longer a form
  of the program),
- ``C live``: every step carries a full piece of C rows, each at the next
  page-aligned start of a 1,024-token prompt.

Times are the host clock around ``block_until_ready`` over chained
dispatches (the fastest of ``BATCHES`` batches of ``REPS``); the operations
come from one traced batch, reduced by the benchmark's own reader. Fails
(exit 2) without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, trace_reduce

PKG = "distributed_llm_training_and_inference_system_tpu"
CONFIGS = {"dense": "mistral-7b-16l", "moe": "olmoe-1b-7b-10l",
           "latent": "xing4.0-29b-a4b-7l",
           "linear": "kimi-linear-48b-a3b-12l-ep8",
           "hybrid": "nemotron-3-nano-30b-a3b-14l-ep2"}
K = 8
REPS, BATCHES = 6, 4
DOC_PAGES, DOCS = 48, 16    # the latent cell's resident documents


class Shape:
    """A cell's slots and pages; the LAST slot's prompt rides."""

    def __init__(self, model: str, cached: int = 0):
        self.latent, self.linear = model == "latent", model == "linear"
        self.hybrid = model == "hybrid"
        self.B, self.MAXP, self.PS, self.NP = (
            (64, 68, 256, 1307) if self.latent else
            (128, 64, 256, 1525) if self.linear else
            (64, 32, 64, 1537) if self.hybrid else (32, 32, 64, 715))
        self.rider = self.B - 1
        # a recurrent model's rider starts behind so much of its own prompt
        self.recurrent = self.linear or self.hybrid
        # where the rider's first piece starts: behind its cached document
        # (a recurrent model's: behind so much of its own prompt)
        self.cached = DOC_PAGES * self.PS if self.latent else cached


def model_config(name: str):
    from importlib import import_module
    schema = import_module(f"{PKG}.config.schema")
    with open(os.path.join("benchmark", "configs", f"{name}.json")) as f:
        config = json.load(f)
    if "linear_attn_config" in config:
        from benchmark.runners import linear
        return schema.ModelConfig.from_dict(linear.model_dict(config))
    if "kv_lora_rank" in config:      # (YaRN lives in a nested group)
        from benchmark.runners import latent
        return schema.ModelConfig.from_dict(latent.model_dict(config))
    return schema.ModelConfig.from_dict(harness.model_dict(config))


def slots(sh: Shape, rng) -> dict:
    """The resident slots mid-generation and the rider's pages."""
    tables = np.zeros((sh.B, sh.MAXP), np.int32)
    free = iter(rng.permutation(np.arange(1, sh.NP)))
    positions = np.zeros(sh.B, np.int32)

    def take(n):
        return [next(free) for _ in range(n)]
    if sh.latent:
        docs = [take(DOC_PAGES) for _ in range(DOCS)]
        for slot in range(sh.B):
            # a document's shared pages, then the slot's own
            tables[slot, :DOC_PAGES + 4] = docs[slot % DOCS] + take(4)
            positions[slot] = DOC_PAGES * sh.PS + 200 + 9 * slot
    elif sh.linear:
        for slot in range(sh.B - 1):
            # a question and half a reply; every 16th behind a document
            at = 300 + 7 * slot + (8192 if slot % 16 == 0 else 0)
            n = at // sh.PS + 1
            tables[slot, :n] = take(n)
            positions[slot] = at
        n = sh.cached // sh.PS + 4
        tables[sh.rider, :n] = take(n)
    elif sh.hybrid:
        for slot in range(sh.B - 1):
            at = 150 + 9 * slot         # a prompt and part of a reply
            n = at // sh.PS + 1
            tables[slot, :n] = take(n)
            positions[slot] = at
        tables[sh.rider, :24] = take(24)
    else:
        for slot in range(sh.B - 1):
            n = 6 + (slot % 4 == 0)         # ~232 live pages, as batch-64
            tables[slot, :n + 1] = take(n + 1)
            positions[slot] = n * sh.PS - 17
        tables[sh.rider, :16] = take(16)
    stops = positions + 10_000
    stops[sh.rider] = 0                      # not resident: it rides
    # a token a slot (with the random pools below, the slots then route to
    # different experts, as a batch of different requests does)
    return {"tables": tables, "positions": positions, "stops": stops,
            "tokens": rng.integers(1, 30_000, sh.B).astype(np.int32)}


def pieces(sh: Shape, C: int, live: int, rng, vocab: int) -> np.ndarray:
    from importlib import import_module
    meta = import_module(f"{PKG}.serve.decode").PIECE_META
    rows = np.zeros((K, meta + C), np.int32)
    if live:
        for k in range(K):
            # consecutive page-aligned pieces of one 1,024-token prompt
            # (tail, behind the latent cell's cached document)
            rows[k, :meta] = (sh.rider, sh.cached + (k * C) % 1024, live, 0)
            rows[k, meta:meta + live] = rng.integers(1, vocab, live)
    return rows


def program(cfg, C: int):
    from importlib import import_module
    decode = import_module(f"{PKG}.serve.decode")

    def step(params, kp, vp, pools, tokens, positions, tables, stops, keys,
             temp, top_k, top_p, ride=None):
        out = decode.decode_scan(
            params, tokens, positions, kp, vp, tables, stops, keys, temp,
            top_k, top_p, cfg, K, return_moe_stats=True, ssm_state=pools,
            ride=ride)
        return out.tokens, out.k_pages, out.v_pages, out.state
    return jax.jit(step, donate_argnums=(1, 2, 3))


def state_pools(cfg, sh: Shape):
    """A recurrent model's conv windows (bfloat16) and states (float32,
    small: a decayed state's size), as serve/kv_cache.py lays them out."""
    if not sh.recurrent:
        return None
    if sh.linear:
        kd, L = cfg.kda, cfg.kda_layers
        conv = (L, kd.conv_kernel - 1, sh.B, kd.conv_channels)
        state = (L, sh.B, kd.num_heads, kd.head_dim, kd.head_dim)
    else:
        s, L = cfg.ssm, cfg.ssm_layers
        conv = (L, sh.B, s.conv_kernel - 1, s.conv_channels)
        state = (L, sh.B, s.num_heads, s.head_dim, s.state_size)
    return {"conv": jax.random.normal(jax.random.PRNGKey(2), conv,
                                      jnp.bfloat16),
            "ssm": 0.05 * jax.random.normal(jax.random.PRNGKey(3), state)}


def scopes_of(text: str, linear_model: bool):
    """operation name -> named scope (or None): a kernel by its own name,
    an XLA operation by its instruction's ``op_name`` in the compiled
    program's ``text``; the benchmark's own reader, under the model's
    runner's scopes."""
    from benchmark.runners import hybrid, linear
    plain = hybrid.SCOPES
    names = linear.SCOPES if linear_model else plain

    def under(call, *a):
        hybrid.SCOPES = names
        try:
            return call(*a)
        finally:
            hybrid.SCOPES = plain
    table = under(hybrid.scopes_of_instructions, text)
    return lambda op: under(hybrid.scope_of, [op]) or table.get(op)


def run_case(fn, params, pools, state, ride, trace: bool,
             linear_model: bool = False):
    """(ms a step, {operation: (calls, us a call)} of the traced batch, ms
    a step by named scope, the pools); ``pools``: (k pages, v pages, a
    recurrent model's state pools). ``fn``: {"jit": the jitted step}, which
    keeps its executable and its text here."""
    B = len(state["tokens"])
    args = [jnp.asarray(state["tokens"]), jnp.asarray(state["positions"]),
            jnp.asarray(state["tables"]), jnp.asarray(state["stops"]),
            jnp.zeros((B, 2), jnp.uint32), jnp.zeros(B, jnp.float32),
            jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32)]
    if ride is not None:
        args.append(jnp.asarray(ride))
    if "exe" not in fn:
        fn["exe"] = fn["jit"].lower(params, *pools, *args).compile()
        fn["scope_of"] = scopes_of(fn["exe"].as_text(), linear_model)
    exe = fn["exe"]

    def batch(pools):
        for _ in range(REPS):
            toks, *pools = exe(params, *pools, *args)
        jax.block_until_ready(toks)
        return pools
    pools = batch(pools)                    # compiles
    best = float("inf")
    for _ in range(BATCHES):
        t0 = time.perf_counter()
        pools = batch(pools)
        best = min(best, time.perf_counter() - t0)
    ops, by_scope = {}, {}
    if trace:
        with harness.scratch_dir("ride_trace_") as tmp:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=opts)
            pools = batch(pools)
            jax.profiler.stop_trace()
            planes = trace_reduce.load(jax.profiler.ProfileData.from_file(
                trace_reduce.find_xplane(tmp)))
        seen: dict = defaultdict(lambda: [0, 0.0])
        for plane in list(planes.values())[:1]:
            for name, s, e in trace_reduce.leaves(plane["ops"]):
                seen[name][0] += 1
                seen[name][1] += e - s
        top = sorted(seen.items(), key=lambda kv: -kv[1][1])[:12]
        ops = {name: [n, round(s / n * 1e6, 1), round(s / (REPS * K) * 1e3, 3)]
               for name, (n, s) in top}
        scopes: dict = defaultdict(float)
        for name, (_, s) in seen.items():
            scopes[fn["scope_of"](name.split(":", 1)[0])
                   or "no named scope"] += s
        by_scope = {k: round(v / (REPS * K) * 1e3, 3) for k, v in sorted(
            scopes.items(), key=lambda kv: -kv[1])}
    return best / (REPS * K) * 1e3, ops, by_scope, pools


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", nargs="+", default=list(CONFIGS),
                    choices=list(CONFIGS))
    ap.add_argument("--rows", nargs="+", type=int, default=[64, 128, 256])
    ap.add_argument("--live", nargs="+", type=int, default=[],
                    help="also pieces of so many live rows (<= C)")
    ap.add_argument("--cached", nargs="+", type=int, default=[0],
                    help="linear, hybrid: the rider's tokens before its "
                    "pieces, a case each (whole pages)")
    ap.add_argument("--out", default="chiprun_out/ride_step_alone.json")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print("ride_step_alone: no TPU; a step time comes from the chip",
              file=sys.stderr)
        return 2
    from importlib import import_module
    gpt = import_module(f"{PKG}.models.gpt")
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind},
              "ms_a_step": {}, "ops": {}, "scope_ms_a_step": {}}
    for model in args.models:
        cfg, sh = model_config(CONFIGS[model]), Shape(model)
        params = jax.jit(lambda key: gpt.init(cfg, key, jnp.bfloat16))(
            jax.random.PRNGKey(0))
        if sh.latent or sh.linear:    # ONE pool of padded latent rows
            pools = (jax.random.normal(
                jax.random.PRNGKey(1),
                (cfg.layers_of("*"), sh.NP, 1, sh.PS, cfg.mla.page_width),
                jnp.bfloat16), None)
        else:
            shape = (cfg.kv_layers, sh.NP, cfg.num_kv_heads, sh.PS,
                     cfg.head_dim)
            pools = tuple(jax.random.normal(key, shape, jnp.bfloat16)
                          for key in jax.random.split(jax.random.PRNGKey(1)))
        pools = (*pools, state_pools(cfg, sh))
        rng = np.random.default_rng(0)
        cases = [("C=0", 0, 0, 0)]
        for C in args.rows:
            cases += [(f"C={C}, 0 live, branch", C, 0, 0),
                      *[(f"C={C}, {n} live" + (
                          f" behind {cached}" if sh.recurrent else ""),
                         C, n, cached)
                        for n in [*args.live, C] if n <= C
                        for cached in (args.cached if sh.recurrent
                                       else [0])]]
        programs = {}
        for label, C, live, cached in cases:
            sh = Shape(model, cached)
            state = slots(sh, np.random.default_rng(0))
            fn = programs.setdefault(C, {"jit": program(cfg, C)})
            ride = pieces(sh, C, live, rng, cfg.vocab_size) if C else None
            ms, ops, by_scope, pools = run_case(
                fn, params, pools, state, ride, trace=True,
                linear_model=sh.linear)
            key = f"{model} | {label}"
            result["ms_a_step"][key] = round(ms, 3)
            result["ops"][key] = ops
            result["scope_ms_a_step"][key] = by_scope
            print(f"{key:40s} {ms:7.3f} ms a step", flush=True)
            for name, (n, us, ms_step) in ops.items():
                print(f"    {name:48s} {n:5d} calls {us:8.1f} us a call "
                      f"{ms_step:7.3f} ms a step", flush=True)
            print("    by scope (ms a step):", json.dumps(by_scope),
                  flush=True)
        del params, pools
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result["ms_a_step"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
