"""The runner of a latent-attention serving cell over resident documents
(traffic ``kind`` ``latent-closed``): the serving runner as it is
(``runners/serve.py``: the same server, hooks, load generator protocol and
window), with

- the traffic drawn by DOCUMENT (``benchmark/loadgen_docqa.py``, the load
  generator's child with this mix's requests) and the documents loaded in
  set-up: each is sent once with one question and ``max_tokens`` 1, so that
  its whole pages are in the prefix cache before the window opens; then one
  request a suffix-prefill bucket the window can reach, long enough to run
  the decode program;
- the correctness check held against the plain latent reference
  (``reference/latent_decoder.py``) on tokens the WINDOW served: requests
  that began and ended inside it, from different slots and different
  documents, document, question and the first served tokens teacher-forced
  through the reference after the window closes (the latent pool is given
  back first: a float32 pass over 13k tokens does not fit beside it), on
  weights whose norms' scales, selection bias and hyper-connection biases
  are seeded NON-trivially;
- the run traced by kernel and scope name as ``runners/hybrid.py`` does
  (``run["trace"]["scope_s"]``), with this model's scopes.

``run.py`` picks a runner by the traffic kind's first word. ``run["kind"]``
stays ``"serve"``. On a program without latent attention it leaves with one
line and exit 1 before JAX starts.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types
from importlib import import_module

import numpy as np

from benchmark import facts, harness, loadgen_docqa
from benchmark.runners import hybrid, serve

# The form of runners/serve.py's check: a served token's reference logit may
# lie CHECK_TOLERANCE_STD reference-logit standard deviations under the
# reference's largest (the dense check's own number). What is held are
# tokens the WINDOW served: CHECK_REQUESTS requests that began and ended
# inside it, each from another slot and another document, the first
# CHECK_NEW_TOKENS of each teacher-forced through the reference over the
# whole document after the window closes (768 tokens; what a pass costs is
# the context, not the tokens held).
# All 64 experts are here, so a routing near-tie swaps one present expert
# for a near-equal one; the token still moves by the difference of two
# experts, and where bfloat16's rounding of the stream flips a 4th / 5th
# expert (the least margin over a sample is ~1e-5) a token lies 0.7-1.7 std
# down with the RIGHT model: the worst token has no limit, as in the hybrid
# cell. The check is made aware of such ties in the hybrid's two ways, both
# read off the chip (my chip runs, PR 33; PERF.md 6 has the table):
# - a token is LEFT OUT where the reference's 4th and 5th biased scores, in
#   any expert layer at the token's position, are closer than
#   ROUTER_TIE_MARGIN (under CHECK_MIN_KEPT of the sample kept is itself not
#   correct);
# - a swap at an EARLIER position of the request reaches a kept token
#   through the request's own latent rows: CHECK_MAY_MISS of the kept tokens
#   may lie further down than the tolerance.
# Kept tokens further down than 0.25 std, % (calls R1 | R2, seeds
# 3300000301 | 302, 256 tokens each, at the FIRST margin of 0.0005;
# unfiltered in brackets): the RIGHT model 1.4 | 1.8 (3.9 | 3.1); every
# matmul operand rounded to float8, the nearest precision under bfloat16,
# 54.5; H_res a row softmax without Sinkhorn 15.5 | 10.8; plain
# interpolation for YaRN 37.6 | 42.9; c_kv unnormed 39.8; softmax router
# scores 38.2; the scale without m^2 41.3; one stream in place of four 43.5;
# a cache hit served from another document's pages 54.5; rope on the wrong
# 64 values 59.6. NOT separated, said plainly: float8 LATENT PAGES alone read
# 3.0 | 3.4 % (a row's rounding is averaged away over 13k keys that random
# queries weigh almost evenly); held on LOGITS instead (tests/test_latent.py,
# where every departure moves them by > 20 x 2e-5), and quantised latent
# pages are refused by name.
# At that margin and 6 requests the right model read 1.4-4.2 % over ten runs
# and then 6.2 % (call C1, seed 3300001001: 20 of 323) against the least
# wrong reading of 10.8: too near. A served score moves by ~1e-3 under
# bfloat16, so a margin of 0.0005 leaves flips in; and the reference's own
# two forms (operation by operation | compiled) differ by up to 4e-4 in a
# margin on the chip (call C2). Every token's gap and margin of R2, read
# again at wider margins (kept share; right | no Sinkhorn | float8 | YaRN
# interpolation): 0.001: 69 %; 1.1 | 9.7 | 55.9 | 43.6; 0.002: 50 %; 0.0 |
# 6.7 | 52.9 | 42.8. So: margin 0.002 (about half the sample is kept),
# twice the requests (12: what a pass costs is now ~2 s), and
# PERF.md 6 has the final runs' readings at these numbers.
CHECK_REQUESTS, CHECK_NEW_TOKENS = 12, 64
CHECK_TOLERANCE_STD = 0.25
ROUTER_TIE_MARGIN = 0.002
CHECK_MIN_KEPT = 0.25
CHECK_MAY_MISS = 0.03
# Every request goes through the reference at ONE length, the
# configuration's ``max_seq_len`` (zeros follow it, which nothing before them
# sees and which choose no expert), through three jitted programs (a kind of
# sub-layer each): the check's time is then the same for every seed. The
# first form rounded to 1,024 and ran the reference operation by operation:
# ~19 s a request warm, and every length a seed had not met before compiled
# each operation anew (~17 min from a cold cache), which the driver's limit of
# 360 s a run cut (PERF.md 6, PR 33).
CHECK_ROUND_TO = 17_408

# longest first: a window's kernels carry the decode kernel's name as a
# prefix, as do a prefill's grouped matmuls
SCOPES = ("mla_paged_attention_mq", "mla_paged_attention", "mla_page_write",
          "mla_kv_compress", "mla_q_proj", "mla_absorb", "hc_maps", "hc_mix",
          "moe_gmm_prefill", "moe_gmm", "moe_shared_expert", "moe_router",
          "moe_dispatch", "moe_combine")


_plain_model_dict = harness.model_dict


def model_dict(config: dict) -> dict:
    """``harness.model_dict`` with the ``rope_scaling`` group kept (it
    drops every nested group, and YaRN lives in one)."""
    return dict(_plain_model_dict(config),
                rope_scaling=config["rope_scaling"])


def seeded_latent_params(params: dict, seed: int) -> dict:
    """The parameter tree with what a seeded init leaves trivial made
    visible. ``gpt.init`` gives every norm's scale 0 (a plain RMS norm: a
    server that left the latent's norm's weight out would pass), the
    router's selection bias 0, and the hyper-connections' b_pre / b_post 0
    and b_res the identity matrix. Seeded here: the q-latent's, kv-latent's
    and the maps' norms' scales (the program's ``1 + scale``) in
    U(-0.5, 0.5), the selection bias in U(-0.01, 0.01) (PR 31's reading: it
    changes WHICH experts are chosen between close scores and adds little
    skew), b_pre and b_post in U(-1, 1), b_res in U(-1, 1) + the identity:
    H_res then differs visibly from I and from its own row softmax, and
    H_post from a constant. Every expert keeps ``gpt.init``'s scale."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 33)
    n = [0]

    def uniform(like, lo, hi):
        n[0] += 1
        return jax.random.uniform(jax.random.fold_in(key, n[0]), like.shape,
                                  jnp.float32, lo, hi).astype(like.dtype)
    blocks = {}
    for name, stack in params["blocks"].items():
        stack = dict(stack)
        hc = dict(stack["hc"])
        hc["norm"] = {"scale": uniform(hc["norm"]["scale"], -0.5, 0.5)}
        hc["b_pre"] = uniform(hc["b_pre"], -1.0, 1.0)
        hc["b_post"] = uniform(hc["b_post"], -1.0, 1.0)
        hc["b_res"] = hc["b_res"] + uniform(hc["b_res"], -1.0, 1.0)
        stack["hc"] = hc
        for norm in ("q_a_norm", "kv_norm"):
            if norm in stack:
                stack[norm] = {"scale": uniform(stack[norm]["scale"],
                                                -0.5, 0.5)}
        if "router" in stack:
            stack["router"] = dict(stack["router"], bias=uniform(
                stack["router"]["bias"], -0.01, 0.01))
        blocks[name] = stack
    return dict(params, blocks=blocks)


class Served(hybrid.Served):
    """``hybrid.Served`` (its hooks, its ``Trace``) on this model's seeded
    non-trivial weights, the documents loaded
    in set-up, the check held against the latent reference on what the
    window served, the run traced by scope."""

    def __init__(self, config: dict, seed: int, traffic: dict):
        # (``hybrid.Served`` puts the hooks on the engine that keep each
        # ended request's slot, prompt and tokens in ``self.served``)
        harness.model_dict = model_dict
        try:
            super().__init__(config, seed)
        finally:
            harness.model_dict = _plain_model_dict
        self.params = seeded_latent_params(self.params, seed)
        self.server.engine.params = self.params
        self.traffic = traffic
        self.documents = loadgen_docqa.documents(
            traffic, self.model_cfg.vocab_size)
        self._doc_of_head = {tuple(d[:64]): i
                             for i, d in enumerate(self.documents)}

    def document_of(self, prompt: list) -> int | None:
        """Which of the mix's documents ``prompt`` begins with."""
        return self._doc_of_head.get(tuple(prompt[:64]))

    # -- set-up --------------------------------------------------------------

    def warm(self, traffic: dict, seed: int) -> None:
        """Load the documents (each once, with one question, one token),
        then one request a suffix-prefill bucket the window's tails can
        reach, 16 tokens long: the chunk program, the suffix programs and
        the decode program have then compiled, and every document's whole
        pages are in the prefix cache."""
        rng = np.random.default_rng([seed, 2])
        vocab = self.model_cfg.vocab_size
        engine, spec = self.server.engine, self.traffic["question_tokens"]

        def question(n):
            return rng.integers(258, vocab, n).tolist()
        for doc in self.documents:
            serve._post(self.url, {"prompt": doc + question(spec["min"]),
                                   "temperature": 0.0, "max_tokens": 1})
        harness.mark(f"{len(self.documents)} documents in the prefix cache",
                     self._t0)
        ps = engine.kv.page_size
        done = set()
        for doc in self.documents:
            for q in range(spec["min"], spec["max"] + 1):
                bucket = engine._suffix_bucket(len(doc) % ps + q)
                if bucket not in done:
                    done.add(bucket)
                    serve._post(self.url, {
                        "prompt": doc + question(q), "temperature": 0.0,
                        "max_tokens": 16})

    # -- the check -----------------------------------------------------------

    def window_sample(self, raw: dict) -> list:
        """[(slot, prompt, served)] of CHECK_REQUESTS requests that began
        and ended inside the window, in the order they ended, each from
        another slot AND another document."""
        ended = [self.served[r["id"]] for r in sorted(
            (r for r in facts.window_requests(raw)
             if not facts.failed(r) and r["id"] in self.served),
            key=lambda r: r["done"])]
        sample, slots, docs = [], set(), set()
        for slot, prompt, served in ended:
            doc = self.document_of(prompt)
            if slot in slots or doc in docs or len(served) < 2:
                continue
            slots.add(slot)
            docs.add(doc)
            sample.append((slot, prompt, served))
            if len(sample) == CHECK_REQUESTS:
                break
        return sample

    def release_pool(self) -> None:
        """Stop the engine thread and give the latent pool's memory back
        before the reference runs: nothing is served after the window. (The
        thread first: a closed loop's callers leave requests in flight, and
        a dispatch over a deleted pool makes the engine allocate a new
        one.)"""
        self.server.stop_engine()
        self.server.engine.kv.k_pages.delete()

    def check_served(self, sample: list, wrong: str | None = None,
                     other_document: bool = False, detail: bool = False
                     ) -> dict:
        """Hold served tokens to the plain reference: each request's prompt
        (document and question) and its first CHECK_NEW_TOKENS served
        tokens teacher-forced through ``latent_decoder.logits``, every
        served token's reference logit held to the reference's largest:
        tokens at a routing near-tie are left out (``ROUTER_TIE_MARGIN``),
        and ``CHECK_MAY_MISS`` of the rest may lie further down than the
        tolerance.
        ``wrong`` gives the reference a fault; ``other_document`` gives it
        the NEXT document's tokens under the same question (what a cache hit
        on another document's pages would serve): how one shows that the
        check fails when it should."""
        from benchmark.reference import latent_decoder
        gaps, margins, std_sum = [], [], 0.0
        for _, prompt, served in sample:
            served = served[:CHECK_NEW_TOKENS]
            n = len(served)
            if other_document:
                i = self.document_of(prompt)
                this, other = self.documents[i], self.documents[
                    (i + 1) % len(self.documents)]
                # the same length, so that positions stay what they were
                other = (other * 2)[:len(this)]
                prompt = other + prompt[len(this):]
            lg, margin = latent_decoder.logits(
                self.params, prompt + served[:-1], self.config,
                positions=range(len(prompt) - 1, len(prompt) - 1 + n),
                wrong=wrong, with_margin=True, round_to=CHECK_ROUND_TO,
                compiled=True)
            lg = np.asarray(lg)
            gaps.extend((lg.max(-1) - lg[np.arange(n), served]).tolist())
            margins.extend(np.asarray(margin).tolist())
            std_sum += float(lg.std())
        if not gaps:
            return {"ok": False, "requests": 0, "tokens": 0}
        std = std_sum / len(sample)
        tol = CHECK_TOLERANCE_STD * std
        kept = [g for g, m in zip(gaps, margins) if m >= ROUTER_TIE_MARGIN]
        missed = sum(g > tol for g in kept)
        out = {"ok": bool(len(sample) == CHECK_REQUESTS
                          and len(kept) >= CHECK_MIN_KEPT * len(gaps)
                          and missed <= CHECK_MAY_MISS * len(kept)),
               "tokens_under_tol": missed, "may_miss": CHECK_MAY_MISS,
               "tokens_kept": len(kept), "tokens": len(gaps),
               "all_tokens_under_tol": sum(g > tol for g in gaps),
               "worst_gap_std": max(kept, default=0.0) / std,
               "mean_gap_std": float(np.mean(gaps)) / std,
               "tol": tol, "logit_std": std, "requests": len(sample),
               "slots": len({s[0] for s in sample}),
               "tokens_off_the_reference_argmax": sum(g > 0 for g in kept),
               "least_routing_margin": min(margins)}
        if detail:
            out.update(gaps=gaps, margins=margins)
        return out

    # -- the window ----------------------------------------------------------

    def drive(self, *args, **kwargs) -> dict:
        """``serve.Served.drive`` with the load generator's child started
        as ``benchmark.loadgen_docqa`` (this mix's requests) and this
        runner's ``Trace`` (seconds by operation), the two seams that need
        no edit to a file the benchmark has."""
        def popen(cmd, **kw):
            cmd = ["benchmark.loadgen_docqa" if c == "benchmark.loadgen"
                   else c for c in cmd]
            return subprocess.Popen(cmd, **kw)
        plain = serve.subprocess
        serve.subprocess = types.SimpleNamespace(
            Popen=popen, PIPE=subprocess.PIPE)
        try:
            return super().drive(*args, **kwargs)
        finally:
            serve.subprocess = plain


def scope_seconds(op_s: dict, texts: dict) -> dict:
    """``hybrid.scope_seconds`` with this model's scopes."""
    plain = hybrid.SCOPES
    hybrid.SCOPES = SCOPES
    try:
        return hybrid.scope_seconds(op_s, texts)
    finally:
        hybrid.SCOPES = plain


def require_latent_support(config: dict) -> None:
    """Leave at once, with one line, where the program under test cannot
    build this configuration: a commit from before the latent-attention keys
    were read loads it as a uniform stack of GQA layers with a 1024-wide
    feed-forward, and would be measured as something it is not."""
    schema = import_module(f"{harness.PKG}.config.schema")
    if not hasattr(schema, "MLAConfig"):
        raise SystemExit(
            f"benchmark/runners/latent.py: this program has no latent "
            f"attention: it cannot run {config['name']}")
    try:
        model = schema.ModelConfig.from_dict(model_dict(config))
    except Exception as e:
        raise SystemExit(f"benchmark/runners/latent.py: this program cannot "
                         f"read {config['name']}: {e}")
    built = (model.mla.kv_lora_rank, model.hc_mult, model.layer_pattern,
             model.rope.scaling, model.moe.num_experts)
    wanted = (config["kv_lora_rank"], config["hc_mult"],
              "*D" * config["first_k_dense_replace"] + "*E" * (
                  config["num_hidden_layers"]
                  - config["first_k_dense_replace"]),
              config["rope_scaling"]["type"], config["n_routed_experts"])
    if built != wanted:
        raise SystemExit(
            f"benchmark/runners/latent.py: this program builds "
            f"{config['name']} with (kv rank, streams, layer table, rope "
            f"scaling, experts) = {built}, the configuration says {wanted}: "
            "it cannot run this cell")


def run(cell: dict, config: dict, traffic_path: str, seed: int,
        seconds: float, trace: bool, t_process_start: float,
        require_tpu: bool = True) -> dict:
    """One run of a latent-attention serving cell; ``runners/serve.py run``
    with this runner's set-up, child and check."""
    require_latent_support(config)
    device = harness.start(cell["chips"], require_tpu)
    harness.mark("imports and device", t_process_start)
    traffic = loadgen_docqa.load(traffic_path)
    served = Served(config, seed, traffic)
    served._t0 = t_process_start
    harness.mark(f"weights ({served.init_s:.1f}s) and server up",
                 t_process_start)
    try:
        with harness.scratch_dir("bench_latent_traffic_") as tmp:
            # ``facts`` and ``serve.drive`` know serve-open / serve-closed
            path = os.path.join(tmp, os.path.basename(traffic_path))
            with open(path, "w") as f:
                json.dump(dict(traffic, kind="serve-" + traffic[
                    "kind"].split("-", 1)[1]), f)
            raw = serve.measure(served, cell, path, seed, seconds, trace,
                                t_process_start, device)
        if raw["trace"].get("op_s"):
            # before the pool goes: the programs' texts are lowered from
            # the live arguments' shapes (read back from the compile cache)
            raw["trace"]["scope_s"] = scope_seconds(
                raw["trace"]["op_s"], served.server.engine.program_texts())
            harness.mark("scopes of the traced operations", t_process_start)
        sample = served.window_sample(raw)
        served.release_pool()
        check = served.check_served(sample, detail=True)
        # every sampled token's gap and margin, for reading the check at
        # other numbers than it was run with (stderr alone)
        print("[bench] check detail " + json.dumps({
            k: [round(x, 6) for x in check.pop(k)]
            for k in ("gaps", "margins") if k in check}), file=sys.stderr)
        raw["check"] = check
        print(f"[bench] reference check on the window's requests "
              f"{raw['check']}", file=sys.stderr)
        harness.mark("reference check on the window's requests",
                     t_process_start)
        return raw
    finally:
        served.close()
