"""The runner of a linear-attention serving cell (traffic ``kind``
``linear-closed``): the serving runner as it is (``runners/serve.py``: the
same server, hooks, load generator protocol and window), with

- the traffic drawn by ``benchmark/loadgen_linear.py`` (the load generator's
  child with this mix's requests: short questions, and a document in front
  of one in sixteen);
- every program the window can reach compiled in set-up: the cold prefill
  buckets of the questions, the chunk program and the final-chunk programs
  of the documents (state-carrying chunked prefill), the decode program;
- the correctness check held against the plain reference
  (``reference/linear_decoder.py``) on tokens the WINDOW served: requests
  whose reply ENDED inside it, from different slots, at least
  ``CHECK_DOCUMENTS`` of them document requests (so that a state carried
  from chunk to chunk is what is checked), prompt and the first served
  tokens teacher-forced through the reference after the window closes (the
  latent pool and the state pools are given back first), on weights whose
  norms' scales and selection bias are seeded NON-trivially; and held
  again against the reference's NEAR_MISSES, which must explain the same
  tokens worse;
- the run judged on the replies that ENDED inside the window
  (``ended_in_window``: the result line's ``attempted`` and ``failed``,
  ``tpot_p95_ms``), because under this mix no request is both sent and
  ended inside it;
- the run traced by kernel and scope name as ``runners/hybrid.py`` does
  (``run["trace"]["scope_s"]``), with this model's scopes, the chunk
  programs' instructions among them.

``run.py`` picks a runner by the traffic kind's first word. ``run["kind"]``
stays ``"serve"``. On a program without the ``K`` layer kind it leaves with
one line and exit 1 before JAX starts.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types
from importlib import import_module

import numpy as np

from benchmark import facts, harness, loadgen_linear
from benchmark.runners import hybrid, serve

# The form of the hybrid and latent cells' checks: a served token's reference
# logit may lie CHECK_TOLERANCE_STD reference-logit standard deviations under
# the reference's largest (its "gap"). What is held are tokens the WINDOW
# served: CHECK_REQUESTS requests whose reply ended inside it, each from
# another slot, CHECK_DOCUMENTS of them document requests (a prompt of
# 6k-13k tokens that went chunk by chunk, its K state carried), the first
# CHECK_NEW_TOKENS of each question request and CHECK_NEW_TOKENS_DOCUMENT of
# each document request teacher-forced through the reference (what a pass
# costs is the context: a document costs ~10 of the questions). They are
# drawn from the cell's judged requests, ``ended_in_window`` below.
#
# Two limits, each with its two readings in PERF.md 6 (PR 40):
#
# 1. CHECK_MAY_MISS of the tokens may lie further down than the tolerance.
#    32 of the router's 256 experts are held, 8 chosen a token. Unlike the
#    hybrid and the latent cell this check leaves NO token out for a routing
#    near-tie: 256 sigmoid scores lie so close that the reference's 8th and
#    9th are under 0.002 apart at 97 % of the positions (my chip run, PR 40,
#    call 3), and a swap there is most often between two ABSENT experts (7
#    in 8 are) or moves the token by one of eight small weights. This limit
#    catches what moves the logits by much: every matmul operand rounded to
#    float8 (the nearest precision under bfloat16), beta left out, one
#    decay a head, the chosen scores not renormalised.
# 2. The reference must explain the served tokens BETTER than each of its
#    NEAR_MISSES does: the same tokens through the reference with ONE fault
#    toggled (the K state rounded to bfloat16 after every token; rope on the
#    pe values) must read a mean gap larger than the reference's own by more
#    than CHECK_NEAR_MISS_STD. Those two move a logit by less than the
#    server's own bfloat16 matmuls do and no limit on the gaps alone
#    separates them from seed to seed; PAIRED on the same tokens they are
#    separated, because a served token is the argmax of the model that
#    served it. A server that HAD the fault reads the other sign. Nearly
#    all of rope's reading comes from the question requests (a document's
#    thousands of keys average it away), hence 14 of them at 512 tokens
#    (every reply has at least 512).
#
# The reference's routing margin is still reported
# (``least_routing_margin``), for reading by hand.
CHECK_REQUESTS, CHECK_DOCUMENTS = 16, 2
CHECK_NEW_TOKENS, CHECK_NEW_TOKENS_DOCUMENT = 512, 128
CHECK_TOLERANCE_STD = 0.25
CHECK_MAY_MISS = 0.05
NEAR_MISSES = ("bf16_state", "rotated_pe")
CHECK_NEAR_MISS_STD = 0.0
# Every request goes through the reference at ONE of two lengths (zeros
# follow it, which nothing before them sees and which choose no expert): a
# question's (up to 1,024 + 512 tokens) or a document's (up to 12,288 + 1,024
# + 128), each a compiled program a kind of sub-layer: the check's time is
# then the same for every seed (PR 33 was refused once for an operation-by-
# operation reference that ran past the driver's limit).
CHECK_PAD_SHORT, CHECK_PAD_LONG = 1536, 13440

# longest first: a window's kernels carry the decode kernel's name as a
# prefix, as do a prefill's grouped matmuls
SCOPES = ("kda_chunk_prefill", "kda_gated_norm", "kda_decode", "kda_conv",
          "mla_paged_attention_mq", "mla_paged_attention", "mla_page_write",
          "mla_kv_compress", "mla_q_proj", "mla_absorb",
          "moe_gmm_prefill", "moe_gmm", "moe_shared_expert", "moe_router",
          "moe_dispatch", "moe_combine")

_plain_model_dict = harness.model_dict
_plain_window_requests = facts.window_requests

# What ``run["judged"]`` says of a run of this runner.
ENDED_IN_WINDOW = "ended-in-window"


def ended_in_window(run: dict) -> list:
    """The requests a run of this cell is judged on (the result line's
    ``attempted`` and ``failed``, ``tpot_p95_ms``, the check's sample):
    those whose reply ENDED inside the window, whenever they were sent, and
    every request that failed. ``facts.window_requests`` asks of a closed
    loop's request that it was also SENT inside the window. Here 256
    callers stand before 128 slots, a reply takes ~35 s behind a wait as
    long, and no request is both sent and ended inside 51 s: that sample
    is empty (``attempted`` 0, which is no result). A reply that ended in
    the window was decoded in it; what it waited before its first token
    is not in any metric this cell reports. A failed request has no stamp
    of its end, so it counts from the warm-up's first second on. What is
    in flight at the close is dropped, as ``facts`` drops it."""
    w0, w1 = run["window"]
    return [r for r in run["stamps"]["records"] if not r.get("in_flight")
            and (facts.failed(r) or w0 <= r["done"] <= w1)]


def window_requests(run: dict) -> list:
    """``facts.window_requests`` for a run that says it is judged on
    ``ended_in_window``; any other run's as it was. ``run`` puts this in
    ``facts`` (``benchmark/run.py`` and the end-to-end readers ask
    ``facts`` which requests count): the seam that needs no edit to a file
    the benchmark has."""
    if run.get("judged") == ENDED_IN_WINDOW:
        return ended_in_window(run)
    return _plain_window_requests(run)


def model_dict(config: dict) -> dict:
    """``harness.model_dict`` with the ``linear_attn_config`` group kept (it
    drops every nested group, and the layer lists live in one)."""
    return dict(_plain_model_dict(config),
                linear_attn_config=config["linear_attn_config"])


def seeded_linear_params(params: dict, seed: int) -> dict:
    """The parameter tree with what a seeded init leaves trivial made
    visible. ``gpt.init`` gives every norm's scale 0 (a plain RMS norm: a
    server that left the K head norm's or the latent's norm's weight out
    would pass) and the router's selection bias 0. Seeded here: the K head
    norm's and the kv latent's norms' scales (the program's ``1 + scale``)
    in U(-0.5, 0.5), the selection bias in U(-0.01, 0.01) (PR 31's reading:
    it changes WHICH experts are chosen between close scores and adds
    little skew). ``A_log``, ``dt_bias`` and the convs come random from
    ``gpt.init`` itself; every expert keeps its scale."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 40)

    def uniform(i, like, lo, hi):
        return jax.random.uniform(jax.random.fold_in(key, i), like.shape,
                                  jnp.float32, lo, hi).astype(like.dtype)
    blocks = dict(params["blocks"])
    kda = dict(blocks["kda"])
    kda["gate_norm"] = {"scale": uniform(0, kda["gate_norm"]["scale"],
                                         -0.5, 0.5)}
    attn = dict(blocks["attn"])
    attn["kv_norm"] = {"scale": uniform(1, attn["kv_norm"]["scale"],
                                        -0.5, 0.5)}
    moe = dict(blocks["moe"])
    moe["router"] = dict(moe["router"], bias=uniform(
        2, moe["router"]["bias"], -0.01, 0.01))
    return dict(params, blocks=dict(blocks, kda=kda, attn=attn, moe=moe))


class Served(hybrid.Served):
    """``hybrid.Served`` (its hooks, its ``Trace``) on this model's seeded
    non-trivial weights, every reachable program compiled in set-up, the
    check held against the linear reference on what the window served, the
    run traced by scope."""

    def __init__(self, config: dict, seed: int, traffic: dict):
        # (``hybrid.Served`` puts the hooks on the engine that keep each
        # ended request's slot, prompt and tokens in ``self.served``)
        harness.model_dict = model_dict
        try:
            super().__init__(config, seed)
        finally:
            harness.model_dict = _plain_model_dict
        self.params = seeded_linear_params(self.params, seed)
        self.server.engine.params = self.params
        self.traffic = traffic
        self._gaps: dict = {}

    # -- set-up --------------------------------------------------------------

    def warm(self, traffic: dict, seed: int) -> None:
        """One request a cold prefill bucket the questions can reach, then
        one a final-chunk bucket a document can end in (a prompt of one
        whole chunk and that many tokens more: the chunk program, then the
        final chunk's), each long enough to run the decode program."""
        rng = np.random.default_rng([seed, 2])
        vocab = self.model_cfg.vocab_size
        engine, spec = self.server.engine, self.traffic["question_tokens"]
        C = engine._chunk_tokens
        lengths = self.prefill_buckets(spec["min"], min(spec["max"], C))
        ps, done = engine.kv.page_size, set()
        for tail in range(ps, C + 1, ps):
            bucket = engine._suffix_bucket(tail)
            if bucket not in done:
                done.add(bucket)
                lengths.append(C + tail)
        for n in lengths:
            serve._post(self.url, {
                "prompt": rng.integers(258, vocab, n).tolist(),
                "temperature": 0.0, "max_tokens": 16})

    # -- the check -----------------------------------------------------------

    def is_document(self, prompt: list) -> bool:
        return len(prompt) > self.traffic["question_tokens"]["max"]

    def window_sample(self, raw: dict) -> list:
        """[(slot, prompt, served)] of CHECK_REQUESTS requests whose reply
        ended inside the window, each from another slot: the first
        CHECK_DOCUMENTS document requests that ended, then plain requests
        in the order they ended."""
        ended = [self.served[r["id"]] for r in sorted(
            (r for r in ended_in_window(raw)
             if not facts.failed(r) and r["id"] in self.served),
            key=lambda r: r["done"])]
        ended = [s for s in ended if len(s[2]) >= 2]
        docs = [s for s in ended if self.is_document(s[1])]
        sample, slots = [], set()
        for s in docs[:CHECK_DOCUMENTS] + [
                s for s in ended if not self.is_document(s[1])]:
            if s[0] in slots:
                continue
            slots.add(s[0])
            sample.append(s)
            if len(sample) == CHECK_REQUESTS:
                break
        return sample

    def release_pools(self) -> None:
        """Stop the engine thread and give the latent pool's and the state
        pools' memory back before the reference runs: nothing is served
        after the window. (The thread first: a closed loop's callers leave
        requests in flight, and a dispatch over a deleted pool makes the
        engine allocate a new one.)"""
        self.server.stop_engine()
        kv = self.server.engine.kv
        kv.k_pages.delete()
        for pool in (kv.state or {}).values():
            pool.delete()

    def reference_gaps(self, sample: list, wrong: str | None) -> dict:
        """Each request's prompt and its first served tokens teacher-forced
        through ``linear_decoder.logits`` (with the faults of ``wrong``):
        every served token's gap (the reference's largest logit less the
        served token's), the routing margins, the mean logit standard
        deviation of a request. Kept a ``wrong`` (one sample a process)."""
        if wrong in self._gaps:
            return self._gaps[wrong]
        from benchmark.reference import linear_decoder
        gaps, margins, std_sum = [], [], 0.0
        for _, prompt, served in sample:
            document = self.is_document(prompt)
            served = served[:CHECK_NEW_TOKENS_DOCUMENT if document
                            else CHECK_NEW_TOKENS]
            n = len(served)
            lg, margin = linear_decoder.logits(
                self.params, prompt + served[:-1], self.config,
                positions=range(len(prompt) - 1, len(prompt) - 1 + n),
                wrong=wrong, with_margin=True, compiled=True,
                pad_to=CHECK_PAD_LONG if document else CHECK_PAD_SHORT)
            lg = np.asarray(lg)
            gaps.extend((lg.max(-1) - lg[np.arange(n), served]).tolist())
            margins.extend(np.asarray(margin).tolist())
            std_sum += float(lg.std())
        self._gaps[wrong] = {"gaps": gaps, "margins": margins,
                             "std": std_sum / max(len(sample), 1)}
        return self._gaps[wrong]

    def check_served(self, sample: list, wrong: str | None = None,
                     detail: bool = False) -> dict:
        """Hold served tokens to the plain reference by the two limits
        above. ``wrong`` gives the reference a fault (its near misses are
        then that reference with one of NEAR_MISSES toggled): how one shows
        that the check fails when it should."""
        if not sample:
            return {"ok": False, "requests": 0, "tokens": 0}
        ref = self.reference_gaps(sample, wrong)
        gaps, std = ref["gaps"], ref["std"]
        tol = CHECK_TOLERANCE_STD * std
        missed = sum(g > tol for g in gaps)
        mean = float(np.mean(gaps)) / std
        first = missed <= CHECK_MAY_MISS * len(gaps)
        further = {}
        # (a reference that fails the first limit needs no second reading)
        for fault in NEAR_MISSES if first else ():
            faults = set(wrong.split("+") if wrong else ()) ^ {fault}
            miss = self.reference_gaps(sample, "+".join(sorted(faults))
                                       or None)
            further[fault] = float(np.mean(miss["gaps"])) / std - mean
        documents = sum(self.is_document(s[1]) for s in sample)
        out = {"ok": bool(len(sample) == CHECK_REQUESTS
                          and documents >= CHECK_DOCUMENTS
                          and first and min(further.values())
                          > CHECK_NEAR_MISS_STD),
               "tokens_under_tol": missed, "may_miss": CHECK_MAY_MISS,
               "tokens": len(gaps),
               "worst_gap_std": max(gaps) / std, "mean_gap_std": mean,
               "near_miss_further_std": further,
               "tol": tol, "logit_std": std, "requests": len(sample),
               "documents": documents,
               "slots": len({s[0] for s in sample}),
               "tokens_off_the_reference_argmax": sum(g > 0 for g in gaps),
               "least_routing_margin": min(ref["margins"])}
        if detail:
            out.update(gaps=gaps, margins=ref["margins"])
        return out

    # -- the window ----------------------------------------------------------

    def drive(self, *args, **kwargs) -> dict:
        """``serve.Served.drive`` with the load generator's child started
        as ``benchmark.loadgen_linear`` (this mix's requests) and the
        hybrid runner's ``Trace`` (seconds by operation), the two seams
        that need no edit to a file the benchmark has."""
        def popen(cmd, **kw):
            cmd = ["benchmark.loadgen_linear" if c == "benchmark.loadgen"
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
    """``hybrid.scope_seconds`` with this model's scopes, and with the chunk
    and final-chunk programs' texts ("prefill chunk N", "suffix prefill N")
    handed on under their jitted functions' names, by which it finds the
    program a trace shows them as (``suffix_prefill``)."""
    jitted = {"prefill chunk": "extend_chunk", "suffix prefill":
              "extend_prefill"}
    renamed = {next((f"{fn} {name}" for head, fn in jitted.items()
                     if name.startswith(head)), name): text
               for name, text in texts.items()}
    plain = hybrid.SCOPES
    hybrid.SCOPES = SCOPES
    try:
        return hybrid.scope_seconds(op_s, renamed)
    finally:
        hybrid.SCOPES = plain


def require_linear_support(config: dict) -> None:
    """Leave at once, with one line, where the program under test cannot
    build this configuration: a commit from before the ``K`` layer kind
    cannot read ``q_lora_rank: null`` or ``linear_attn_config``, and would
    otherwise be measured as something it is not."""
    schema = import_module(f"{harness.PKG}.config.schema")
    if not hasattr(schema, "KDAConfig"):
        raise SystemExit(
            f"benchmark/runners/linear.py: this program has no delta-rule "
            f"linear-attention layer: it cannot run {config['name']}")
    try:
        model = schema.ModelConfig.from_dict(model_dict(config))
    except Exception as e:
        raise SystemExit(f"benchmark/runners/linear.py: this program cannot "
                         f"read {config['name']}: {e}")
    kinds = {i: "K" for i in config["linear_attn_config"]["kda_layers"]}
    dense = config["first_k_dense_replace"]
    wanted = ("".join(kinds.get(i + 1, "*") + ("D" if i < dense else "E")
                      for i in range(config["num_hidden_layers"])),
              config["linear_attn_config"]["num_heads"],
              config["kv_lora_rank"], 0, config["num_experts"],
              config["router_experts"], "none")
    built = (model.layer_pattern, model.kda.num_heads, model.mla.kv_lora_rank,
             model.mla.q_lora_rank, model.moe.num_experts,
             model.moe.router_experts, model.position_embedding)
    if built != wanted:
        raise SystemExit(
            f"benchmark/runners/linear.py: this program builds "
            f"{config['name']} with (layer table, K heads, kv rank, q rank, "
            f"experts held, router width, position embedding) = {built}, "
            f"the configuration says {wanted}: it cannot run this cell")


def run(cell: dict, config: dict, traffic_path: str, seed: int,
        seconds: float, trace: bool, t_process_start: float,
        require_tpu: bool = True) -> dict:
    """One run of a linear-attention serving cell; ``runners/serve.py run``
    with this runner's set-up, child and check."""
    require_linear_support(config)
    device = harness.start(cell["chips"], require_tpu)
    harness.mark("imports and device", t_process_start)
    traffic = loadgen_linear.load(traffic_path)
    served = Served(config, seed, traffic)
    harness.mark(f"weights ({served.init_s:.1f}s) and server up",
                 t_process_start)
    try:
        with harness.scratch_dir("bench_linear_traffic_") as tmp:
            # ``facts`` and ``serve.drive`` know serve-open / serve-closed
            path = os.path.join(tmp, os.path.basename(traffic_path))
            with open(path, "w") as f:
                json.dump(dict(traffic, kind="serve-" + traffic[
                    "kind"].split("-", 1)[1]), f)
            raw = serve.measure(served, cell, path, seed, seconds, trace,
                                t_process_start, device)
        raw["judged"] = ENDED_IN_WINDOW
        facts.window_requests = window_requests
        if raw["trace"].get("op_s"):
            # before the pools go: the programs' texts are lowered from the
            # live arguments' shapes (read back from the compile cache)
            raw["trace"]["scope_s"] = scope_seconds(
                raw["trace"]["op_s"],
                served.server.engine.program_texts(chunks=True))
            harness.mark("scopes of the traced operations", t_process_start)
        sample = served.window_sample(raw)
        served.release_pools()
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
