"""The runner of a multi-turn SESSIONS serving cell (traffic ``kind``
``sessions-closed``): the serving runner as it is (``runners/serve.py``: the
same server, hooks, load generator protocol and window), with

- the traffic driven by ``benchmark/loadgen_sessions.py`` (one session a
  caller; a turn is the session's text so far plus a new message, the
  reply's ids as the stream gave them);
- set-up that makes every session's FIRST history resident (sent once with
  ``max_tokens`` 1: its K/V pages, and the snapshot of the delta-rule state
  at its last page boundary) and compiles every program the window can
  reach: the chunk program, the final-chunk programs, the decode program
  with a piece riding, the two copies of the snapshot pool;
- the correctness check held against the plain reference
  (``reference/sessions_decoder.py``) on turns the WINDOW served:
  ``CHECK_REQUESTS`` turns that were sent and ended inside it, one a slot,
  every one of them armed from a SNAPSHOT (a prefix hit through the
  recurrent state), the longest among them, the WHOLE session teacher-forced
  through the reference from position 0 after the window closes and the
  served reply's tokens held at their positions; and on ``PROBES`` of those
  turns' OWN snapshots, as the window's takes left them in the pool: the
  entry's rows held to the reference's state at the cut, and the prompt cut
  one token behind it sent again to the same engine, which arms a slot from
  that entry and generates RIGHT BEHIND the hit (the pools are given back
  after the probes, before the reference runs);
- the run traced by kernel and scope name as ``runners/hybrid.py`` does
  (``run["trace"]["scope_s"]``), with this model's scopes and the two
  snapshot copies, which are programs of their own.

``run.py`` picks a runner by the traffic kind's first word. ``run["kind"]``
stays ``"serve"``. On a program that cannot read ``solar_open2`` or keeps no
snapshots it leaves with one line and exit 1 before JAX starts.
"""

from __future__ import annotations

import concurrent.futures
import json
import os
import subprocess
import sys
import time
import types
from collections import defaultdict
from importlib import import_module

import numpy as np

from benchmark import facts, harness, loadgen_sessions
from benchmark.runners import hybrid, serve

# The form of the linear cell's first limit (``runners/linear.py``): a served
# token's reference logit may lie CHECK_TOLERANCE_STD reference-logit
# standard deviations under the reference's largest (its "gap"), and
# CHECK_MAY_MISS of the tokens may lie further down. The reference computes
# every session from position 0 with no snapshot. Three limits, each with
# its two readings in PERF.md 6 (PR 46):
#
# 1. The WINDOW's turns (CHECK_REQUESTS snapshot hits, one a slot, the
#    whole reply of each): catches what moves the logits by much wherever
#    it sits (beta without its factor 2, no gate, rope, scores not
#    renormalised, float8 operands). 40 of the router's 320 experts are
#    held, 8 chosen a token; as in the linear cell no token is left out for
#    a routing near-tie (the least margin is reported). It does NOT see a
#    slot armed from a wrong state: a reply starts 300-1,300 tokens behind
#    its hit, and a delta rule over 128-wide keys has overwritten what it
#    held by then (each token erases ~beta / 128 of it: my chip run, PR 46,
#    call 2: a state set to ZERO at the hit moved 341 of 2,278 gaps, by
#    0.0003 std in the mean).
# 2. The PROBES' tokens: PROBES of the sampled turns' prompts, cut one
#    token behind their own cut ``b`` and sent again after the window: the
#    engine arms a slot from the entry the window's take left under ``b``'s
#    page hash, prefills ONE token and generates PROBE_TOKENS right behind
#    the hit, where the armed state is nearly all a layer's output is made
#    of. A slot armed from nothing, or from the snapshot of the page
#    before, leaves the reference's argmax at once.
# 3. The PROBES' snapshot entries themselves, read out of the pool: the
#    relative error (Frobenius, the worst layer of the worst probe) of the
#    entry's state against the reference's state before token ``b`` may be
#    STATE_TOLERANCE, and both pools must hold float32. The server computes
#    the chunked form with bfloat16 operands and its entries lie 5.0-11.8 %
#    off the float32 reference's (my chip runs, PR 46, calls 3-6, 68
#    probes); a wrong beta
#    or an ungated softmax layer under it 114-125 %. What NO limit on
#    tokens or states separates, said plainly: a state rounded to bfloat16
#    after every token lies 5.7-6.9 % off (the server's own bfloat16
#    operands are the larger error) and moves the window's gaps by 0.0002
#    std in the mean (paired on 2,278 tokens: t = 1.0; calls 1-3), so the
#    pools' dtype is held by NAME.
#
# Readings (calls 2-3; RIGHT | wrong): limit 1, tokens past the tolerance,
# 0.04-0.2 % | beta without its factor 52.6-56.4 %, float8 operands 76.9 %,
# no gate, rope, no renormalisation 98.5-99.7 %; limit 2, 0.8 % (1 of 128)
# | armed from nothing 94.5 %, from the page before 97.7 %, beta 56 %, no
# gate 98 %; limit 3, at most 11.8 % | 114 %, 125 %.
CHECK_REQUESTS = 12
CHECK_TOLERANCE_STD = 0.25
CHECK_MAY_MISS = 0.05
PROBES, PROBE_TOKENS = 4, 32
CHECK_POSITIONS = 512       # the longest reply the traffic draws
PROBE_MAY_MISS = 0.10
STATE_TOLERANCE = 0.25
# every session goes through the reference at ONE padded length, the
# configuration's ``max_seq_len`` (zeros follow it, which nothing before
# them sees and which choose no expert): a compiled program a kind of
# sub-layer, the same for every seed (PR 33 was refused once for a
# reference that compiled a program a length). The delta rule's loop and
# the attention's query blocks run over the session's own tokens alone.

# longest first: a window's kernels carry the decode kernel's name as a prefix
SCOPES = ("kda_snapshot_take", "kda_snapshot_arm", "kda_chunk_prefill",
          "kda_gated_norm", "kda_decode", "kda_conv", "paged_attention_mq",
          "paged_attention", "kv_page_write", "attn_gate",
          "moe_gmm_prefill", "moe_gmm", "moe_shared_expert", "moe_router",
          "moe_dispatch", "moe_combine")

_plain_model_dict = harness.model_dict


def model_dict(config: dict) -> dict:
    """``harness.model_dict`` with the groups it drops and this model's
    layer table lives in: ``linear_attn_config`` and ``gqa_layers``."""
    return dict(_plain_model_dict(config),
                linear_attn_config=config["linear_attn_config"],
                gqa_layers=config["gqa_layers"])


def seeded_sessions_params(params: dict, seed: int) -> dict:
    """The parameter tree with what a seeded init leaves trivial made
    visible: ``gpt.init`` gives every norm's scale 0 and the router's
    selection bias 0. Seeded here: the K head norm's scale (the program's
    ``1 + scale``) in U(-0.5, 0.5), the selection bias in U(-0.01, 0.01)
    (PR 31's reading: it changes WHICH experts are chosen between close
    scores and adds little skew). ``A_log``, ``dt_bias``, the convs and the
    gate's projection come random from ``gpt.init`` itself."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 46)

    def uniform(i, like, lo, hi):
        return jax.random.uniform(jax.random.fold_in(key, i), like.shape,
                                  jnp.float32, lo, hi).astype(like.dtype)
    blocks = dict(params["blocks"])
    kda = dict(blocks["kda"])
    kda["gate_norm"] = {"scale": uniform(0, kda["gate_norm"]["scale"],
                                         -0.5, 0.5)}
    moe = dict(blocks["moe"])
    moe["router"] = dict(moe["router"], bias=uniform(
        1, moe["router"]["bias"], -0.01, 0.01))
    return dict(params, blocks=dict(blocks, kda=kda, moe=moe))


class Served(hybrid.Served):
    """``hybrid.Served`` (its hooks, its ``Trace``) on this model's seeded
    non-trivial weights, the sessions' first histories resident and every
    reachable program compiled in set-up, the check held against the
    sessions reference on snapshot hits the window served."""

    def __init__(self, config: dict, seed: int, traffic: dict):
        harness.model_dict = model_dict
        try:
            super().__init__(config, seed)
        finally:
            harness.model_dict = _plain_model_dict
        self.params = seeded_sessions_params(self.params, seed)
        self.server.engine.params = self.params
        self.traffic = traffic
        self._gaps: dict = {}
        self.state_dtypes: list = []    # of the state and snapshot pools
        # {request id: prompt tokens its admission skipped through a
        # snapshot} (0: prefilled from zero)
        self.armed_at: dict = {}
        engine = self.server.engine
        on_finish = engine.on_finish

        def finish_hook(req):
            self.armed_at[req.request_id] = int(req.prefix_cached_tokens)
            on_finish(req)
        engine.on_finish = finish_hook

    # -- set-up --------------------------------------------------------------

    def warm(self, traffic: dict, seed: int) -> None:
        """Compile what the window can reach, then make the sessions
        resident. A two-turn session of its own first: a history of one
        whole chunk and a tail in each final-chunk bucket (the chunk
        program, every final-chunk program, the snapshot's take), then its
        second turn (the arm), each long enough to run the decode program.
        Then every session's FIRST history at once, ``max_tokens`` 1: with
        half the slots resident they ride, which compiles nothing new (the
        engine's one decode program carries pieces from its first call)."""
        rng = np.random.default_rng([seed, 2])
        vocab = self.model_cfg.vocab_size
        engine = self.server.engine
        C, ps, done = engine._chunk_tokens, engine.kv.page_size, set()

        def post(prompt, max_tokens):
            return serve._post(self.url, {
                "prompt": prompt, "temperature": 0.0, "ignore_eos": True,
                "max_tokens": max_tokens})["choices"][0]["token_ids"]
        for tail in range(ps, C + 1, ps):
            bucket = engine._suffix_bucket(tail)
            if bucket in done:
                continue
            done.add(bucket)
            # (the last chunk holds ``tail`` rows: the cut one page under
            # the prompt's end, then one row more)
            first = rng.integers(258, vocab, C + tail).tolist()
            reply = post(first, 16)
            post(first + reply + rng.integers(258, vocab, 40).tolist(), 16)
        histories = loadgen_sessions.first_histories(self.traffic, vocab)
        with concurrent.futures.ThreadPoolExecutor(
                self.serve_cfg.max_batch_size) as pool:
            list(pool.map(lambda h: post(h, 1), histories))
        kda = self.stats().get("kda", {})
        print(f"[bench] {len(histories)} first histories resident: "
              f"{ {k: kda.get(k) for k in ('snapshots_taken', 'snapshot_hits', 'snapshot_evictions', 'snapshot_entries_live')} }",
              file=sys.stderr)

    # -- the check -----------------------------------------------------------

    def window_sample(self, raw: dict) -> list:
        """[(slot, prompt, served, armed at)] of CHECK_REQUESTS turns that
        were sent and ended inside the window and were armed from a
        snapshot, each from another slot: the longest first, then in the
        order they ended."""
        ended = [(*self.served[r["id"]], self.armed_at.get(r["id"], 0))
                 for r in sorted(
            (r for r in facts.window_requests(raw)
             if not facts.failed(r) and r["id"] in self.served),
            key=lambda r: r["done"])]
        hits = [s for s in ended if s[3] > 0 and len(s[2]) >= 2]
        longest = max(hits, key=lambda s: len(s[1]), default=None)
        sample, slots = [], set()
        for s in ([longest] if longest else []) + hits:
            if s[0] in slots:
                continue
            slots.add(s[0])
            sample.append(s)
            if len(sample) == CHECK_REQUESTS:
                break
        return sample

    def probe(self, raw: dict) -> list:
        """[(prompt cut one token behind the cut, served, the cut ``b``, the
        entry's states [Lk, n, d, d])] of PROBES turns of the window whose
        OWN snapshot (taken at the largest whole number of pages under the
        prompt's length while the window prefilled it) still stands in the
        pool: among the 4 x PROBES that ended last (the pool holds the
        latest takes), the shortest (what a probe costs is its context).
        The engine is as the window left it: the entry's rows are read out
        of the snapshot pool, then the cut prompt is POSTed: it hits
        ``b``'s page, is armed from that entry, prefills one token and
        generates right behind the hit."""
        from importlib import import_module
        hashes = import_module(
            f"{harness.PKG}.serve.kv_cache").prefix_page_hashes
        engine = self.server.engine
        self.wait_idle()
        ps, out, standing = engine.kv.page_size, [], []
        for r in sorted((r for r in facts.window_requests(raw)
                         if not facts.failed(r) and r["id"] in self.served),
                        key=lambda r: -r["done"]):
            prompt = self.served[r["id"]][1]
            b = (len(prompt) - 1) // ps * ps
            chain = hashes(prompt[:b], ps)
            with engine.lock:
                # (the entry, and every page of the chain under it)
                stands = (engine.kv.snapshot_at(chain[-1]) is not None
                          and engine.kv.hashed_pages(chain) == len(chain))
            if stands:
                standing.append((prompt, b))
            if len(standing) == 4 * PROBES:
                break
        for prompt, b in sorted(standing, key=lambda s: len(s[0])):
            with engine.lock:
                entry = engine.kv.snapshot_at(hashes(prompt[:b], ps)[-1])
            if entry is None:
                continue
            pools = (engine.kv.state["ssm"], engine.kv.snapshots["ssm"])
            self.state_dtypes = sorted({str(a.dtype) for a in pools})
            state = np.asarray(pools[1][:, entry], np.float32)
            reply = serve._post(self.url, {
                "prompt": prompt[:b + 1], "temperature": 0.0,
                "ignore_eos": True, "max_tokens": PROBE_TOKENS})
            if self.armed_at.get(reply["id"]) != b:
                continue                # (its pages went in between)
            out.append((prompt[:b + 1], reply["choices"][0]["token_ids"], b,
                        state))
            if len(out) == PROBES:
                break
        return out

    def wait_idle(self, timeout_s: float = 60.0) -> None:
        """Wait until the engine holds no request (a decode dispatch it
        left in flight touches no snapshot): what the callers left in
        flight at the window's close is
        aborted or ends (a prompt still being prefilled may yet take a
        snapshot, and that copy DONATES the pool a probe reads)."""
        engine = self.server.engine
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with engine.lock:
                busy = (engine.scheduler.active_count
                        or engine.scheduler.queue_depth or engine._riding
                        or engine._partial_prefills)
            if not busy:
                return
            time.sleep(0.05)
        raise RuntimeError("the engine did not go idle after the window")

    def release_pools(self) -> None:
        """Stop the engine thread and give the K/V pools', the state
        pools' and the snapshot pools' memory back before the reference
        runs: nothing is served after the probes."""
        self.server.stop_engine()
        kv = self.server.engine.kv
        kv.k_pages.delete()
        kv.v_pages.delete()
        for pools in (kv.state, getattr(kv, "snapshots", None)):
            for pool in (pools or {}).values():
                pool.delete()

    def reference_gaps(self, sample: list, probes: list,
                       wrong: str | None) -> dict:
        """Each sampled turn's whole session (prompt and served reply), and
        each probe's, teacher-forced through ``sessions_decoder.logits``
        from position 0 (with the faults of ``wrong``): every served
        token's gap (the reference's largest logit less the served
        token's), the routing margins, the mean logit standard deviation of
        a turn; of the probes also the worst relative error of an entry's
        state against the reference's state before its cut. Kept a
        ``wrong`` (one sample a process)."""
        if wrong in self._gaps:
            return self._gaps[wrong]
        from benchmark.reference import sessions_decoder
        pad = int(self.config["serve"]["max_seq_len"])
        page = int(self.config["serve"]["kv_block_size"])

        def forced(prompt, served, hit):
            # (ONE count of positions for every reply, the last repeated:
            # what runs behind the jitted sub-layers, the head among it,
            # then has one shape and compiles once, not once a length)
            n = len(served)
            at = list(range(len(prompt) - 1, len(prompt) - 1 + n))
            at += at[-1:] * (CHECK_POSITIONS - n)
            lg, margin, states = sessions_decoder.logits(
                self.params, prompt + served[:-1], self.config, positions=at,
                wrong=wrong, with_margin=True, compiled=True, pad_to=pad,
                hit=hit, page=page, with_state=True)
            lg = np.asarray(lg)[:n]
            return ((lg.max(-1) - lg[np.arange(n), served]).tolist(),
                    np.asarray(margin)[:n].tolist(), float(lg.std()),
                    np.asarray(states))
        out = {"gaps": [], "margins": [], "probe_gaps": [], "state_err": []}
        stds = []
        for _, prompt, served, armed in sample:
            gaps, margins, std, _ = forced(prompt, served, armed)
            out["gaps"] += gaps
            out["margins"] += margins
            stds.append(std)
        for prompt, served, b, entry in probes:
            gaps, _, _, states = forced(prompt, served, b)
            out["probe_gaps"] += gaps
            out["state_err"].append(max(
                float(np.linalg.norm(entry[i] - states[i])
                      / max(np.linalg.norm(states[i]), 1e-30))
                for i in range(len(states))))
        out["std"] = float(np.mean(stds)) if stds else 0.0
        self._gaps[wrong] = out
        return out

    def check_served(self, sample: list, probes: list,
                     wrong: str | None = None, detail: bool = False) -> dict:
        """Hold served tokens and snapshot entries to the plain reference
        by the three limits above. ``wrong`` gives the reference a fault:
        how one shows that the check fails when it should."""
        if not sample:
            return {"ok": False, "requests": 0, "tokens": 0}
        ref = self.reference_gaps(sample, probes, wrong)
        gaps, std = ref["gaps"], ref["std"]
        tol = CHECK_TOLERANCE_STD * std
        missed = sum(g > tol for g in gaps)
        probe_missed = sum(g > tol for g in ref["probe_gaps"])
        state_err = max(ref["state_err"], default=None)     # None: no probe
        out = {"ok": bool(len(sample) == CHECK_REQUESTS
                          and len(probes) == PROBES
                          and missed <= CHECK_MAY_MISS * len(gaps)
                          and probe_missed <= PROBE_MAY_MISS
                          * len(ref["probe_gaps"])
                          and state_err is not None
                          and state_err <= STATE_TOLERANCE
                          and self.state_dtypes == ["float32"]),
               "tokens_under_tol": missed, "may_miss": CHECK_MAY_MISS,
               "tokens": len(gaps),
               "worst_gap_std": max(gaps) / std,
               "mean_gap_std": float(np.mean(gaps)) / std,
               "probes": len(probes),
               "probe_tokens": len(ref["probe_gaps"]),
               "probe_tokens_under_tol": probe_missed,
               "probe_may_miss": PROBE_MAY_MISS,
               "probe_worst_gap_std": max(ref["probe_gaps"], default=0.0)
               / std,
               "state_rel_err": state_err, "state_tol": STATE_TOLERANCE,
               "state_dtypes": self.state_dtypes,
               "tol": tol, "logit_std": std, "requests": len(sample),
               "slots": len({s[0] for s in sample}),
               "snapshot_hits": sum(s[3] > 0 for s in sample),
               "longest_session": max(len(s[1]) + len(s[2])
                                      for s in sample),
               "tokens_off_the_reference_argmax": sum(g > 0 for g in gaps),
               "least_routing_margin": min(ref["margins"])}
        if detail:
            out.update(gaps=gaps, margins=ref["margins"],
                       probe_gaps=ref["probe_gaps"],
                       state_errs=ref["state_err"])
        return out

    # -- the window ----------------------------------------------------------

    def drive(self, *args, **kwargs) -> dict:
        """``serve.Served.drive`` with the load generator's child started
        as ``benchmark.loadgen_sessions`` (and, through ``hybrid.Served``,
        the hybrid runner's ``Trace``): the seams that need no edit to a
        file the benchmark has."""
        def popen(cmd, **kw):
            cmd = ["benchmark.loadgen_sessions" if c == "benchmark.loadgen"
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
    """``linear.scope_seconds`` with this model's scopes: the chunk and
    final-chunk programs' texts handed on under their jitted functions'
    names, and every operation of a program that is NAMED for a scope (the
    two snapshot copies, ``jit_kda_snapshot_take`` / ``_arm``: programs of
    their own, of which ``program_texts`` has none) put under it."""
    jitted = {"prefill chunk": "extend_chunk", "suffix prefill":
              "extend_prefill"}
    renamed = {next((f"{fn} {name}" for head, fn in jitted.items()
                     if name.startswith(head)), name): text
               for name, text in texts.items()}
    # a program named for a scope: ``jit_kda_snapshot_take``
    named = {p: p[len("jit_"):] for p in op_s
             if p.startswith("jit_") and p[len("jit_"):] in SCOPES}
    plain = hybrid.SCOPES
    hybrid.SCOPES = SCOPES
    try:
        out = defaultdict(lambda: [0, 0.0], {
            k: list(v) for k, v in hybrid.scope_seconds(
                {p: ops for p, ops in op_s.items() if p not in named},
                renamed).items()})
    finally:
        hybrid.SCOPES = plain
    for program, scope in named.items():
        for n, seconds in op_s[program].values():
            out[scope][0] += n
            out[scope][1] += seconds
    return {k: tuple(v) for k, v in out.items()}


def require_sessions_support(config: dict) -> None:
    """Leave at once, with one line, where the program under test cannot
    build this configuration or follow a prefix hit through a recurrent
    state: a commit from before ``solar_open2`` reads the file as a uniform
    stack with rope, and one without a snapshot pool would re-prefill every
    turn and be measured as something it is not."""
    schema = import_module(f"{harness.PKG}.config.schema")
    who = "benchmark/runners/sessions.py: this program"
    if "state_snapshot_entries" not in getattr(
            schema.ServeConfig, "__dataclass_fields__", {}):
        raise SystemExit(
            f"{who} keeps no snapshot of a recurrent state at a page "
            f"boundary (ServeConfig has no state_snapshot_entries): it "
            f"cannot run {config['name']}")
    try:
        model = schema.ModelConfig.from_dict(model_dict(config))
    except Exception as e:
        raise SystemExit(f"{who} cannot read {config['name']}: {e}")
    softmax = set(config["gqa_layers"])
    wanted = ("".join(("*" if i in softmax else "K") + "E"
                      for i in range(config["num_hidden_layers"])),
              config["linear_attn_config"]["num_heads"],
              bool(config["kda_allow_neg_eigval"]),
              bool(config["use_gqa_gate"]), config["n_routed_experts"],
              config["router_experts"], "none")
    built = (model.layer_pattern, model.kda.num_heads,
             getattr(model.kda, "allow_neg_eigval", None),
             getattr(model, "attention_gate", None), model.moe.num_experts,
             model.moe.router_experts, model.position_embedding)
    if built != wanted:
        raise SystemExit(
            f"{who} builds {config['name']} with (layer table, K heads, "
            f"beta up to 2, gated attention, experts held, router width, "
            f"position embedding) = {built}, the configuration says "
            f"{wanted}: it cannot run this cell")


def run(cell: dict, config: dict, traffic_path: str, seed: int,
        seconds: float, trace: bool, t_process_start: float,
        require_tpu: bool = True) -> dict:
    """One run of a sessions serving cell; ``runners/serve.py run`` with
    this runner's set-up, child and check."""
    require_sessions_support(config)
    device = harness.start(cell["chips"], require_tpu)
    harness.mark("imports and device", t_process_start)
    traffic = loadgen_sessions.load(traffic_path)
    served = Served(config, seed, traffic)
    harness.mark(f"weights ({served.init_s:.1f}s) and server up",
                 t_process_start)
    try:
        with harness.scratch_dir("bench_sessions_traffic_") as tmp:
            # ``facts`` and ``serve.drive`` know serve-open / serve-closed
            path = os.path.join(tmp, os.path.basename(traffic_path))
            with open(path, "w") as f:
                json.dump(dict(traffic, kind="serve-" + traffic[
                    "kind"].split("-", 1)[1]), f)
            raw = serve.measure(served, cell, path, seed, seconds, trace,
                                t_process_start, device)
        if raw["trace"].get("op_s"):
            # before the pools go: the programs' texts are lowered from the
            # live arguments' shapes (read back from the compile cache)
            raw["trace"]["scope_s"] = scope_seconds(
                raw["trace"]["op_s"],
                served.server.engine.program_texts(chunks=True))
            harness.mark("scopes of the traced operations", t_process_start)
        sample = served.window_sample(raw)
        probes = served.probe(raw)
        harness.mark(f"{len(probes)} probes behind their own snapshots",
                     t_process_start)
        served.release_pools()
        check = served.check_served(sample, probes, detail=True)
        # every sampled token's gap and margin, for reading the check at
        # other numbers than it was run with (stderr alone)
        print("[bench] check detail " + json.dumps({
            k: [round(x, 6) for x in check.pop(k)]
            for k in ("gaps", "margins", "probe_gaps", "state_errs")
            if k in check}), file=sys.stderr)
        raw["check"] = check
        print(f"[bench] reference check on the window's snapshot hits "
              f"{raw['check']}", file=sys.stderr)
        harness.mark("reference check on the window's snapshot hits",
                     t_process_start)
        return raw
    finally:
        served.close()
