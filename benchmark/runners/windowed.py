"""The runner of a serving cell whose model mixes WINDOW layers with full
ones, every layer sparse experts (traffic ``kind`` ``windowed-closed``;
``model_type: mellum``): the serving runner as it is (``runners/serve.py``:
the same server, hooks, load generator and window), with

- the configuration read WITH its nested groups (``layer_types``,
  ``rope_parameters``: ``harness.model_dict`` drops every list and group, and
  without them the program would build a stack of full layers under one
  rope); a program that cannot build the window layers leaves at once with
  one line and exit 1, before JAX starts;
- weights whose trivial vectors are seeded NON-trivially (every norm's scale,
  the per-head q/k norms' among them: a unit scale hides a missing norm);
- every program the window can reach compiled in set-up: the two cold
  buckets, the chunk program, the two final-chunk buckets and the decode
  program with a piece riding (a model with window layers prefills over its
  ring in chunks of two pages: ``serve/kv_cache.py WINDOW_CHUNK_PAGES``);
- the run judged on the replies that ENDED inside the window
  (``runners/linear.py ended_in_window``): a reply takes ~30 s behind a wait
  as long, so none is both sent and ended inside 51 s;
- the correctness check held against the plain reference
  (``reference/windowed_decoder.py``) on tokens the WINDOW served, i.e. what
  the timed path produced: prompts that rode decode steps piece by piece,
  then decoding through the ring. After the window the engine is stopped
  and both pools freed (the float32 reference runs beside 7.6 GB of
  weights), and CHECK_REQUESTS replies that ended in it are teacher-forced
  WHOLE (prompt and reply, up to 15k tokens, at ONE padded length) through
  the reference; held are CHECK_SPAN served tokens at three places of each:
  the reply's first (the prompt's last piece and the first decode steps),
  around the first time its ring WRAPPED while it decoded, and its last (the
  context at its longest). The sample holds the two longest contexts (past
  4 x the window), the shortest, and the next to end from other slots; AND on
  the route every attention program took: a run on the gather path is not
  correct, whatever its tokens;
- a trace by scope over all programs (``scope_s``) and over the decode
  program alone (``decode_scope_s``): ``window_attention`` is the window
  layers' page kernel, ``paged_attention`` the full layers'.

``run.py`` picks a runner by the traffic kind's first word; the traffic and
load generators know ``serve-open`` / ``serve-closed`` alone, so they are
handed a copy of the traffic file with the kind's first word set back to
``serve``. ``run["kind"]`` stays ``"serve"``.
"""

from __future__ import annotations

import json
import sys
from importlib import import_module

import numpy as np

from benchmark import facts, harness
from benchmark.reference import windowed_decoder
from benchmark.runners import hybrid, linear, parallel, serve, shortconv

# The form of the other window-sampled checks: a served token's reference
# logit may lie under the reference's largest by its "gap", counted in
# reference-logit standard deviations (0.96 here). Three limits, each set
# between its two readings on the chip (my chip runs, PR 63, calls 1 and 2;
# ``experiments/windowed_check_readings.py``; PERF.md 6 has the table): the
# RIGHT model on nine runs of nine seeds, ~2,200 held tokens each, and the
# reference's wrong variants on the same served tokens, two seeds each.
#
# 1. CHECK_MAY_MISS of the held tokens may lie further down than
#    CHECK_TOLERANCE_STD. The right model: NO token of 19,700 further down
#    than 0.25 std (its worst 0.045-0.12 std: bfloat16 through 8 layers
#    moves a logit by a hundredth of its spread, and 2-5 % of the served
#    tokens are the reference's runner-up at a near-tie). The nearest wrong
#    variant, YaRN's frequencies without the attention factor: 1.6 % and
#    0.9 %; the top-8 weights not renormalised 14 and 24 %; YaRN on every
#    layer 33 and 49 %; every layer full, the period's full layer first
#    75-89 %; float8 operands (the nearest precision under the
#    configuration's bfloat16) 100 %. 0.25 std is twice the right model's
#    worst token; 0.5 % (11 of 2,200 tokens) is 0.55 of the nearest wrong
#    reading.
# 2. The MEAN gap may not pass CHECK_MEAN_GAP_STD: the right model 0.0002 to
#    0.0007 std, the nearest wrong variant 0.030 and 0.032 (no attention
#    factor), then 0.083-0.125, 0.20-0.30, 1.4-4.4. 0.005 is 7 x the right
#    model's largest and a sixth of the nearest wrong reading.
# 3. The reference must explain the served tokens BETTER than each of its
#    NEAR_MISSES does: the same tokens through the reference with ONE fault
#    toggled (YaRN's frequencies without the attention factor; the top-8
#    weights not renormalised) must read a mean gap larger than the
#    reference's own by more than CHECK_NEAR_MISS_STD: PAIRED on the same
#    tokens, because a served token is the argmax of the model that served
#    it (``runners/linear.py`` has the argument). The right model reads
#    +0.005 to +0.033 std (attention factor) and +0.064 to +0.19
#    (renormalisation) on nine seeds; a reference that HAS the fault reads
#    the other sign (-0.033, -0.085). The near misses are read on the
#    sample's CHECK_NEAR_MISS_REQUESTS shortest contexts (what a pass costs
#    is the context). With limits 1 and 2 where they are this third limit
#    catches nothing the first two let through on these seeds; it stays
#    because it is the one that does not depend on the server's noise.
#
# What NO check on served tokens separates, said plainly: the reference with
# a window of 1,023 or 1,025 keys. One key of 1,024 at the window's far edge
# moves a logit by ~1e-3 of its standard deviation; the served token stays
# both models' argmax, and a greedy token says nothing more. Held on LOGITS
# on the CPU (tests/test_mellum.py, float32, 1e-4, a window of 16) and in
# float32 on the chip at the published widths (chip_smoke.py).
UNSEEN_BY_TOKENS = ("window_minus_1", "window_plus_1")
CHECK_REQUESTS = 6
CHECK_SPAN = 128
CHECK_TOLERANCE_STD = 0.25
CHECK_MAY_MISS = 0.005
CHECK_MEAN_GAP_STD = 0.005
NEAR_MISSES = ("no_attention_factor", "no_renorm")
CHECK_NEAR_MISS_STD = 0.0
CHECK_NEAR_MISS_REQUESTS = 4
# (the reference compiles ONE length, the server's ``max_seq_len``; its row
# blocks run over the live tokens alone)

# The names a device trace shows this model's work under: Pallas kernels by
# the name the program gives them, XLA operations by the named scopes they
# were traced in. An operation counts under EVERY scope it lies in.
SCOPES = ("window_attention_mq", "window_attention", "paged_attention_mq",
          "paged_attention", "kv_page_write", "moe_gmm_prefill", "moe_gmm",
          "moe_router", "moe_dispatch", "moe_combine", "lm_head", "sampler")

_plain_model_dict = harness.model_dict


def model_dict(config: dict) -> dict:
    """The configuration file's model keys as ``ModelConfig.from_dict``
    takes them, WITH the groups that say which layers have a window and
    which rope a kind has (no ``rope_theta`` at the top level: it lives in
    ``rope_parameters``)."""
    d = {k: v for k, v in config.items() if not isinstance(v, (dict, list))}
    for group in ("layer_types", "mlp_layer_types", "rope_parameters"):
        d[group] = config[group]
    return d


def seeded_windowed_params(params: dict, seed: int) -> dict:
    """The parameter tree with the vectors a seeded init leaves trivial made
    visible: ``gpt.init`` gives every norm's scale 0 (a plain RMS norm).
    Seeded here: each scale (the program's ``1 + scale``; the two norms a
    layer, the q/k head norms and the final one) in U(-0.3, 0.3)."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 63)
    count = iter(range(1 << 16))

    def visible(path, leaf):
        if path[-1].key != "scale":
            return leaf
        return jax.random.uniform(
            jax.random.fold_in(key, next(count)), leaf.shape, jnp.float32,
            -0.3, 0.3).astype(leaf.dtype)
    return jax.tree_util.tree_map_with_path(visible, params)


class Served(hybrid.Served):
    """``serve.Served`` on seeded non-trivial weights (``hybrid.Served``
    gives the hooks that keep what each request was served and the trace's
    seam; its own seeding wants a router bias this model has not, and is
    stood aside while it builds), every reachable program compiled in
    set-up, the check held against the windowed reference on what the window
    served."""

    # (False in the tests' rehearsal on the CPU, where the kernel is the
    # gather baseline by construction)
    require_streaming = True

    def __init__(self, config: dict, seed: int):
        harness.model_dict = model_dict
        hybrid_seeding = hybrid.seeded_hybrid_params
        hybrid.seeded_hybrid_params = lambda params, seed: params
        try:
            super().__init__(config, seed)
        finally:
            harness.model_dict = _plain_model_dict
            hybrid.seeded_hybrid_params = hybrid_seeding
        # nothing has been served yet and the engine's programs take the
        # tree as an argument: server and reference read the same one
        self.params = seeded_windowed_params(self.params, seed)
        self.server.engine.params = self.params
        kv = self.server.engine.kv
        # (read now: the pools are deleted before the check runs)
        self.ring_rows = kv.ring_entries * kv.page_size
        self._gaps: dict = {}

    # -- set-up --------------------------------------------------------------

    def warm(self, traffic: dict, seed: int) -> None:
        """One request a cold bucket, then one a final-chunk bucket behind a
        whole chunk (the chunk program, then the final chunk's), each long
        enough to run the decode program (ONE program holds the plain step
        and the step that carries a piece: a riding prompt compiles
        nothing)."""
        rng = np.random.default_rng([seed, 2])
        vocab = self.model_cfg.vocab_size
        engine = self.server.engine
        C, ps = engine._chunk_tokens, engine.kv.page_size
        lengths, done = [], set()
        for n in range(ps, C + 1, ps):
            if engine._bucket(n) not in done:
                done.add(engine._bucket(n))
                lengths.append(n)
        done = set()
        for tail in range(ps, C + 1, ps):
            if engine._suffix_bucket(tail) not in done:
                done.add(engine._suffix_bucket(tail))
                lengths.append(C + tail)
        for n in lengths:
            serve._post(self.url, {
                "prompt": rng.integers(258, vocab, n).tolist(),
                "temperature": 0.0, "max_tokens": 16})

    # -- the check -----------------------------------------------------------

    def window_sample(self, raw: dict) -> list:
        """[(slot, prompt, served)] of CHECK_REQUESTS replies that ended
        inside the window: the two longest contexts, the shortest, then the
        first to end from slots not yet in the sample."""
        ended = [self.served[r["id"]] for r in sorted(
            (r for r in linear.ended_in_window(raw)
             if not facts.failed(r) and r["id"] in self.served),
            key=lambda r: r["done"])]
        ended = [s for s in ended if len(s[2]) >= 6]
        by_context = sorted(ended, key=lambda s: len(s[1]) + len(s[2]))
        sample = by_context[-2:] + by_context[:1]
        slots = {s[0] for s in sample}
        # ... one a slot before a second of any slot
        for other_slots_first in (True, False):
            for s in ended:
                if len(sample) >= CHECK_REQUESTS:
                    break
                if any(s is t for t in sample) or (
                        other_slots_first and s[0] in slots):
                    continue
                slots.add(s[0])
                sample.append(s)
        return sample[:CHECK_REQUESTS]

    def release_pools(self) -> None:
        """Stop the engine thread and give both pools' memory back before
        the float32 reference runs. (The thread first: a closed loop's
        callers leave requests in flight, and a dispatch over a deleted pool
        makes the engine allocate a new one.)"""
        self.server.stop_engine()
        kv = self.server.engine.kv
        for pool in (kv.k_pages, kv.v_pages):
            pool.delete()

    def held_tokens(self, prompt: list, served: list) -> tuple[list, bool]:
        """(indices into ``served`` of the tokens the check holds, whether
        the ring wrapped while the reply decoded): CHECK_SPAN at the reply's
        start, CHECK_SPAN around the first decode write that passed from
        the ring's last entry to its first, CHECK_SPAN at its end."""
        n = len(served)
        span = min(CHECK_SPAN, n // 3)    # (a short reply: thirds of it)
        held = set(range(span)) | set(range(n - span, n))
        # served[j] is written at position len(prompt) + j
        wrap = next((j for j in range(span, n - span)
                     if (len(prompt) + j) % self.ring_rows == 0), None)
        if wrap is not None:
            held |= set(range(wrap - span // 2, wrap + span // 2))
        return sorted(held), wrap is not None

    def reference_gaps(self, sample: list, wrong: str | None) -> dict:
        """Each sampled reply's whole context teacher-forced through
        ``windowed_decoder.logits`` (with the faults of ``wrong``): every
        held token's gap, a list a request, and the request's logit
        standard deviation. Kept a (request, ``wrong``): one sample a
        process."""
        out = {"gaps": [], "std": []}
        for slot, prompt, served in sample:
            key = (slot, len(prompt), len(served), wrong)
            if key not in self._gaps:
                held, _ = self.held_tokens(prompt, served)
                lg = np.asarray(windowed_decoder.logits(
                    self.params, prompt + served[:-1], self.config,
                    positions=[len(prompt) - 1 + j for j in held],
                    wrong=wrong, round_to=self.serve_cfg.max_seq_len))
                tokens = np.asarray(served)[held]
                self._gaps[key] = (
                    (lg.max(-1) - lg[np.arange(len(held)), tokens]).tolist(),
                    float(lg.std()))
            gaps, std = self._gaps[key]
            out["gaps"].append(gaps)
            out["std"].append(std)
        return out

    def check_served(self, sample: list, wrong: str | None = None,
                     detail: bool = False) -> dict:
        """Hold served tokens to the plain reference by the three limits
        above; the sample must hold two contexts past 4 windows and a reply
        whose ring wrapped while it decoded; and every attention program of
        the run must have taken the page-streaming kernel. ``wrong`` gives
        the reference a fault (its near misses are then that reference with
        one of NEAR_MISSES toggled): how one shows that the check fails when
        it should. ``detail`` adds every token's gap."""
        if not sample:
            return {"ok": False, "requests": 0, "tokens": 0}
        ref = self.reference_gaps(sample, wrong)
        gaps = [g for request in ref["gaps"] for g in request]
        std = float(np.mean(ref["std"]))
        tol = CHECK_TOLERANCE_STD * std
        missed = sum(g > tol for g in gaps)
        mean = float(np.mean(gaps)) / std
        first = (missed <= CHECK_MAY_MISS * len(gaps)
                 and mean <= CHECK_MEAN_GAP_STD)
        # the near misses, on the shortest contexts (a reference that fails
        # the first two limits needs no third reading)
        short = sorted(range(len(sample)), key=lambda i: len(
            sample[i][1]) + len(sample[i][2]))[:CHECK_NEAR_MISS_REQUESTS]
        own = float(np.mean([g for i in short for g in ref["gaps"][i]])) / std
        further = {}
        for fault in NEAR_MISSES if first else ():
            toggled = set(wrong.split("+") if wrong else ()) ^ {fault}
            miss = self.reference_gaps([sample[i] for i in short],
                                       "+".join(sorted(toggled)) or None)
            further[fault] = float(np.mean(
                [g for request in miss["gaps"] for g in request])) / std - own
        contexts = [len(p) + len(s) for _, p, s in sample]
        wrapped = sum(self.held_tokens(p, s)[1] for _, p, s in sample)
        long = sum(c > 4 * self.config["sliding_window"] for c in contexts)
        impls = shortconv.attention_impls() + windowed_impls()
        streamed = bool(impls) and all(impl == "pallas" for _, impl in impls)
        out = {"ok": bool(len(sample) == CHECK_REQUESTS and long >= 2
                          and wrapped >= 1 and first
                          and min(further.values()) > CHECK_NEAR_MISS_STD
                          and (streamed or not self.require_streaming)),
               "attention_impls": [f"{op}={impl}" for op, impl in impls],
               "tokens_under_tol": missed, "may_miss": CHECK_MAY_MISS,
               "mean_gap_std": mean, "mean_gap_limit": CHECK_MEAN_GAP_STD,
               "near_miss_further_std": further,
               "worst_gap_std": max(gaps) / std, "tol": tol,
               "logit_std": std, "requests": len(sample),
               "contexts": contexts, "contexts_past_4_windows": long,
               "ring_wrapped_while_decoding": wrapped,
               "slots": len({s[0] for s in sample}), "tokens": len(gaps),
               "tokens_off_the_reference_argmax": sum(g > 0 for g in gaps)}
        if detail:
            out["gaps"] = gaps
        return out


def windowed_impls() -> list:
    """[(op, implementation)] of every window-attention program this process
    has traced (``shortconv.attention_impls`` keeps the ``paged_attention``
    ones)."""
    platform = import_module(f"{harness.PKG}.utils.platform")
    reported = getattr(platform, "reported_impls", lambda: ())()
    return sorted({(op, impl) for op, impl, _ in reported
                   if op.startswith("window_attention")})


def require_windowed_support(config: dict) -> None:
    """Leave at once, with a reason, where the program under test cannot
    build this configuration: a commit from before ``layer_types`` and
    ``rope_parameters`` were read loads it as a stack of full layers under
    one rope of base 10,000 (another model, and a cache of 8 full-length
    planes), and would be measured as something it is not."""
    schema = import_module(f"{harness.PKG}.config.schema")
    try:
        model = schema.ModelConfig.from_dict(model_dict(config))
    except Exception as e:
        raise SystemExit(f"benchmark/runners/windowed.py: this program "
                         f"cannot read {config['name']}: {e}")
    kinds = {"sliding_attention": "sliding", "full_attention": "full"}
    wanted = (tuple(kinds[t] for t in config["layer_types"]),
              config["sliding_window"],
              config["rope_parameters"]["full_attention"]["attention_factor"],
              config["num_experts"])
    built = (tuple(getattr(model, "layer_types", ())),
             getattr(model, "sliding_window", 0),
             getattr(model.rope, "attention_factor", 1.0),
             model.moe.num_experts if model.is_moe else 0)
    if built != wanted:
        raise SystemExit(
            f"benchmark/runners/windowed.py: this program builds "
            f"{config['name']} with (layer kinds, window, attention factor, "
            f"experts) = {built}, the configuration says {wanted}: it "
            "cannot run this cell")


def window(served: Served, cell: dict, traffic_path: str, seed: int,
           seconds: float, trace: bool, t_process_start: float,
           device: dict) -> tuple[dict, list]:
    """``parallel.window`` (warm and drive the server, read the traced
    programs' scopes, stop the engine and free its pools) with this cell's
    scopes where it reads its own."""
    plain = parallel.SCOPES
    parallel.SCOPES = SCOPES
    try:
        return parallel.window(served, cell, traffic_path, seed, seconds,
                               trace, t_process_start, device)
    finally:
        parallel.SCOPES = plain


def run(cell: dict, config: dict, traffic_path: str, seed: int,
        seconds: float, trace: bool, t_process_start: float,
        require_tpu: bool = True) -> dict:
    """One run of a windowed serving cell; ``runners/serve.py run`` with the
    traffic file's kind handed on as the generators know it, the run judged
    on the replies that ended in the window and the check held on them."""
    require_windowed_support(config)
    device = harness.start(cell["chips"], require_tpu)
    harness.mark("imports and device", t_process_start)
    served = Served(config, seed)
    served.require_streaming = require_tpu
    harness.mark(f"weights ({served.init_s:.1f}s) and server up",
                 t_process_start)
    try:
        raw, sample = window(served, cell, traffic_path, seed, seconds,
                             trace, t_process_start, device)
        raw["judged"] = linear.ENDED_IN_WINDOW
        facts.window_requests = linear.window_requests
        check = served.check_served(sample, detail=True)
        # every held token's gap, for reading the check at other numbers
        # than it was run with (stderr alone)
        print("[bench] check detail " + json.dumps(
            [round(g, 6) for g in check.pop("gaps", [])]), file=sys.stderr)
        raw["check"] = check
        # (beside the check, not part of it: the full pool must not preempt)
        raw["check"]["preemptions_in_window"] = (
            raw["stats"]["after"]["preemptions"]
            - raw["stats"]["before"]["preemptions"])
        print(f"[bench] reference check on the window's requests "
              f"{raw['check']}", file=sys.stderr)
        harness.mark("reference check on the window's requests",
                     t_process_start)
        return raw
    finally:
        served.close()
