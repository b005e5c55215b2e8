"""The runner of a hybrid state-space / attention / sparse-expert serving
cell (traffic ``kind`` ``hybrid-closed``): the serving runner as it is
(``runners/serve.py``: the same server, hooks, warm-up, load generator and
window), with

- the correctness check held against the plain hybrid reference
  (``reference/hybrid_decoder.py``) on tokens the WINDOW served (requests
  that ended inside it, from many slots, teacher-forced after it closes;
  the dense runner's four prompts before the window are not served here),
  on weights whose state-space vectors, gated-norm scale and selection
  bias are seeded NON-trivially (a zero bias or a unit norm hides its own
  absence) and whose experts are served at full scale: a token at a
  routing near-tie, where two sets of experts are both right, is left out
  of the sample instead;
- a trace of its own: device seconds by KERNEL or SCOPE name from the same
  profile (``run["trace"]["scope_s"]``), because ``trace_reduce`` keeps
  only the ten operations that took most time and this model's decode step
  has about twenty named ones. A trace names an XLA operation by its HLO
  instruction and carries no named scope (``experiments/
  trace_scope_probe.py``, PR 31), so the scope is read from the
  instruction's ``op_name`` in the engine's own ``program_texts()``.

``run.py`` picks a runner by the traffic kind's first word; the traffic and
load generators know ``serve-open`` / ``serve-closed`` alone, so they are
handed a copy of the traffic file with the kind's first word set back to
``serve`` (as ``runners/moe.py`` does). ``run["kind"]`` stays ``"serve"``.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import shutil
import sys
import time
from collections import defaultdict
from importlib import import_module

import numpy as np

from benchmark import facts, harness, trace_reduce, traffic as traffic_mod
from benchmark.reference import hybrid_decoder
from benchmark.runners import serve

# The form of runners/serve.py's check: a served token's reference logit may
# lie CHECK_TOLERANCE_STD reference-logit standard deviations under the
# reference's largest (the dense check's own number). What is held are
# tokens the WINDOW served: CHECK_REQUESTS of the requests that began and
# ended inside it, each from another slot, the first CHECK_NEW_TOKENS of
# each teacher-forced through the reference after the window closes.
CHECK_TOLERANCE_STD = 0.25
CHECK_REQUESTS, CHECK_NEW_TOKENS = 12, 96
# The experts are served at full scale, and 128 experts under a seeded
# router score within a hundredth of each other: the gap between a
# position's 6th and 7th largest biased score is 0.010 in the mean, and
# bfloat16's rounding of the stream moves a score by about a tenth of that
# (my chip runs, PR 31, calls A-B). So in one expert layer in ten the
# server picks the other set, either set is right, and the position's
# stream moves by a whole expert's output (half the router's experts are
# absent here: often by one expert's against nothing): held to the worst
# of ALL its tokens the right model reads 1.3-1.4 std. The check is made
# aware of such ties in two ways, both read off the chip:
# - a token is LEFT OUT of the sample where the reference's 6th and 7th
#   biased scores, in any expert layer at the token's position, are closer
#   than ROUTER_TIE_MARGIN (30-35 % of the sampled tokens are kept; fewer
#   than CHECK_MIN_KEPT is itself not correct: under softmax scores 0.1 to
#   0.4 % are);
# - a swap at an EARLIER position reaches a kept token through the
#   state-space layers' state and the attention layers' keys, which no
#   margin at the token's own position tells: CHECK_MAY_MISS of the kept
#   tokens may lie further down than the tolerance.
# Kept tokens further down than 0.25 std, three seeds of ~370 kept tokens
# (calls A-B; PERF.md 6 has the table): the RIGHT model 2.2 / 4.1 / 1.9 %
# (and 2.0-5.0 % in the seven final runs of call C);
# float8 operands in the routed experts' two matmuls ALONE (the nearest
# precision under bfloat16, in the grouped matmul and nowhere else) 21.5 /
# 19.3 / 26.3 %; in every matmul 74.6 / 70.8 / 71.7 %; the gated norm before
# the gate 78.9 / 82.6 / 84.5 %; padding let into the state 46.2 / 42.3 /
# 45.9 %. 9 % lies between the right model's largest (5.0) and the routed
# experts' least (19.3) with a factor of two to either; a slot served wrong
# through its 96 tokens adds 1 / 12 = 8.3 % to the right model's own and is
# not correct. NOT separated, said plainly: rope in attention 5.7 / 4.9 /
# 7.7 % and the bias used as a weight 2.4 / 3.1 / 1.9 %; both are held on
# LOGITS (tests/test_hybrid.py, 1e-4).
ROUTER_TIE_MARGIN = 0.002
CHECK_MIN_KEPT = 0.15
CHECK_MAY_MISS = 0.09
# the reference compiles one shape a multiple of this many tokens
CHECK_ROUND_TO = 128

# The names a device trace shows this model's work under: Pallas kernels by
# the name the program gives them, XLA operations by the named scope they
# were traced in (``ops/ssm.py``, ``models/layers.py``). Longest first: a
# prefill's kernels carry the decode kernel's name as a prefix.
SCOPES = ("ssm_scan_prefill", "ssm_gated_norm", "ssm_decode", "ssm_conv",
          "moe_gmm_prefill", "moe_gmm", "moe_shared_expert", "moe_router",
          "moe_dispatch", "moe_combine", "paged_attention", "kv_page_write")


def scope_of(texts) -> str | None:
    """The first of ``SCOPES`` that any of an event's texts (its name, its
    string stats: the HLO line and the operation's source name) holds."""
    for scope in SCOPES:
        for text in texts:
            if re.search(rf"(?<![A-Za-z_]){scope}(?![A-Za-z_])", text):
                return scope
    return None


def op_seconds(profile, names: dict = trace_reduce.NAMES) -> dict:
    """{program: {operation: [events, device seconds]}} over the LEAF
    operations of the first device plane (``trace_reduce.load`` / ``leaves``
    decide what a leaf is), each put under the program whose execution (an
    event of the modules line) it lies in, by ``trace_reduce.program_of``.
    The operation is its HLO instruction's name (``fusion.123``,
    ``moe_gmm.160``). {} where the profile has no such plane."""
    planes = trace_reduce.load(profile, names)
    if not planes:
        return {}
    first = planes[sorted(planes)[0]]
    modules = sorted((s, e, trace_reduce.program_of(n, names))
                     for n, s, e in first["modules"])
    starts = [m[0] for m in modules]
    out: dict = defaultdict(lambda: defaultdict(lambda: [0, 0.0]))
    for name, s, e in trace_reduce.leaves(first["ops"], names):
        i = bisect.bisect_right(starts, s) - 1
        program = (modules[i][2] if i >= 0 and s < modules[i][1]
                   else "outside any program")
        cell = out[program][name.split(":", 1)[0]]
        cell[0] += 1
        cell[1] += e - s
    return {p: {k: tuple(v) for k, v in ops.items()}
            for p, ops in out.items()}


_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"')


def scopes_of_instructions(hlo_text: str) -> dict:
    """{HLO instruction name: scope} for the instructions of an optimised
    HLO text whose ``op_name`` (the operation's source name, named scopes
    and all) holds one of ``SCOPES``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            scope = scope_of([m.group(2)])
            if scope:
                out[m.group(1)] = scope
    return out


def scope_seconds(op_s: dict, texts: dict,
                  names: dict = trace_reduce.NAMES) -> dict:
    """{scope: [events, device seconds]} from ``op_seconds`` and the
    engine's ``program_texts()``: a Pallas kernel is told by its own name
    (``moe_gmm.160``), an XLA operation (``fusion.123``) by the scope its
    instruction's ``op_name`` holds in the text of the program it ran in."""
    by_program: dict = defaultdict(dict)
    for name, text in texts.items():
        # ("prefill 256" and "prefill 512" are one program in a trace)
        program = trace_reduce.program_of(
            "jit_" + name.split(" ")[0], names)
        for k, v in scopes_of_instructions(text).items():
            by_program[program].setdefault(k, v)
    out: dict = defaultdict(lambda: [0, 0.0])
    for program, ops in op_s.items():
        for name, (n, seconds) in ops.items():
            scope = scope_of([name]) or by_program[program].get(name)
            if scope:
                out[scope][0] += n
                out[scope][1] += seconds
    return {k: tuple(v) for k, v in out.items()}


class Trace(harness.Trace):
    """``harness.Trace`` that also keeps ``op_seconds`` of the profile
    (``result["op_s"]``) before the profile's directory is removed."""

    def __exit__(self, *exc):
        if not self.on:
            return super().__exit__(*exc)
        import jax
        self.t1 = time.monotonic()
        try:
            jax.profiler.stop_trace()
            path = trace_reduce.find_xplane(self._dir)
            if path and exc[0] is None:
                profile = jax.profiler.ProfileData.from_file(path)
                self.result = trace_reduce.reduce(
                    trace_reduce.load(profile), self.t1 - self.t0)
                if self.result:
                    self.result["t0"], self.result["t1"] = self.t0, self.t1
                    self.result["op_s"] = op_seconds(profile)
                self.listing = trace_reduce.listing(profile)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False


def seeded_hybrid_params(params: dict, seed: int) -> dict:
    """The parameter tree with the vectors a seeded init leaves trivial made
    visible: ``gpt.init`` gives the skip ``D`` = 1, the gated norm's and
    every layer norm's scale 0 (a plain RMS norm) and the router's selection
    bias 0. A trained model's are learned; at their trivial values a server
    that left the gated norm's weight, the skip or the bias OUT would pass
    the check. Seeded here: ``D`` in U(0.5, 1.5), the gated norm's scale
    (the program's ``1 + scale``) in U(-0.5, 0.5), the selection bias in
    U(-0.01, 0.01): it changes WHICH experts are chosen between scores that
    close and adds little skew of its own (at +-0.1, the spread of the
    scores themselves, a third of the held experts went unused a step: my
    chip runs, PR 31, call 6; at +-0.01 a step reaches 84 % of them, the
    seeded model's own unevenness: PERF.md 6). Every expert is served at
    the scale ``gpt.init`` gives it."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 31)

    def uniform(i, like, lo, hi):
        return jax.random.uniform(jax.random.fold_in(key, i), like.shape,
                                  jnp.float32, lo, hi).astype(like.dtype)
    blocks = dict(params["blocks"])
    if "ssm" in blocks:
        ssm = dict(blocks["ssm"])
        ssm["D"] = uniform(0, ssm["D"], 0.5, 1.5)
        ssm["gate_norm"] = {"scale": uniform(1, ssm["gate_norm"]["scale"],
                                             -0.5, 0.5)}
        blocks["ssm"] = ssm
    if "moe" in blocks:
        moe = dict(blocks["moe"])
        moe["router"] = dict(moe["router"], bias=uniform(
            2, moe["router"]["bias"], -0.01, 0.01))
        blocks["moe"] = moe
    return dict(params, blocks=blocks)


class Served(serve.Served):
    """``serve.Served`` on seeded non-trivial weights, with the check held
    against the hybrid reference on what the window served, and the run
    traced by scope."""

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        # nothing has been served yet and the engine's programs take the
        # tree as an argument: server and reference read the same one
        self.params = seeded_hybrid_params(self.params, seed)
        self.server.engine.params = self.params
        # {request id: (slot, prompt, served tokens)} of what has ended
        self.served: dict = {}
        slots: dict = {}
        engine = self.server.engine
        on_token, on_finish = engine.on_token, engine.on_finish

        def token_hook(req, tokens):
            slots.setdefault(req.request_id, req.slot)
            on_token(req, tokens)

        def finish_hook(req):
            self.served[req.request_id] = (
                slots.pop(req.request_id, None), list(req.prompt_tokens),
                list(req.generated_tokens))
            on_finish(req)

        engine.on_token, engine.on_finish = token_hook, finish_hook

    def check_against_reference(self, seed: int, config: dict | None = None
                                ) -> dict:
        """Nothing before the window: ``run`` holds the check on requests
        the window finished (``window_sample``). Not correct until then."""
        return {"ok": False, "pending": "held on the window's requests"}

    def window_sample(self, raw: dict) -> list:
        """[(slot, prompt, served)] of CHECK_REQUESTS requests that began
        and ended inside the window, in the order they ended, one a slot
        before a second of any slot."""
        ended = [self.served[r["id"]] for r in sorted(
            (r for r in facts.window_requests(raw)
             if not facts.failed(r) and r["id"] in self.served),
            key=lambda r: r["done"])]
        first: dict = {}
        for s in ended:
            first.setdefault(s[0], s)
        rest = [s for s in ended if first[s[0]] is not s]
        return (list(first.values()) + rest)[:CHECK_REQUESTS]

    def check_served(self, sample: list, wrong: str | None = None,
                     detail: bool = False) -> dict:
        """Hold served tokens to the plain reference: each request's prompt
        and its first CHECK_NEW_TOKENS served tokens teacher-forced through
        ``hybrid_decoder.logits``, every served token's reference logit
        held to the reference's largest: tokens at a routing near-tie are
        left out (``ROUTER_TIE_MARGIN``), and ``CHECK_MAY_MISS`` of the
        rest may lie further down than the tolerance. ``wrong`` gives the
        reference a fault (see the reference): how one shows that the check
        fails when it should. ``detail`` adds every token's gap and
        margin."""
        gaps, margins, std_sum = [], [], 0.0
        engine = self.server.engine
        for _, prompt, served in sample:
            served = served[:CHECK_NEW_TOKENS]
            n = len(served)
            lg, margin = hybrid_decoder.logits(
                self.params, prompt + served[:-1], self.config,
                positions=range(len(prompt) - 1, len(prompt) - 1 + n),
                wrong=wrong, prompt_len=len(prompt),
                pad_to=engine._bucket(len(prompt)), with_margin=True,
                round_to=CHECK_ROUND_TO)
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
        out = {"ok": bool(len(kept) >= CHECK_MIN_KEPT * len(gaps)
                          and missed <= CHECK_MAY_MISS * len(kept)),
               "tokens_under_tol": missed, "may_miss": CHECK_MAY_MISS,
               "worst_gap": max(kept, default=0.0), "tol": tol,
               "logit_std": std, "requests": len(sample),
               "slots": len({s[0] for s in sample}), "tokens": len(gaps),
               "tokens_kept": len(kept),
               "tokens_off_the_reference_argmax": sum(g > 0 for g in kept)}
        if detail:
            out.update(gaps=gaps, margins=margins)
        return out

    def drive(self, *args, **kwargs) -> dict:
        """``serve.Served.drive`` with this file's ``Trace`` where it makes
        a ``harness.Trace`` (the one seam that needs no edit to a file the
        benchmark has)."""
        plain = harness.Trace
        harness.Trace = Trace
        try:
            return super().drive(*args, **kwargs)
        finally:
            harness.Trace = plain


def require_hybrid_support(config: dict) -> None:
    """Leave at once, with a reason, where the program under test cannot
    build this configuration: a commit from before the layer table was read
    loads it as a uniform stack of attention-then-feed-forward layers with
    rope and all experts its own, and would be measured as something it is
    not."""
    schema = import_module(f"{harness.PKG}.config.schema")
    try:
        model = schema.ModelConfig.from_dict(harness.model_dict(config))
    except Exception as e:
        raise SystemExit(f"benchmark/runners/hybrid.py: this program cannot "
                         f"read {config['name']}: {e}")
    moe = model.moe
    built = (getattr(model, "layer_pattern", ""),
             getattr(getattr(model, "ssm", None), "num_heads", 0),
             moe.num_experts, getattr(moe, "router_experts", 0),
             getattr(model, "position_embedding", "rope"))
    wanted = (config["hybrid_override_pattern"], config["mamba_num_heads"],
              config["n_routed_experts"], config["router_experts"],
              config["position_embedding"])
    if built != wanted:
        raise SystemExit(
            f"benchmark/runners/hybrid.py: this program builds "
            f"{config['name']} with (layer table, state-space heads, experts "
            f"held, router width, position embedding) = {built}, the "
            f"configuration says {wanted}: it cannot run this cell")


def run(cell: dict, config: dict, traffic_path: str, seed: int,
        seconds: float, trace: bool, t_process_start: float,
        require_tpu: bool = True) -> dict:
    """One run of a hybrid serving cell; ``runners/serve.py run`` with the
    traffic file's kind handed on as the generators know it."""
    require_hybrid_support(config)
    device = harness.start(cell["chips"], require_tpu)
    harness.mark("imports and device", t_process_start)
    traffic = traffic_mod.load(traffic_path)
    traffic["kind"] = "serve-" + traffic["kind"].split("-", 1)[1]
    served = Served(config, seed)
    harness.mark(f"weights ({served.init_s:.1f}s) and server up",
                 t_process_start)
    try:
        with harness.scratch_dir("bench_hybrid_traffic_") as tmp:
            path = os.path.join(tmp, os.path.basename(traffic_path))
            with open(path, "w") as f:
                json.dump(traffic, f)
            raw = serve.measure(served, cell, path, seed, seconds, trace,
                                t_process_start, device)
        raw["check"] = served.check_served(served.window_sample(raw))
        print(f"[bench] reference check on the window's requests "
              f"{raw['check']}", file=sys.stderr)
        harness.mark("reference check on the window's requests",
                     t_process_start)
        if raw["trace"].get("op_s"):
            # after the window, and in a traced run alone: the programs'
            # texts cost a compile each (read back from the compile cache)
            raw["trace"]["scope_s"] = scope_seconds(
                raw["trace"]["op_s"], served.server.engine.program_texts())
            harness.mark("scopes of the traced operations", t_process_start)
        return raw
    finally:
        served.close()
