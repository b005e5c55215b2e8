"""The runner of a serving cell whose model walks ONE stack of
sandwich-normed layers several times over one set of weights, K and V kept
per (pass, layer) (traffic ``kind`` ``looped-closed``; ``model_type:
ouro``): the serving runner as it is (``runners/serve.py``: the same server,
hooks, warm-up, load generator and window), with

- weights whose trivial vectors are seeded NON-trivially (every norm's
  scale, all four a layer and the final one: a unit scale hides a missing
  norm; the exit gate's bias, beside the kernel the program's init seeds);
- the correctness check held against the plain reference
  (``reference/looped_decoder.py``) on tokens the WINDOW served (requests
  that began and ended inside it, each from another slot, teacher-forced
  after it closes, the engine stopped and its pools freed first: the float32
  reference runs beside 5.3 GB of weights and does not fit beside 8 GB of
  pool), AND on the route every attention program took: a run on the gather
  path is not correct, whatever its tokens (``attention_impls``);
- a trace by scope (``runners/parallel.py``'s reduction, every scope an
  operation lies under counted) over all programs (``scope_s``) and over the
  decode program alone (``decode_scope_s``): ``loop_pass`` is one pass of
  the stack, ``exit_gate`` what closes it.

``run.py`` picks a runner by the traffic kind's first word; the traffic and
load generators know ``serve-open`` / ``serve-closed`` alone, so they are
handed a copy of the traffic file with the kind's first word set back to
``serve``. ``run["kind"]`` stays ``"serve"``.
"""

from __future__ import annotations

import sys
from importlib import import_module

import numpy as np

from benchmark import harness
from benchmark.reference import looped_decoder
from benchmark.runners import hybrid, parallel, shortconv

# ``runners/serve.py``'s form: a served token's reference logit may lie
# CHECK_TOLERANCE_STD reference-logit standard deviations under the
# reference's largest, and CHECK_MAY_MISS of the tokens may lie further
# down. What is held are 12 of the requests that began and ended inside the
# window (``hybrid.Served.window_sample``: each from another slot), the first
# CHECK_NEW_TOKENS of each: 192 tokens a run.
# Both limits are set from readings on the chip (my chip runs, PR 60, call 1;
# ``experiments/looped_check_readings.py``; PERF.md 6 has the table). The
# engine computes in bfloat16 through 4 x 48 layer applications, each
# sub-layer's output normed to unit size before the residual takes it (a
# rounding is never small beside what it is added to) and every pass
# starting from a NORMED state: the RIGHT model's served tokens lie 0.13
# std under the reference's largest in the mean, 47-54 % of them off its
# argmax (the two largest of 49,152 logits lie ~0.2 std apart), 20-23 %
# further down than 0.25 std; further down than 0.5 std: 2.6 to 19.3 % in
# twelve runs of twelve seeds (calls 1, 4 and 5; the mean 8 %; worst gap
# 0.6-1.2 std). Tokens further down than 0.5 std under each wrong reference
# (two seeds): one pass fewer 99.0 and 99.5 % (mean gap 2.6 std), the first
# pass's K/V plane in every pass, no second norm of a pair, the un-normed
# state handed on and float8 operands in every matmul (the nearest precision
# under the configuration's bfloat16) 100 % each (mean gaps 3.5-4.5 std: a
# token of another function is a draw from the vocabulary, ~4 std down).
# 0.5 std and 45 % lie between the right model's largest, 19.3 %, and the
# wrong models' smallest, 99.0 %, with a factor of 2.3 to the one and 2.2 to
# the other.
CHECK_TOLERANCE_STD = 0.5
CHECK_MAY_MISS = 0.45
CHECK_NEW_TOKENS = 16
# the reference compiles one shape a multiple of this many tokens (prompts
# end at 512 and 15 served tokens follow: at most three shapes)
CHECK_ROUND_TO = 192

# The names a device trace shows this model's work under: Pallas kernels by
# the name the program gives them, XLA operations by the named scopes they
# were traced in (``models/gpt.py``, ``serve/decode.py``). An operation
# counts under EVERY scope it lies in.
SCOPES = ("loop_pass", "exit_gate", "paged_attention_mq", "paged_attention",
          "kv_page_write", "lm_head", "sampler")


def seeded_looped_params(params: dict, seed: int) -> dict:
    """The parameter tree with the vectors a seeded init leaves trivial made
    visible: ``gpt.init`` gives every norm's scale 0 (a plain RMS norm) and
    the gate's bias 0. Seeded here: each scale (the program's ``1 +
    scale``) in U(-0.3, 0.3), the bias in U(-0.5, 0.5). (At the published
    threshold 1 no token's logits depend on the gate: it is held on the
    CPU, tests/test_ouro.py, and by name in the engine's ``loop`` group.)"""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 60)
    count = iter(range(1 << 16))

    def uniform(like, lo, hi):
        return jax.random.uniform(jax.random.fold_in(key, next(count)),
                                  like.shape, jnp.float32, lo, hi
                                  ).astype(like.dtype)

    def visible(path, leaf):
        names = tuple(k.key for k in path)
        if names[-1] == "scale":
            return uniform(leaf, -0.3, 0.3)
        if names == ("exit_gate", "bias"):
            return uniform(leaf, -0.5, 0.5)
        return leaf
    return jax.tree_util.tree_map_with_path(visible, params)


class Served(hybrid.Served):
    """``serve.Served`` on seeded non-trivial weights, with the check held
    against the looped reference on what the window served, and the run
    traced by scope. (``hybrid.Served`` gives the hooks that keep what each
    request was served, the window's sample and the trace's seam; its
    seeding finds neither a state-space layer nor an expert to touch.)"""

    # (False in the tests' rehearsal on the CPU, where the kernel is the
    # gather baseline by construction)
    require_streaming = True

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        # nothing has been served yet and the engine's programs take the
        # tree as an argument: server and reference read the same one
        self.params = seeded_looped_params(self.params, seed)
        self.server.engine.params = self.params
        # (read now: the pools are deleted before the check runs)
        self.pool_shape = tuple(self.server.engine.kv.k_pages.shape)

    def release_pools(self) -> None:
        """Stop the engine thread and give the K/V pools' memory back before
        the float32 reference runs. (The thread first: a closed loop's
        callers leave requests in flight, and a dispatch over a deleted
        pool makes the engine allocate a new one.)"""
        self.server.stop_engine()
        kv = self.server.engine.kv
        for pool in (kv.k_pages, kv.v_pages):
            pool.delete()

    def check_served(self, sample: list, wrong: str | None = None,
                     detail: bool = False) -> dict:
        """Hold served tokens to the plain reference: each request's prompt
        and its first CHECK_NEW_TOKENS served tokens teacher-forced through
        ``looped_decoder.logits``, every served token's reference logit
        held to the reference's largest (``CHECK_MAY_MISS`` of them may lie
        further down than the tolerance); the pool must have one plane a
        (pass, layer); and every attention program of the run must have
        taken the page-streaming kernel. ``wrong`` gives the reference a
        fault (see the reference): how one shows that the check fails when
        it should. ``detail`` adds every token's gap."""
        gaps, std_sum = [], 0.0
        for _, prompt, served in sample:
            served = served[:CHECK_NEW_TOKENS]
            n = len(served)
            lg = np.asarray(looped_decoder.logits(
                self.params, prompt + served[:-1], self.config,
                positions=range(len(prompt) - 1, len(prompt) - 1 + n),
                wrong=wrong, round_to=CHECK_ROUND_TO))
            gaps.extend((lg.max(-1) - lg[np.arange(n), served]).tolist())
            std_sum += float(lg.std())
        if not gaps:
            return {"ok": False, "requests": 0, "tokens": 0}
        std = std_sum / len(sample)
        tol = CHECK_TOLERANCE_STD * std
        missed = sum(g > tol for g in gaps)
        impls = shortconv.attention_impls()
        streamed = bool(impls) and all(impl == "pallas" for _, impl in impls)
        planes = (self.config["total_ut_steps"]
                  * self.config["num_hidden_layers"])
        out = {"ok": bool(missed <= CHECK_MAY_MISS * len(gaps)
                          and self.pool_shape[0] == planes
                          and (streamed or not self.require_streaming)),
               "attention_impls": [f"{op}={impl}" for op, impl in impls],
               "pool_shape": list(self.pool_shape),
               "tokens_under_tol": missed, "may_miss": CHECK_MAY_MISS,
               "worst_gap": max(gaps), "mean_gap": float(np.mean(gaps)),
               "tol": tol, "logit_std": std, "requests": len(sample),
               "slots": len({s[0] for s in sample}), "tokens": len(gaps),
               "tokens_off_the_reference_argmax": sum(g > 0 for g in gaps)}
        if detail:
            out["gaps"] = gaps
        return out


def require_looped_support(config: dict) -> None:
    """Leave at once, with a reason, where the program under test cannot
    build this configuration: a commit from before ``total_ut_steps`` was
    read loads it as a plain pre-norm stack walked ONCE (another model, a
    quarter of the work), and would be measured as something it is not."""
    schema = import_module(f"{harness.PKG}.config.schema")
    try:
        model = schema.ModelConfig.from_dict(harness.model_dict(config))
    except Exception as e:
        raise SystemExit(f"benchmark/runners/looped.py: this program "
                         f"cannot read {config['name']}: {e}")
    planes = config["total_ut_steps"] * config["num_hidden_layers"]
    built = (getattr(model, "num_passes", 1),
             getattr(model, "sandwich_norm", False),
             getattr(model, "kv_layers", model.num_layers))
    wanted = (config["total_ut_steps"], True, planes)
    if built != wanted:
        raise SystemExit(
            f"benchmark/runners/looped.py: this program builds "
            f"{config['name']} with (passes, sandwich norms, planes of the "
            f"K/V pool) = {built}, the configuration says {wanted}: it "
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
    """One run of a looped serving cell; ``runners/serve.py run`` with the
    traffic file's kind handed on as the generators know it, and the check
    held on the window's requests."""
    require_looped_support(config)
    device = harness.start(cell["chips"], require_tpu)
    harness.mark("imports and device", t_process_start)
    served = Served(config, seed)
    served.require_streaming = require_tpu
    harness.mark(f"weights ({served.init_s:.1f}s) and server up",
                 t_process_start)
    try:
        raw, sample = window(served, cell, traffic_path, seed, seconds,
                             trace, t_process_start, device)
        raw["check"] = served.check_served(sample)
        # (beside the check, not part of it: the pool must not preempt)
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
