"""The runner of a serving cell whose model mixes by gated short
convolutions and, one layer in four, attention with heads of 64, over
dense and sparse-expert feed-forwards (traffic ``kind``
``shortconv-closed``; ``model_type: lfm2_moe``): the serving runner as it
is (``runners/serve.py``: the same server, hooks, warm-up, load generator
and window), with

- the configuration's ``layer_types`` kept (``harness.model_dict`` keeps
  scalars alone);
- weights whose trivial vectors are seeded NON-trivially (every norm's
  scale, the q / k head norms' among them, and the experts' selection
  bias: a unit norm or a zero bias hides behind its own absence; the conv's
  taps are uniform a tap by the program's own init, so asymmetric);
- the correctness check held against the plain reference
  (``reference/shortconv_decoder.py``) on tokens the WINDOW served (requests
  that began and ended inside it, from many slots, teacher-forced after it
  closes, the engine stopped and its pools freed first: the float32
  reference runs beside 10.8 GB of weights), AND on the route every
  attention program took: a run on the gather path is not correct, whatever
  its tokens (``attention_impls``);
- a trace by scope (``runners/parallel.py``'s reduction, every scope an
  operation lies under counted: ``shortconv_mixer/shortconv_step`` is the
  mixer's time and the window step's) over all programs (``scope_s``) and
  over the decode program alone (``decode_scope_s``).

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
from benchmark.reference import shortconv_decoder
from benchmark.runners import hybrid, parallel

# The form of runners/hybrid.py's check: a served token's reference logit
# may lie CHECK_TOLERANCE_STD reference-logit standard deviations under the
# reference's largest; a token at a routing near-tie (the reference's 4th
# and 5th biased scores, in any of the 14 expert layers at the token's
# position, closer than ROUTER_TIE_MARGIN) is left out, since either set of
# experts is right there (33-40 % of the sampled tokens are kept; fewer
# than CHECK_MIN_KEPT is itself not correct); CHECK_MAY_MISS of the kept
# tokens may lie further down: a pick that flipped at an EARLIER position
# reaches a kept token through the attention layers' keys and the conv
# windows, and under a tied head over seeded weights the logits' std is
# 0.9, so a flipped expert moves a near-tie of the top two. What is held
# are 12 of the requests that began and ended inside the window
# (``hybrid.Served.window_sample``: each from another slot), the first
# CHECK_NEW_TOKENS of each.
# Both limits are set from readings on the chip (my chip runs, PR 55, calls
# 1-3; 768 tokens a run, 251-304 kept; ``experiments/
# shortconv_check_readings.py``; PERF.md 6 has the table). Kept tokens
# further down than 0.1 std: the RIGHT model 7.6 to 13.5 % in eleven runs
# of eleven seeds (worst 0.37-1.05 std, mean 0.019-0.037); float8 operands in
# every matmul (the nearest precision under the configuration's bfloat16)
# 61.6 %, B and C swapped 66.4 %, the taps reversed 69.7 %, rope on a conv
# layer's stream 78.1 %, the conv dropped 98.3 %, no rope in attention
# 28.1 %. 25 % lies between the right model's largest (13.5) and float8's
# (61.6) with a factor of two to either. NOT separated, said plainly: the
# expert bias dropped 21.6 %, the q / k head norms dropped 17.4 %, the bias
# used as a weight 9.9 %, a window a former occupant left (it moves a
# sequence's first two positions) 10.4 %: all four are held on LOGITS on
# the CPU (tests/test_lfm2.py, 1e-4), the last also by chip_smoke.py's
# reused slot.
CHECK_TOLERANCE_STD = 0.1
CHECK_NEW_TOKENS = 64
ROUTER_TIE_MARGIN = 0.002
CHECK_MIN_KEPT = 0.15
CHECK_MAY_MISS = 0.25
# the reference compiles one shape a multiple of this many tokens (prompts
# end at 1,024 and 64 served tokens follow: at most three shapes)
CHECK_ROUND_TO = 384

# The names a device trace shows this model's work under: Pallas kernels by
# the name the program gives them, XLA operations by the named scopes they
# were traced in (``models/layers.py``, ``ops/shortconv.py``,
# ``models/gpt.py``, ``serve/decode.py``). An operation counts under EVERY
# scope it lies in.
SCOPES = ("shortconv_mixer", "shortconv_step", "shortconv_conv",
          "moe_gmm_prefill", "moe_gmm", "moe_router", "moe_dispatch",
          "moe_combine", "paged_attention_mq", "paged_attention",
          "kv_page_write", "dense_mlp", "lm_head", "sampler")

_plain_model_dict = harness.model_dict


def model_dict(config: dict) -> dict:
    """``harness.model_dict`` with the layers' kinds kept."""
    return dict(_plain_model_dict(config), layer_types=config["layer_types"])


def seeded_shortconv_params(params: dict, seed: int) -> dict:
    """The parameter tree with the vectors a seeded init leaves trivial made
    visible: ``gpt.init`` gives every norm's scale 0 (a plain RMS norm; the
    q / k head norms too) and the experts' selection bias 0. Seeded here:
    each scale (the program's ``1 + scale``) in U(-0.3, 0.3), the bias in
    U(-0.01, 0.01): among 32 sigmoid scores a few hundredths apart it
    changes WHICH experts are picked and adds little skew of its own (the
    hybrid cell's finding, PERF.md 6, PR 31)."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 55)
    count = iter(range(1 << 16))

    def uniform(like, lo, hi):
        return jax.random.uniform(jax.random.fold_in(key, next(count)),
                                  like.shape, jnp.float32, lo, hi
                                  ).astype(like.dtype)

    def visible(path, leaf):
        names = tuple(k.key for k in path)
        if names[-1] == "scale":
            return uniform(leaf, -0.3, 0.3)
        if names[-2:] == ("router", "bias"):
            return uniform(leaf, -0.01, 0.01)
        return leaf
    return jax.tree_util.tree_map_with_path(visible, params)


def attention_impls() -> list:
    """[(op, implementation)] of every attention program this process has
    traced (``utils/platform.py report_impl``; [] where the program under
    test keeps no such record)."""
    platform = import_module(f"{harness.PKG}.utils.platform")
    reported = getattr(platform, "reported_impls", lambda: ())()
    return sorted({(op, impl) for op, impl, _ in reported
                   if op.startswith("paged_attention")})


class Served(hybrid.Served):
    """``serve.Served`` on seeded non-trivial weights, with the check held
    against the short-conv reference on what the window served, and the run
    traced by scope. (``hybrid.Served`` gives the hooks that keep what each
    request was served, the window's sample and the trace's seam; its
    seeding finds neither an ``ssm`` nor a zero bias of 128 experts to
    touch and is followed by this cell's own.)"""

    def __init__(self, config: dict, seed: int):
        harness.model_dict = model_dict
        try:
            super().__init__(config, seed)
        finally:
            harness.model_dict = _plain_model_dict
        # nothing has been served yet and the engine's programs take the
        # tree as an argument: server and reference read the same one
        self.params = seeded_shortconv_params(self.params, seed)
        self.server.engine.params = self.params

    def release_pools(self) -> None:
        """Stop the engine thread and give the K/V and conv pools' memory
        back before the float32 reference runs (``parallel.Served``'s, which
        says why the thread goes first)."""
        parallel.Served.release_pools(self)

    def check_served(self, sample: list, wrong: str | None = None,
                     detail: bool = False) -> dict:
        """Hold served tokens to the plain reference: each request's prompt
        and its first CHECK_NEW_TOKENS served tokens teacher-forced through
        ``shortconv_decoder.logits``, every served token's reference logit
        held to the reference's largest; tokens at a routing near-tie are
        left out and ``CHECK_MAY_MISS`` of the rest may lie further down
        than the tolerance; and every attention program of the run must
        have taken the page-streaming kernel. ``wrong`` gives the reference
        a fault (see the reference): how one shows that the check fails
        when it should. ``detail`` adds every token's gap and margin."""
        gaps, margins, std_sum = [], [], 0.0
        for _, prompt, served in sample:
            served = served[:CHECK_NEW_TOKENS]
            n = len(served)
            lg, margin = shortconv_decoder.logits(
                self.params, prompt + served[:-1], self.config,
                positions=range(len(prompt) - 1, len(prompt) - 1 + n),
                wrong=wrong, with_margin=True, round_to=CHECK_ROUND_TO)
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
        impls = attention_impls()
        streamed = bool(impls) and all(
            impl == "pallas" for _, impl in impls)
        out = {"ok": bool(len(kept) >= CHECK_MIN_KEPT * len(gaps)
                          and missed <= CHECK_MAY_MISS * len(kept)
                          and (streamed or not self.require_streaming)),
               "attention_impls": [f"{op}={impl}" for op, impl in impls],
               "tokens_under_tol": missed, "may_miss": CHECK_MAY_MISS,
               "worst_gap": max(kept, default=0.0),
               "mean_gap": float(np.mean(kept)) if kept else 0.0,
               "tol": tol, "logit_std": std, "requests": len(sample),
               "slots": len({s[0] for s in sample}), "tokens": len(gaps),
               "tokens_kept": len(kept),
               "tokens_off_the_reference_argmax": sum(g > 0 for g in kept)}
        if detail:
            out.update(gaps=gaps, margins=margins)
        return out

    # (False in the tests' rehearsal on the CPU, where the kernel is the
    # gather baseline by construction)
    require_streaming = True


def require_shortconv_support(config: dict) -> None:
    """Leave at once, with a reason, where the program under test cannot
    build this configuration: a commit from before the ``lfm2_moe`` keys
    were read loads it as a uniform stack of attention-then-experts layers
    with softmax routing and no convolution, and would be measured as
    something it is not."""
    schema = import_module(f"{harness.PKG}.config.schema")
    try:
        model = schema.ModelConfig.from_dict(model_dict(config))
    except Exception as e:
        raise SystemExit(f"benchmark/runners/shortconv.py: this program "
                         f"cannot read {config['name']}: {e}")
    letters = {"conv": "C", "full_attention": "*"}
    wanted = ("".join(
        letters[t] + ("D" if i < config["num_dense_layers"] else "E")
        for i, t in enumerate(config["layer_types"])),
        config["conv_L_cache"], "sigmoid", "head")
    built = (getattr(model, "layer_pattern", ""),
             getattr(model, "shortconv_kernel", 0),
             getattr(model.moe, "router_score", "softmax"),
             getattr(model, "qk_norm", "none"))
    if built != wanted:
        raise SystemExit(
            f"benchmark/runners/shortconv.py: this program builds "
            f"{config['name']} with (layer table, conv taps, router score, "
            f"q/k norm) = {built}, the configuration says {wanted}: it "
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
    """One run of a short-conv serving cell; ``runners/serve.py run`` with
    the traffic file's kind handed on as the generators know it, and the
    check held on the window's requests."""
    require_shortconv_support(config)
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
        print(f"[bench] reference check on the window's requests "
              f"{raw['check']}", file=sys.stderr)
        harness.mark("reference check on the window's requests",
                     t_process_start)
        return raw
    finally:
        served.close()
