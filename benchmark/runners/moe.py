"""The runner of a sparse-expert serving cell (traffic ``kind``
``moe-closed``): the serving runner as it is
(``runners/serve.py``: the same server, hooks, warm-up, load generator and
window), with the correctness check held against the plain MoE reference
(``reference/moe_decoder.py``) where the dense one stands.

``run.py`` picks a runner by the traffic kind's first word, which is the
only way a new FILE can choose the reference; the traffic generator and the
load generator know ``serve-open`` / ``serve-closed`` alone, so they are
handed a copy of the traffic file with the kind's first word set back to
``serve``. (PERF.md 7: let a configuration name its reference module, and
this detour can go.) ``run["kind"]`` stays ``"serve"``: ``run.py`` and
``facts.py`` read the run as any serving run.
"""

from __future__ import annotations

import json
import os
from importlib import import_module

import numpy as np

from benchmark import harness, traffic as traffic_mod
from benchmark.reference import moe_decoder
from benchmark.runners import serve

# The form of runners/serve.py's check: a served token's reference logit
# may lie this many reference-logit standard deviations under the
# reference's largest. The dense check allows 0.25 std, three times the
# worst bfloat16 near-tie seen at Mistral's widths. The same rule on this
# model's own readings (my chip runs, PR 27; PERF.md 6): the right model's
# worst token lay 0.038 std down over 11 seeds before the q/k scales below
# were seeded and 0.022 over 25 seeds with them (logit std 0.89), and
# three times the larger is 0.12.
# What it separates (calls 2 and 6, worst token of 64 in std, 3 seeds
# each): the reference WITHOUT the q/k norms 0.24 / 0.35 / 0.45; the
# reference with every matmul operand rounded to float8, the nearest
# precision under the configuration's bfloat16, 0.20 - 0.95 over 6 seeds;
# the reference given renormalised top-8 weights 0.31 - 0.68 on 5 seeds of
# 6 and 0.08 on the sixth (a greedy reply over random weights sometimes
# settles where one token leads by a wide margin, and nothing moves it).
# What NO tolerance of this form separates: the engine put on training's
# capacity route (a fifth of the experts overflow in a full decode step)
# reads 0.00 - 0.08 whether its prompts are served alone or in the last
# slots of a full batch: where a dropped expert does not change the argmax
# a token check reads 0, and the server returns tokens, not logits.
# Dropless routing is held on LOGITS instead: tests/test_olmoe.py (1e-4,
# the capacity route asserted to fail), chip_smoke.py (the layer against
# float32 on the chip).
CHECK_TOLERANCE_STD = 0.12
# half-width of the seeded q/k-norm scales (the program's ``1 + scale``)
QK_SCALE_SPREAD = 0.5


def seeded_qk_scales(params: dict, seed: int) -> dict:
    """The parameter tree with seeded non-zero scales on the q/k norms.
    A trained OLMoE's are learned; ``gpt.init`` leaves them 0 (a plain
    RMS norm), and at a seeded init the projections' RMS is already near
    1, so the norm would be nearly the identity and a server WITHOUT it
    would pass the check. A per-channel ``1 + U(-0.5, 0.5)`` on q and on k
    reweighs every attention logit, which a server or a reference that
    leaves the norm out cannot follow."""
    import jax
    import jax.numpy as jnp
    blocks = dict(params["blocks"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 27)
    for i, name in enumerate(("q_norm", "k_norm")):
        if name in blocks:
            scale = blocks[name]["scale"]
            blocks[name] = {"scale": jax.random.uniform(
                jax.random.fold_in(key, i), scale.shape, jnp.float32,
                -QK_SCALE_SPREAD, QK_SCALE_SPREAD).astype(scale.dtype)}
    return dict(params, blocks=blocks)


class Served(serve.Served):
    """``serve.Served`` with the q/k norms made visible and the check held
    against the MoE reference."""

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        # nothing has been served yet and the engine's programs take the
        # tree as an argument: server and reference read the same one
        self.params = seeded_qk_scales(self.params, seed)
        self.server.engine.params = self.params

    def check_against_reference(self, seed: int, config: dict | None = None
                                ) -> dict:
        """``serve.Served.check_against_reference`` with
        ``moe_decoder.logits`` where ``dense_decoder.logits`` stands and
        this file's tolerance: the same seeded prompts served greedily,
        prompt and served tokens teacher-forced through the plain
        reference, every served token's reference logit held to the
        reference's largest."""
        rng = np.random.default_rng([seed, 1])
        vocab = self.model_cfg.vocab_size
        gaps, std_sum, early = [], 0.0, 0
        for _ in range(serve.CHECK_PROMPTS):
            prompt = rng.integers(258, vocab,
                                  serve.CHECK_PROMPT_TOKENS).tolist()
            out = serve._post(self.url, {"prompt": prompt,
                                         "temperature": 0.0,
                                         "max_tokens": serve.CHECK_NEW_TOKENS})
            served = out["choices"][0]["token_ids"]
            early += len(served) < serve.CHECK_NEW_TOKENS
            n = len(served)
            lg = np.asarray(moe_decoder.logits(
                self.params, prompt + served[:-1], config or self.config,
                positions=range(len(prompt) - 1, len(prompt) - 1 + n)))
            gaps.extend((lg.max(-1) - lg[np.arange(n), served]).tolist())
            std_sum += float(lg.std())
        std = std_sum / serve.CHECK_PROMPTS
        tol = CHECK_TOLERANCE_STD * std
        return {"ok": bool(max(gaps) <= tol), "worst_gap": max(gaps),
                "tol": tol, "logit_std": std, "stopped_early": early,
                "tokens": len(gaps),
                "tokens_off_the_reference_argmax": sum(g > 0 for g in gaps)}


def require_moe_support(config: dict) -> None:
    """Leave at once, with a reason, where the program under test cannot
    build this configuration: a commit from before the published MoE keys
    were read loads it as a DENSE model with a 1024-wide feed-forward and
    no q/k norms, and would be measured as something it is not."""
    schema = import_module(f"{harness.PKG}.config.schema")
    model = schema.ModelConfig.from_dict(harness.model_dict(config))
    built = (model.moe.num_experts if model.is_moe else 0,
             getattr(model, "qk_norm", "none"))
    wanted = (config["num_experts"], config.get("qk_norm", "none"))
    if built != wanted:
        raise SystemExit(
            f"benchmark/runners/moe.py: this program builds "
            f"{config['name']} with (experts, qk_norm) = {built}, the "
            f"configuration says {wanted}: it cannot run this cell")


def run(cell: dict, config: dict, traffic_path: str, seed: int,
        seconds: float, trace: bool, t_process_start: float,
        require_tpu: bool = True) -> dict:
    """One run of an MoE serving cell; ``runners/serve.py run`` with the
    traffic file's kind handed on as the generators know it."""
    require_moe_support(config)
    device = harness.start(cell["chips"], require_tpu)
    harness.mark("imports and device", t_process_start)
    traffic = traffic_mod.load(traffic_path)
    traffic["kind"] = "serve-" + traffic["kind"].split("-", 1)[1]
    served = Served(config, seed)
    harness.mark(f"weights ({served.init_s:.1f}s) and server up",
                 t_process_start)
    try:
        with harness.scratch_dir("bench_moe_traffic_") as tmp:
            path = os.path.join(tmp, os.path.basename(traffic_path))
            with open(path, "w") as f:
                json.dump(traffic, f)
            return serve.measure(served, cell, path, seed, seconds, trace,
                                 t_process_start, device)
    finally:
        served.close()
