"""The runner of a serving cell whose model runs attention AND a Mamba-2
mixer in every layer (traffic ``kind`` ``parallel-closed``): the serving
runner as it is (``runners/serve.py``: the same server, hooks, warm-up,
load generator and window), with

- the configuration's list-valued keys kept (``ssm_multipliers``,
  ``mlp_multipliers``: ``harness.model_dict`` keeps scalars alone);
- weights whose trivial vectors are seeded NON-trivially (the skip ``D``,
  the gated norm's scale: a dropped ``D`` or a unit norm hides behind its
  own absence). The kernels' scales are the program's own seeded init for
  a model with muP multipliers (``models/gpt.py _mup_init_std``): each
  branch adds about half the stream's RMS;
- the correctness check held against the plain reference
  (``reference/parallel_decoder.py``) on tokens the WINDOW served (requests
  that began and ended inside it, from many slots, teacher-forced after it
  closes, the engine stopped and its pools freed first: the float32
  reference runs beside 8.8 GB of weights);
- a trace by scope (``runners/hybrid.py``'s reduction of the profile by
  program and operation), with EVERY scope an operation lies under counted
  (``parallel_ssm/ssm_decode`` is the branch's time and the recurrence's),
  over all programs (``scope_s``) and over the decode program alone
  (``decode_scope_s``).

``run.py`` picks a runner by the traffic kind's first word; the traffic and
load generators know ``serve-open`` / ``serve-closed`` alone, so they are
handed a copy of the traffic file with the kind's first word set back to
``serve``. ``run["kind"]`` stays ``"serve"``.
"""

from __future__ import annotations

import json
import os
import re
import sys
from collections import defaultdict
from importlib import import_module

import numpy as np

from benchmark import facts, harness, trace_reduce, traffic as traffic_mod
from benchmark.reference import parallel_decoder
from benchmark.runners import hybrid, serve

# The form of runners/serve.py's check: a served token's reference logit may
# lie CHECK_TOLERANCE_STD reference-logit standard deviations under the
# reference's largest, and CHECK_MAY_MISS of the tokens may lie further
# down. What is held are tokens the WINDOW served: 12 of the requests that
# began and ended inside it (``hybrid.Served.window_sample``: each from
# another slot), the first CHECK_NEW_TOKENS of each teacher-forced through
# the reference after the window closes.
# Both limits are set from readings on the chip (my chip runs, PR 49, calls
# 1-2; 512-768 tokens a run; ``experiments/parallel_check_readings.py``). The
# RIGHT model: the worst of its tokens lies 0.022-0.026 std down in four
# runs (a dense model: no routing ties; bfloat16 rounds the stream and the
# reference does not), none past 0.05. Tokens further down than 0.1 std:
# float8 operands in every matmul (the nearest precision under the
# configuration's bfloat16) 10.5 %, the gate moved behind the norm 15.0 %,
# ``ssm_multipliers[0]`` set to 1 23.6 %, one group for two 36.5 %, B and C
# swapped 45.1 %, ``ssm_multipliers[3]`` 46.1 %, no rope 66.4 %, a dropped
# attention branch 73.8 %, every other dropped branch, skip or multiplier
# 79.5-100 %. 0.1 std is four times the right model's worst; 3 % lies
# between the right model's 0 and float8's 10.5 with a factor of three to
# the nearer. NOT seen by any token check, said plainly: ``lm_head_multiplier``
# (it scales every logit: the argmax and the gaps in std are the same),
# ``attention_in_multiplier`` (it IS 1), ``key_multiplier`` on q for k (the
# scores are bilinear), all three held on the CPU (tests/test_falcon_h1.py:
# logits, and the key rows the pages keep); and a bfloat16 STATE (mean gap
# 0.00027 std against the right model's 0.00024): the state pool's dtype is
# held by name instead (``state_dtype`` must be float32).
CHECK_TOLERANCE_STD = 0.1
CHECK_NEW_TOKENS = 64
CHECK_MAY_MISS = 0.03
# the reference compiles one shape a multiple of this many tokens (prompts
# end at 2,048 and 64 served tokens follow: at most three shapes)
CHECK_ROUND_TO = 768

# The names a device trace shows this model's work under: Pallas kernels by
# the name the program gives them, XLA operations by the named scopes they
# were traced in (``models/layers.py``, ``ops/ssm.py``, ``models/gpt.py``,
# ``serve/decode.py``). An operation counts under EVERY scope it lies in.
SCOPES = ("parallel_attention", "parallel_ssm", "ssm_in_proj", "ssm_out_proj",
          "ssm_scan_prefill", "ssm_gated_norm", "ssm_decode", "ssm_conv",
          "paged_attention_mq", "paged_attention", "kv_page_write",
          "dense_mlp", "lm_head", "sampler")

_plain_model_dict = harness.model_dict


def model_dict(config: dict) -> dict:
    """``harness.model_dict`` with the two list-valued multipliers kept."""
    return dict(_plain_model_dict(config),
                ssm_multipliers=config["ssm_multipliers"],
                mlp_multipliers=config["mlp_multipliers"])


def scopes_of(texts) -> set:
    """Every one of ``SCOPES`` that any of an event's texts holds as a
    whole word (``paged_attention`` is not in ``paged_attention_mq``)."""
    return {scope for scope in SCOPES for text in texts
            if re.search(rf"(?<![A-Za-z_]){scope}(?![A-Za-z_])", text)}


def scope_seconds(op_s: dict, texts: dict,
                  names: dict = trace_reduce.NAMES) -> dict:
    """{program: {scope: (events, device seconds)}} from
    ``hybrid.op_seconds`` and the engine's ``program_texts()``: a Pallas
    kernel is told by its own name, an XLA operation (``fusion.123``) by
    the scopes its instruction's ``op_name`` holds in the text of the
    program it ran in; both count under every scope they lie in."""
    by_program: dict = defaultdict(dict)
    for name, text in texts.items():
        program = trace_reduce.program_of("jit_" + name.split(" ")[0], names)
        for line in text.splitlines():
            m = hybrid._INSTRUCTION.match(line)
            if m:
                found = scopes_of([m.group(2)])
                if found:
                    by_program[program].setdefault(m.group(1), found)
    out: dict = defaultdict(lambda: defaultdict(lambda: [0, 0.0]))
    for program, ops in op_s.items():
        for name, (n, seconds) in ops.items():
            for scope in scopes_of([name]) | by_program[program].get(
                    name, set()):
                out[program][scope][0] += n
                out[program][scope][1] += seconds
    return {p: {k: tuple(v) for k, v in scopes.items()}
            for p, scopes in out.items()}


def seeded_parallel_params(params: dict, seed: int) -> dict:
    """The parameter tree with the vectors a seeded init leaves trivial
    made visible: ``gpt.init`` gives the skip ``D`` = 1 and the gated
    norm's scale 0 (a plain grouped RMS norm). Seeded here: ``D`` in
    U(0.5, 1.5), the gated norm's scale (the program's ``1 + scale``) in
    U(-0.5, 0.5), as the hybrid cell seeds its own."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 49)

    def uniform(i, like, lo, hi):
        return jax.random.uniform(jax.random.fold_in(key, i), like.shape,
                                  jnp.float32, lo, hi).astype(like.dtype)
    par = dict(params["blocks"]["par"])
    par["D"] = uniform(0, par["D"], 0.5, 1.5)
    par["gate_norm"] = {"scale": uniform(1, par["gate_norm"]["scale"],
                                         -0.5, 0.5)}
    return dict(params, blocks=dict(params["blocks"], par=par))


class Served(hybrid.Served):
    """``serve.Served`` on seeded non-trivial weights, with the check held
    against the parallel reference on what the window served, and the run
    traced by scope. (``hybrid.Served`` gives the hooks that keep what each
    request was served, the window's sample and the trace's seam.)"""

    def __init__(self, config: dict, seed: int):
        harness.model_dict = model_dict
        try:
            super().__init__(config, seed)
        finally:
            harness.model_dict = _plain_model_dict
        # nothing has been served yet and the engine's programs take the
        # tree as an argument: server and reference read the same one
        self.params = seeded_parallel_params(self.params, seed)
        self.server.engine.params = self.params
        # (read now: the pools are deleted before the check runs)
        self.state_dtype = str(self.server.engine.kv.state["ssm"].dtype)

    def release_pools(self) -> None:
        """Stop the engine thread and give the K/V and state pools' memory
        back before the float32 reference runs at the window's context
        lengths: nothing is served after the window. (The thread first: a
        closed loop's callers leave requests in flight, and a dispatch over
        a deleted pool makes the engine allocate a new one.)"""
        self.server.stop_engine()
        kv = self.server.engine.kv
        for pool in (kv.k_pages, kv.v_pages, *kv.state.values()):
            pool.delete()

    def check_served(self, sample: list, wrong: str | None = None,
                     detail: bool = False) -> dict:
        """Hold served tokens to the plain reference: each request's prompt
        and its first CHECK_NEW_TOKENS served tokens teacher-forced through
        ``parallel_decoder.logits``, every served token's reference logit
        held to the reference's largest; ``CHECK_MAY_MISS`` of them may lie
        further down than the tolerance; the recurrent state's pool must
        be float32 by name. ``wrong`` gives the reference a
        fault (see the reference): how one shows that the check fails when
        it should. ``detail`` adds every token's gap."""
        gaps, std_sum = [], 0.0
        for _, prompt, served in sample:
            served = served[:CHECK_NEW_TOKENS]
            n = len(served)
            lg = np.asarray(parallel_decoder.logits(
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
        out = {"ok": bool(missed <= CHECK_MAY_MISS * len(gaps)
                          and self.state_dtype == "float32"),
               "state_dtype": self.state_dtype,
               "tokens_under_tol": missed, "may_miss": CHECK_MAY_MISS,
               "worst_gap": max(gaps), "mean_gap": float(np.mean(gaps)),
               "tol": tol, "logit_std": std, "requests": len(sample),
               "slots": len({s[0] for s in sample}), "tokens": len(gaps),
               "tokens_off_the_reference_argmax": sum(g > 0 for g in gaps)}
        if detail:
            out["gaps"] = gaps
        return out


def require_parallel_support(config: dict) -> None:
    """Leave at once, with a reason, where the program under test cannot
    build this configuration: a commit from before the ``falcon_h1`` keys
    were read loads it as a uniform stack of attention-then-MLP layers
    without a state-space branch or a multiplier, and would be measured as
    something it is not."""
    schema = import_module(f"{harness.PKG}.config.schema")
    try:
        model = schema.ModelConfig.from_dict(model_dict(config))
    except Exception as e:
        raise SystemExit(f"benchmark/runners/parallel.py: this program "
                         f"cannot read {config['name']}: {e}")
    mup = getattr(model, "mup", None)
    built = (getattr(model, "layer_pattern", ""),
             getattr(getattr(model, "ssm", None), "num_heads", 0),
             getattr(mup, "embedding", 1.0), tuple(getattr(mup, "ssm", ())))
    wanted = ("PD" * config["num_hidden_layers"], config["mamba_n_heads"],
              config["embedding_multiplier"],
              tuple(config["ssm_multipliers"]))
    if built != wanted:
        raise SystemExit(
            f"benchmark/runners/parallel.py: this program builds "
            f"{config['name']} with (layer table, state-space heads, "
            f"embedding multiplier, ssm multipliers) = {built}, the "
            f"configuration says {wanted}: it cannot run this cell")


def window(served: Served, cell: dict, traffic_path: str, seed: int,
           seconds: float, trace: bool, t_process_start: float,
           device: dict) -> tuple[dict, list]:
    """Warm and drive a server that is up (``serve.measure``), read the
    traced programs' scopes, then stop the engine and free its pools: (the
    raw run, the window's sample for ``check_served``)."""
    traffic = traffic_mod.load(traffic_path)
    traffic["kind"] = "serve-" + traffic["kind"].split("-", 1)[1]
    with harness.scratch_dir("bench_parallel_traffic_") as tmp:
        path = os.path.join(tmp, os.path.basename(traffic_path))
        with open(path, "w") as f:
            json.dump(traffic, f)
        raw = serve.measure(served, cell, path, seed, seconds, trace,
                            t_process_start, device)
    if raw["trace"].get("op_s"):
        # after the window, and in a traced run alone: the programs' texts
        # cost a compile each (read back from the compile cache), lowered
        # from the live arguments' shapes: before the pools go
        by_program = scope_seconds(raw["trace"]["op_s"],
                                   served.server.engine.program_texts())
        total: dict = defaultdict(lambda: [0, 0.0])
        for scopes in by_program.values():
            for scope, (n, s) in scopes.items():
                total[scope][0] += n
                total[scope][1] += s
        raw["trace"]["scope_s"] = {k: tuple(v) for k, v in total.items()}
        raw["trace"]["decode_scope_s"] = by_program.get("decode", {})
        print(f"[bench] device seconds by scope, decode program "
              f"{raw['trace']['decode_scope_s']}; all programs "
              f"{raw['trace']['scope_s']}", file=sys.stderr)
        harness.mark("scopes of the traced operations", t_process_start)
    sample = served.window_sample(raw)
    served.release_pools()
    return raw, sample


def run(cell: dict, config: dict, traffic_path: str, seed: int,
        seconds: float, trace: bool, t_process_start: float,
        require_tpu: bool = True) -> dict:
    """One run of a parallel serving cell; ``runners/serve.py run`` with
    the traffic file's kind handed on as the generators know it, and the
    check held on the window's requests."""
    require_parallel_support(config)
    device = harness.start(cell["chips"], require_tpu)
    harness.mark("imports and device", t_process_start)
    served = Served(config, seed)
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
