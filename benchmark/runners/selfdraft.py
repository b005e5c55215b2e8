"""The runner of a self-drafting serving cell over resident system prompts
(traffic ``kind`` ``selfdraft-closed``): the latent runner as it is
(``runners/latent.py``: the server, hooks, the load generator's child
``benchmark/loadgen_docqa.py``, the documents loaded in set-up, the window),
with

- a model whose next-token prediction module DRAFTS (``speculative: mtp``):
  every decode step is a draft-and-verify window of two rows a slot, and the
  engine keeps, beside each served token, the draft it verified at that
  position (``Request.draft_tokens``; the traffic asks for them with
  ``return_draft_tokens``);
- the correctness check held against the plain reference
  (``reference/selfdraft_decoder.py``) on what the WINDOW served, tokens AND
  drafts: requests whose reply ended inside it, from different slots and
  system prompts, teacher-forced through main stack and module after the
  pool is given back; a served token is held to the reference's main logits
  at its position, a served draft to the reference MODULE's logits at the
  row that made it, under one tolerance. A program that skipped the module,
  or ran another, cannot be ``correct``;
- weights whose norms' scales (the module's three among them) and selection
  bias are seeded NON-trivially;
- the run judged on the replies that ENDED inside the window
  (``runners/linear.py ended_in_window``), because under this mix a dozen
  requests at most are both sent and ended inside it;
- the run traced by kernel and scope name, every scope an operation lies in
  counted, over all programs and over the decode program alone
  (``runners/parallel.py``'s reduction with this model's scopes).

``run.py`` picks a runner by the traffic kind's first word. ``run["kind"]``
stays ``"serve"``. On a program that cannot serve the module it leaves with
one line and exit 1 before JAX starts.
"""

from __future__ import annotations

import json
import os
import sys
from collections import defaultdict
from importlib import import_module

import numpy as np

from benchmark import facts, harness, loadgen_docqa
from benchmark.runners import hybrid, latent, linear, parallel, serve

# The form of runners/latent.py's check, on two kinds of served value. A
# served TOKEN's reference logit (main stack, its position's row) and a
# served DRAFT's reference logit (the module, the row that read the token
# before it) may each lie CHECK_TOLERANCE_STD reference-logit standard
# deviations under that row's largest. Held are CHECK_REQUESTS requests whose
# reply ended inside the window, each from another slot, no more than
# two under one system prompt (there are 8), the first CHECK_NEW_TOKENS of
# each.
# Half the router's experts are absent, and a token chooses 8 of 256: a
# routing near-tie between the 8th and 9th biased score swaps a held expert
# for nothing, and the position's stream moves by an expert's whole output.
# As in the latent and hybrid cells a value is LEFT OUT where the
# reference's 8th and 9th biased scores are closer than ROUTER_TIE_MARGIN in
# any expert layer at its row (for a draft: also in the module's router at
# its row), and CHECK_MAY_MISS of the kept values of EACH kind may lie
# further down than the tolerance (a swap at an earlier position reaches a
# kept value through the latent rows, which no margin at its own row tells).
# The limits are read off the chip (PERF.md 6, PR 53, has the table: the
# right model, every matmul operand rounded to float8, RMSNorm_h left out,
# the module reading the normed stream, the swapped concatenation, the
# token's own embedding). 256 sigmoid scores lie close: at the latent
# cell's margin of 0.002 an eighth of the values is kept (my chip run,
# PR 53, call 1: 62 of 512 tokens), at 0.0005 a good half, of which the
# right model reads 1.7 % of the tokens and 0.4 % of the drafts past the
# tolerance (unfiltered 2.9 % and 0.8 %).
CHECK_REQUESTS, CHECK_NEW_TOKENS = 12, 64
CHECK_PER_DOCUMENT = 2
CHECK_TOLERANCE_STD = 0.25
ROUTER_TIE_MARGIN = 0.0005
CHECK_MIN_KEPT = 0.25
CHECK_MAY_MISS = 0.06
# every request goes through the reference at ONE length (the
# configuration's ``max_seq_len``), a jitted program a kind of sub-layer
CHECK_ROUND_TO = 12_544

# every scope an operation lies in counts (``parallel.scope_seconds``): the
# module's ``mtp_layer`` holds latent and expert scopes of its own
SCOPES = ("mtp_embed_proj", "mtp_layer", "mtp_head", "draft_verify",
          "mla_paged_attention_mq", "mla_paged_attention", "mla_page_write",
          "mla_kv_compress", "mla_q_proj", "mla_absorb", "moe_gmm_prefill",
          "moe_gmm", "moe_shared_expert", "moe_router", "moe_dispatch",
          "moe_combine", "lm_head", "sampler")


def seeded_selfdraft_params(params: dict, seed: int) -> dict:
    """The parameter tree with what a seeded init leaves trivial made
    visible. ``gpt.init`` gives every norm's scale 0 (a plain RMS norm: a
    server that left ``RMSNorm_h``'s weight out would pass, and one that
    fed the module the stream AFTER the main model's final norm would serve
    the same drafts) and the router's selection bias 0. Seeded here: the
    q-latent's and kv-latent's norms' scales, the main model's final
    norm's and the module's three norms' (the program's ``1 + scale``) in
    U(-0.5, 0.5), the selection bias in U(-0.01, 0.01) (PR 31's
    reading: it changes WHICH experts are chosen between close scores and
    adds little skew). Every expert keeps ``gpt.init``'s scale."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 53)
    n = [0]

    def uniform(like, lo, hi):
        n[0] += 1
        return jax.random.uniform(jax.random.fold_in(key, n[0]), like.shape,
                                  jnp.float32, lo, hi).astype(like.dtype)
    blocks = {name: dict(stack) for name, stack in params["blocks"].items()}
    for norm in ("q_a_norm", "kv_norm"):
        blocks["attn"][norm] = {"scale": uniform(
            blocks["attn"][norm]["scale"], -0.5, 0.5)}
    blocks["moe"]["router"] = dict(blocks["moe"]["router"], bias=uniform(
        blocks["moe"]["router"]["bias"], -0.01, 0.01))
    mtp = dict(params["mtp"])
    for norm in ("enorm", "hnorm", "final_norm"):
        mtp[norm] = {"scale": uniform(mtp[norm]["scale"], -0.5, 0.5)}
    final = {"scale": uniform(params["final_norm"]["scale"], -0.5, 0.5)}
    return dict(params, blocks=blocks, mtp=mtp, final_norm=final)


class Served(latent.Served):
    """``latent.Served`` on this model's seeded weights, with each ended
    request's served DRAFTS kept beside its tokens, set-up that reaches
    every program a drafting engine's window runs, and the check held
    against the self-drafting reference."""

    def __init__(self, config: dict, seed: int, traffic: dict):
        # (``hybrid.Served`` builds the server and keeps each ended
        # request's slot, prompt and tokens; ``latent.Served``'s own
        # constructor seeds hyper-connections this model has none of)
        hybrid.Served.__init__(self, config, seed)
        self.params = seeded_selfdraft_params(self.params, seed)
        engine = self.server.engine
        engine.params = self.params
        self.traffic = traffic
        self.documents = loadgen_docqa.documents(
            traffic, self.model_cfg.vocab_size)
        self._doc_of_head = {tuple(d[:64]): i
                             for i, d in enumerate(self.documents)}
        # {request id: the draft verified at each served position (-1:
        # none)} of what has ended
        self.drafts: dict = {}
        on_finish = engine.on_finish

        def finish_hook(req):
            self.drafts[req.request_id] = list(
                getattr(req, "draft_tokens", ()))
            on_finish(req)

        engine.on_finish = finish_hook

    # -- set-up --------------------------------------------------------------

    def suffix_programs(self, tail: int) -> tuple:
        """The (chunk, final) buckets a request runs whose prompt lies
        ``tail`` tokens past its cached pages: a drafting engine's window
        starts one row early (the module's row of the last cached
        position), whole chunks go through the chunk program, the rest
        through the suffix program that samples."""
        engine = self.server.engine
        C = engine._chunk_tokens
        rows = tail + int(engine.serve_cfg.speculative == "mtp")
        chunked = rows > C
        return (C if chunked else 0,
                engine._suffix_bucket(rows - C if chunked else rows))

    def warm(self, traffic: dict, seed: int) -> None:
        """Load the system prompts (each once, with one task, one token),
        then one request a pair of programs the window's tails can reach,
        16 tokens long: the chunk program, the suffix programs and the
        draft-and-verify program have then compiled, and every system
        prompt's whole pages are in the prefix cache."""
        rng = np.random.default_rng([seed, 2])
        vocab = self.model_cfg.vocab_size
        spec = self.traffic["question_tokens"]
        body = dict(self.traffic["sampling"])

        def question(n):
            return rng.integers(258, vocab, n).tolist()
        for doc in self.documents:
            serve._post(self.url, {**body, "prompt": doc
                                   + question(spec["min"]), "max_tokens": 1})
        harness.mark(f"{len(self.documents)} system prompts in the prefix "
                     "cache", self._t0)
        ps = self.server.engine.kv.page_size
        done = set()
        for doc in self.documents:
            for q in range(spec["min"], spec["max"] + 1):
                programs = self.suffix_programs(len(doc) % ps + q)
                if programs not in done:
                    done.add(programs)
                    serve._post(self.url, {**body, "prompt": doc
                                           + question(q), "max_tokens": 16})

    # -- the check -----------------------------------------------------------

    def window_sample(self, raw: dict) -> list:
        """[(slot, prompt, served, drafts)] of CHECK_REQUESTS requests whose
        reply ENDED inside the window (``linear.ended_in_window``: what the
        run is judged on), in the order they ended, each from another slot,
        at most CHECK_PER_DOCUMENT under one system prompt."""
        ended = [(r["id"], self.served[r["id"]]) for r in sorted(
            (r for r in linear.ended_in_window(raw)
             if not facts.failed(r) and r["id"] in self.served),
            key=lambda r: r["done"])]
        sample, slots, docs = [], set(), defaultdict(int)
        for rid, (slot, prompt, served) in ended:
            doc = self.document_of(prompt)
            if (slot in slots or docs[doc] >= CHECK_PER_DOCUMENT
                    or len(served) < 2):
                continue
            slots.add(slot)
            docs[doc] += 1
            sample.append((slot, prompt, served, self.drafts.get(rid, [])))
            if len(sample) == CHECK_REQUESTS:
                break
        return sample

    def check_served(self, sample: list, wrong: str | None = None,
                     detail: bool = False) -> dict:
        """Hold served tokens AND served drafts to the plain reference:
        each request's prompt and its first CHECK_NEW_TOKENS served tokens
        teacher-forced through ``selfdraft_decoder.forward``. Served token
        j is held to the main stack's row prompt - 1 + j; the draft
        verified at served position j (made by the module's row prompt - 2
        + j, which read served token j - 1) to the module's logits at that
        row. Values at a routing near-tie are left out, and
        ``CHECK_MAY_MISS`` of the rest of each kind may lie further down
        than the tolerance. ``wrong`` gives the reference a fault."""
        from benchmark.reference import selfdraft_decoder
        kinds = {k: {"gaps": [], "margins": []} for k in ("token", "draft")}
        std_sum = {"token": 0.0, "draft": 0.0}
        for _, prompt, served, drafts in sample:
            served = served[:CHECK_NEW_TOKENS]
            drafts = list(drafts[:len(served)])
            n, p = len(served), len(prompt)
            # rows p - 1 .. p + n - 2: row p - 1 + j makes served token j,
            # and its module row the draft verified at served position j + 1
            out = selfdraft_decoder.forward(
                self.params, prompt + served[:-1], self.config,
                positions=range(p - 1, p - 1 + n), wrong=wrong,
                round_to=CHECK_ROUND_TO, compiled=True)
            main, draft = np.asarray(out["main"]), np.asarray(out["draft"])
            margin = np.asarray(out["margin"])
            both = np.minimum(margin, np.asarray(out["draft_margin"]))
            kinds["token"]["gaps"] += (
                main.max(-1) - main[np.arange(n), served]).tolist()
            kinds["token"]["margins"] += margin.tolist()
            at = [j for j in range(1, len(drafts)) if drafts[j] >= 0]
            rows = [j - 1 for j in at]
            kinds["draft"]["gaps"] += (
                draft[rows].max(-1)
                - draft[rows, [drafts[j] for j in at]]).tolist()
            kinds["draft"]["margins"] += both[rows].tolist()
            std_sum["token"] += float(main.std())
            std_sum["draft"] += float(draft.std())
        out = {"ok": len(sample) == CHECK_REQUESTS, "requests": len(sample),
               "slots": len({s[0] for s in sample}),
               "may_miss": CHECK_MAY_MISS}
        for kind, v in kinds.items():
            gaps, margins = v["gaps"], v["margins"]
            if not gaps:
                out.update({"ok": False, f"{kind}s": 0})
                continue
            std = std_sum[kind] / len(sample)
            tol = CHECK_TOLERANCE_STD * std
            kept = [g for g, m in zip(gaps, margins)
                    if m >= ROUTER_TIE_MARGIN]
            missed = sum(g > tol for g in kept)
            out["ok"] = bool(out["ok"]
                             and len(kept) >= CHECK_MIN_KEPT * len(gaps)
                             and missed <= CHECK_MAY_MISS * len(kept))
            out.update({
                f"{kind}s": len(gaps), f"{kind}s_kept": len(kept),
                f"{kind}s_under_tol": missed,
                f"all_{kind}s_under_tol": sum(g > tol for g in gaps),
                f"{kind}_worst_gap_std": max(kept, default=0.0) / std,
                f"{kind}_mean_gap_std": float(np.mean(gaps)) / std,
                f"{kind}_logit_std": std,
                f"{kind}s_off_the_reference_argmax":
                    sum(g > 0 for g in kept)})
            if detail:
                out.update({f"{kind}_gaps": gaps,
                            f"{kind}_margins": margins})
        return out


def scope_seconds(op_s: dict, texts: dict) -> dict:
    """``parallel.scope_seconds`` ({program: {scope: (events, seconds)}},
    every scope an operation lies in) with this model's scopes."""
    plain = parallel.SCOPES
    parallel.SCOPES = SCOPES
    try:
        return parallel.scope_seconds(op_s, texts)
    finally:
        parallel.SCOPES = plain


def require_selfdraft_support(config: dict) -> None:
    """Leave at once, with one line, where the program under test cannot
    serve this configuration's prediction module: a commit from before it
    refuses ``num_nextn_predict_layers`` 1 by name, and one that read the
    key and dropped the module would be measured as something it is not."""
    schema = import_module(f"{harness.PKG}.config.schema")
    try:
        model = schema.ModelConfig.from_dict(harness.model_dict(config))
        schema.ServeConfig(model=config["name"], **config["serve"])
    except Exception as e:
        raise SystemExit(f"benchmark/runners/selfdraft.py: this program "
                         f"cannot read {config['name']}: {e}")
    built = (model.mla.kv_lora_rank, model.mla.q_lora_rank,
             model.layer_pattern, getattr(model, "mtp_layers", 0),
             model.moe.num_experts, getattr(model.moe, "router_experts", 0))
    wanted = (config["kv_lora_rank"], config["q_lora_rank"],
              "*D" * config["first_k_dense_replace"] + "*E" * (
                  config["num_hidden_layers"]
                  - config["first_k_dense_replace"]),
              config["num_nextn_predict_layers"],
              config["n_routed_experts"], config["router_experts"])
    if built != wanted:
        raise SystemExit(
            f"benchmark/runners/selfdraft.py: this program builds "
            f"{config['name']} with (kv rank, q rank, layer table, "
            f"prediction modules, experts held, router width) = {built}, "
            f"the configuration says {wanted}: it cannot run this cell")


def run(cell: dict, config: dict, traffic_path: str, seed: int,
        seconds: float, trace: bool, t_process_start: float,
        require_tpu: bool = True) -> dict:
    """One run of a self-drafting serving cell; ``runners/latent.py run``
    with this runner's set-up, scopes and check."""
    require_selfdraft_support(config)
    device = harness.start(cell["chips"], require_tpu)
    harness.mark("imports and device", t_process_start)
    traffic = loadgen_docqa.load(traffic_path)
    served = Served(config, seed, traffic)
    served._t0 = t_process_start
    harness.mark(f"weights ({served.init_s:.1f}s) and server up",
                 t_process_start)
    try:
        with harness.scratch_dir("bench_selfdraft_traffic_") as tmp:
            # ``facts`` and ``serve.drive`` know serve-open / serve-closed
            path = os.path.join(tmp, os.path.basename(traffic_path))
            with open(path, "w") as f:
                json.dump(dict(traffic, kind="serve-" + traffic[
                    "kind"].split("-", 1)[1]), f)
            raw = serve.measure(served, cell, path, seed, seconds, trace,
                                t_process_start, device)
        # 128 callers before 64 slots, a reply ~25 s behind a wait as long:
        # a dozen requests are both sent and ended inside 51 s. The run is
        # judged, as the linear cell is, on the replies that ENDED in the
        # window whenever they were sent (``attempted``, ``failed``,
        # ``tpot_p95_ms``, the check's sample); tokens a second counts every
        # chunk the window delivered either way
        raw["judged"] = linear.ENDED_IN_WINDOW
        facts.window_requests = linear.window_requests
        if raw["trace"].get("op_s"):
            # before the pool goes: the programs' texts are lowered from
            # the live arguments' shapes (read back from the compile cache)
            by_program = scope_seconds(
                raw["trace"]["op_s"], served.server.engine.program_texts())
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
        served.release_pool()
        check = served.check_served(sample, detail=True)
        # every sampled value's gap and margin, for reading the check at
        # other numbers than it was run with (stderr alone)
        print("[bench] check detail " + json.dumps({
            k: [round(x, 6) for x in check.pop(k)]
            for k in [k for k in check
                      if k.endswith(("_gaps", "_margins"))]}),
              file=sys.stderr)
        raw["check"] = check
        print(f"[bench] reference check on the window's requests "
              f"{raw['check']}", file=sys.stderr)
        harness.mark("reference check on the window's requests",
                     t_process_start)
        return raw
    finally:
        served.close()
