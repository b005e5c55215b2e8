"""The runner of a serving cell whose model generates by DIFFUSION OVER
BLOCKS (traffic ``kind`` ``diffusion-closed``): the serving runner as it is
(``runners/serve.py``: the same server, hooks, warm-up, load generator and
window), on weights whose per-head q/k-norm scales are seeded (as
``runners/moe.py`` seeds OLMoE's), with

- the correctness check held against the plain block-diffusion reference
  (``reference/diffusion_decoder.py``) on the TRAJECTORIES OF REQUESTS THE
  WINDOW SERVED (the engine's finish hook keeps slot, prompt, tokens, the
  denoise step of each token, which a client gets with
  ``return_unmask_steps``, and the prompt tokens the prefix cache gave):
  for sampled blocks of sampled requests and every denoise step of such a
  block the reference forwards the window the server saw (rows fixed before
  that step, masks elsewhere) and holds the token(s) fixed at that step AND
  the choice of row(s) to its own;
- a trace of its own: device seconds by PROGRAM and kernel or scope name
  from the same profile (``run["trace"]["program_scope_s"]``): the denoise
  forward's kernels carry the names a prefill window's do
  (``paged_attention_blk``, ``moe_gmm_prefill``), so they are told apart by
  the program they ran in, and ``trace_reduce``'s ten-line ``device_ops``
  list drops a kernel off a busier program.

``run.py`` picks a runner by the traffic kind's first word; the traffic and
load generators know ``serve-open`` / ``serve-closed`` alone, so they are
handed a copy of the traffic file with the kind's first word set back to
``serve`` (as ``runners/moe.py`` does). ``run["kind"]`` stays ``"serve"``.
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
from benchmark.reference import diffusion_decoder
from benchmark.runners import hybrid, serve

# The check, held after the window on what the window SERVED: CHECK_REQUESTS
# of the requests that were sent and ended inside it (``facts.
# window_requests``), one a slot: the CHECK_LONGEST longest (contexts of
# 1,000 tokens and more, ~17 pages through the block kernel), then
# prefix-cache hits (their first window follows the suffix program of one
# page, a cold request's the cold prefill) and requests served cold; and of
# each CHECK_BLOCKS blocks from its first (whose window holds the prompt's
# remainder as fixed rows) to its last whole one (its longest context).
# While they were served the other 63 slots were live at steps of their
# own, some committing while others fixed rows. For every denoise step of
# such a block the reference forwards the canvas the server saw, padded
# with masks to a multiple of CHECK_ROUND_TO (under the block mask no row
# sees a later block, so the padding changes nothing, and the forwards run
# a few compiled shapes). Two limits, each in standard deviations of the
# reference's logits at the window's rows (0.89 here):
#
# (a) CHECK_TOKEN_STD: a token fixed at that step may lie this far under
#     the reference's largest logit at its row. The engine computes in
#     bfloat16 and rounds the stream in every layer; where the two largest
#     logits are closer than that rounding its argmax is the reference's
#     runner-up.
# (b) CHECK_ROW_STD: the row(s) the server chose may be this much less
#     confident, in the reference's log-probability of its own argmax,
#     than the reference's most confident masked row. The transfer rule
#     ranks rows by a softmax over 151,936 logits; the masked rows of one
#     window hold the same embedding and differ by position alone, and
#     bfloat16 decides near-ties.
#
# Readings on that sample (my chip runs, PR 42, review round; PERF.md 6 has
# the table; ~235 tokens and as many steps a reading, contexts to 1,216).
# The RIGHT model, the seeds read before the final runs: (a) 0.006-0.064,
# (b) 0.030-0.045. What the limits refuse, (a) | (b), two windows each
# (the second with the longest requests in the sample): the reference under
# a CAUSAL mask 1.15, 0.97 | 0.09, 0.17; without the per-head norms 0.82,
# 0.55 | 0.09, 0.07; with logits shifted by one 1.09, 0.67 | 0.50, 0.42;
# with matmul operands rounded to float8, the nearest precision under
# bfloat16, 7.17, 6.13 | 0.68, 0.63; a SERVER that skips the commit forward
# 1.51, 1.36 | 0.10, 0.07 (on four idle-engine prompts of under 121 tokens
# it read 0.25-0.37: a late block's whole context is then K/V of half-
# masked windows). (a) 0.15 lies 2.3 times over the right model's largest
# and 3.7 times under the least wrong reading; (b) 0.20 lies 4.4 times over
# and 2.1 (shifted) to 3.1 (float8) times under. Float8 fails by each
# limit, the shifted logits too; the causal mask, the missing head norms
# and the skipped commit by (a) alone: (b) is there for the transfer rule
# (a server that fixed the leftmost row, or a random one, picks rows the
# reference ranks 0.2-0.4 down, the spread the shifted reading shows).
CHECK_REQUESTS = 16
CHECK_LONGEST = 4
CHECK_BLOCKS = 4
CHECK_PREFIX_HITS = 1       # at least so many of the sample, at most half
CHECK_ROUND_TO = 256
CHECK_TOKEN_STD = 0.15
CHECK_ROW_STD = 0.20
# float8 (e4m3) matmul operands: the nearest precision under bfloat16
FLOAT8 = (4, 3)
# the seeded q/k-norm scales' range (``seeded_head_scales``; the MoE
# runner's ``QK_SCALE_SPREAD``)
QK_SCALE = (-0.5, 0.5)
VARIANTS = ("causal", "no_head_norm", "shift", "float8")

# names a device trace shows this model's work under: Pallas kernels by
# the name the program gives them, XLA operations by the named scope they
# were traced in. Longest first.
SCOPES = ("paged_attention_blk", "paged_attention_mq", "paged_attention",
          "moe_gmm_prefill", "moe_gmm", "moe_router", "moe_dispatch",
          "moe_combine", "kv_page_write", "sample_tokens", "unmask")


def scope_of(texts) -> str | None:
    for scope in SCOPES:
        for text in texts:
            if re.search(rf"(?<![A-Za-z_]){scope}(?![A-Za-z_])", text):
                return scope
    return None


def program_scope_seconds(op_s: dict, texts: dict, vocab: int,
                          names: dict = trace_reduce.NAMES) -> dict:
    """{program: {scope: [events, device seconds]}} from
    ``hybrid.op_seconds`` and the engine's ``program_texts()``: a Pallas
    kernel is told by its own name, an XLA operation by the scope its
    instruction's ``op_name`` holds in the text of the program it ran in;
    an operation under no scope whose HLO line holds the vocabulary's
    width is put under ``vocab_rows`` (the head's matmul and what the
    transfer rule reads of the logits), anything else under ``other``."""
    by_program: dict = defaultdict(dict)
    for name, text in texts.items():
        program = trace_reduce.program_of("jit_" + name.split(" ")[0], names)
        for line in text.splitlines():
            m = hybrid._INSTRUCTION.match(line)
            if not m:
                continue
            scope = scope_of([m.group(2)]) or (
                "vocab_rows" if f",{vocab}]" in line else None)
            if scope:
                by_program[program].setdefault(m.group(1), scope)
    out: dict = defaultdict(lambda: defaultdict(lambda: [0, 0.0]))
    for program, ops in op_s.items():
        for name, (n, seconds) in ops.items():
            scope = (scope_of([name]) or by_program[program].get(name)
                     or "other")
            cell = out[program][scope]
            cell[0] += n
            cell[1] += seconds
    return {p: {k: tuple(v) for k, v in s.items()} for p, s in out.items()}


def block_starts(prompt_len: int, reply_len: int, block: int,
                 count: int) -> list:
    """``count`` block starts of a reply, evenly from its first block (the
    one the prompt ends in) to its last WHOLE one (a reply cut at
    ``max_tokens`` may end inside a block: what lay after the cut was never
    returned)."""
    first = prompt_len // block
    last = (prompt_len + reply_len) // block - 1
    if last < first:
        return []
    return sorted({(first + round(i * (last - first) / max(count - 1, 1)))
                   * block for i in range(count)})


def windows_of(prompt: list, tokens: list, steps: list, block: int,
               mask_id: int, starts=None):
    """The trajectory a reply's tokens and unmask steps describe: for every
    block (or those at ``starts``) and denoise step, (canvas up to the
    block's end as the server saw it BEFORE the step, the block's start,
    the rows fixed AT the step, the rows still masked before it). Without
    ``starts`` the reply must end on a block."""
    n = len(prompt)
    full = prompt + tokens
    at = [-1] * n + steps
    if starts is None:
        assert len(full) % block == 0, "the check's replies end on a block"
        starts = range(n // block * block, len(full), block)
    for start in starts:
        rows = range(start, start + block)
        for step in range(max(at[r] for r in rows) + 1):
            window = [full[r] if at[r] < step else mask_id for r in rows]
            yield (full[:start] + window, start,
                   [r - start for r in rows if at[r] == step],
                   [r - start for r in rows if at[r] >= step])


def commit_skipping(denoise_scan):
    """The WRONG server the check is shown to catch (tests, experiments;
    put in place of ``serve/engine.py``'s ``denoise_scan`` before its
    program is traced, never by the program): ``denoise_scan`` a forward at
    a time, and a window whose last mask that forward fixed is emitted AT
    ONCE: no commit forward, the K/V of the half-masked window stay in the
    pages."""
    import jax
    import jax.numpy as jnp
    decode = import_module(f"{harness.PKG}.serve.decode")
    committed = decode.DENOISE_COUNTS.index("blocks_committed")

    def scan(params, window, starts, k_pages, v_pages, tables, stops, keys,
             temperature, top_k, top_p, cfg, num_steps, **kw):
        Bd, mask_id = cfg.diffusion.block_length, cfg.diffusion.mask_token_id

        def one(carry, _):
            window, starts, kp, vp, *sums = carry
            (window, starts, kp, vp, *new), out = denoise_scan(
                params, window, starts, kp, vp, tables, stops, keys,
                temperature, top_k, top_p, cfg, 1, **kw)
            toks, at, step = window
            done = (starts < stops) & ~(at == decode.UNFIXED).any(axis=-1)
            out = jnp.where(done[:, None], jnp.concatenate(
                [toks, at, jnp.ones_like(toks[:, :1])], axis=-1), out[0])
            window = (jnp.where(done[:, None], mask_id, toks),
                      jnp.where(done[:, None], decode.UNFIXED, at),
                      jnp.where(done, 0, step))
            starts = jnp.where(done, starts + Bd, starts)
            new[-1] = new[-1].at[committed].add(
                jnp.sum(done).astype(new[-1].dtype))
            return (window, starts, kp, vp,
                    *[a + b for a, b in zip(sums, new)]), out

        zeros = [jnp.zeros((cfg.moe.stats_size,), jnp.int32)] \
            if cfg.is_moe else []
        zeros.append(jnp.zeros((len(decode.DENOISE_COUNTS),), jnp.int32))
        return jax.lax.scan(one, (window, starts, k_pages, v_pages, *zeros),
                            None, length=num_steps)
    return scan


def seeded_head_scales(params: dict, seed: int) -> dict:
    """The parameter tree with seeded non-zero scales on the per-head q/k
    norms (the program's weight is ``1 + scale``; ``1 + U(QK_SCALE)`` a
    channel, as ``runners/moe.py`` seeds OLMoE's). A trained model's are
    learned; ``gpt.init`` leaves them 0, a plain RMS norm, which a server
    or a reference that drops the learned scale could not be told from."""
    import jax
    import jax.numpy as jnp
    blocks = dict(params["blocks"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 42)
    for i, name in enumerate(("q_norm", "k_norm")):
        scale = blocks[name]["scale"]
        blocks[name] = {"scale": jax.random.uniform(
            jax.random.fold_in(key, i), scale.shape, jnp.float32,
            *QK_SCALE).astype(scale.dtype)}
    return dict(params, blocks=blocks)


class Served(serve.Served):
    """``serve.Served`` with the per-head q/k norms' scales seeded, the
    check held against the block-diffusion reference on what the window
    served, and the by-program trace."""

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        # nothing has been served yet and the engine's programs take the
        # tree as an argument: server and reference read the same one
        self.params = seeded_head_scales(self.params, seed)
        self.server.engine.params = self.params
        # {request id: (slot, prompt, served tokens, the denoise step of
        # each, prompt tokens the prefix cache gave)} of what has ended
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
                list(req.generated_tokens), list(req.unmask_steps),
                req.prefix_cached_tokens)
            on_finish(req)

        engine.on_token, engine.on_finish = token_hook, finish_hook

    def check_against_reference(self, seed: int, config: dict | None = None
                                ) -> dict:
        """Nothing before the window: ``run`` holds the check on requests
        the window finished (``window_sample``). Not correct until then."""
        return {"ok": False, "pending": "held on the window's requests"}

    def warm(self, traffic: dict, seed: int) -> None:
        """``serve.Served.warm``, twice: the pool's prompts come round
        again inside a window, whole pages of a repeated prompt are prefix-
        cache hits, and what is left of it (under a page) runs the suffix
        program of ONE page. The second pass sends the first's prompts
        again, so that program too has compiled before the window."""
        super().warm(traffic, seed)
        super().warm(traffic, seed)

    def window_sample(self, raw: dict) -> list:
        """[(slot, prompt, tokens, steps, cached)] of CHECK_REQUESTS
        requests that were sent and ended inside the window, one a slot
        before a second of any slot: the CHECK_LONGEST longest (what ends
        first in a window is short), then in the order they ended
        prefix-cache hits, for half of what is left, then requests served
        cold."""
        ended = [self.served[r["id"]] for r in sorted(
            (r for r in facts.window_requests(raw)
             if not facts.failed(r) and r["id"] in self.served),
            key=lambda r: r["done"])]
        ended = [s for s in ended if len(s[2]) == len(s[3]) and block_starts(
            len(s[1]), len(s[2]), self.config["block_length"], 1)]
        longest = sorted(ended, key=lambda s: -(len(s[1]) + len(s[2])))
        hits = [s for s in ended if s[4] > 0]
        sample, slots = [], set()
        for pool, room, fresh_slot in (
                (longest, CHECK_LONGEST, True),
                (hits, (CHECK_REQUESTS + CHECK_LONGEST) // 2, True),
                ([s for s in ended if s[4] == 0], CHECK_REQUESTS, True),
                (ended, CHECK_REQUESTS, True), (ended, CHECK_REQUESTS, False)):
            for s in pool:
                if len(sample) >= room:
                    break
                if any(s is t for t in sample) or (
                        fresh_slot and s[0] in slots):
                    continue
                slots.add(s[0])
                sample.append(s)
        return sample

    def release_pools(self) -> None:
        """Stop the engine thread and give the K/V pools' memory back
        before the reference runs at the window's context lengths: nothing
        is served after the window. (The thread first: a closed loop's
        callers leave requests in flight, and a dispatch over a deleted
        pool makes the engine allocate a new one.)"""
        self.server.stop_engine()
        kv = self.server.engine.kv
        kv.k_pages.delete()
        kv.v_pages.delete()

    def check_served(self, sample: list, variant: str | None = None,
                     keep_gaps: bool = False) -> dict:
        """Follow the sampled blocks of the sampled requests through the
        reference and read both limits. ``variant`` gives the WRONG
        reference the limits are shown to refuse (``VARIANTS``);
        ``keep_gaps`` also returns every token's and every step's gap."""
        cfg = self.config
        Bd, mask_id = cfg["block_length"], cfg["mask_token_id"]
        forward = {"causal": {"mask_block": 1},
                   "float8": {"operand_bits": FLOAT8}}.get(variant, {})
        ref_cfg = dict(cfg, qk_norm="none") if variant == "no_head_norm" \
            else cfg
        shift = int(variant == "shift")
        token_gaps, row_gaps, stds, contexts = [], [], [], []
        for _, prompt, tokens, steps, _ in sample:
            full = prompt + tokens
            for canvas, start, fixed, masked in windows_of(
                    prompt, tokens, steps, Bd, mask_id, block_starts(
                        len(prompt), len(tokens), Bd, CHECK_BLOCKS)):
                length = -(-len(canvas) // CHECK_ROUND_TO) * CHECK_ROUND_TO
                padded = canvas + [mask_id] * (length - len(canvas))
                lg = np.asarray(diffusion_decoder.logits(
                    self.params, padded, ref_cfg,
                    positions=range(start - shift, start - shift + Bd),
                    **forward), np.float64)
                lg[:, mask_id] = -np.inf    # the mask token is never drawn
                std = float(lg[np.isfinite(lg)].std())
                top = lg.max(-1)
                lse = top + np.log(np.exp(lg - top[:, None]).sum(-1))
                conf = top - lse            # log-probability of the argmax
                for r in fixed:     # the token the reply holds at that row
                    token_gaps.append((top[r] - lg[r, full[start + r]]) / std)
                best = max(conf[r] for r in masked)
                row_gaps.append((best - min(conf[r] for r in fixed)) / std)
                stds.append(std)
                contexts.append(len(canvas))
        if not row_gaps:
            return {"ok": False, "requests": len(sample), "tokens": 0}
        worst_token, worst_row = float(max(token_gaps)), float(max(row_gaps))
        hits = sum(s[4] > 0 for s in sample)
        return {"ok": bool(len(sample) == CHECK_REQUESTS
                           and hits >= CHECK_PREFIX_HITS
                           and worst_token <= CHECK_TOKEN_STD
                           and worst_row <= CHECK_ROW_STD),
                "worst_token_gap_std": worst_token,
                "token_limit_std": CHECK_TOKEN_STD,
                "worst_row_gap_std": worst_row,
                "row_limit_std": CHECK_ROW_STD,
                "logit_std": float(np.mean(stds)),
                "requests": len(sample),
                "slots": len({s[0] for s in sample}),
                "prefix_hits": hits,
                "tokens": len(token_gaps), "steps": len(row_gaps),
                "longest_context": max(contexts),
                "tokens_off_the_reference_argmax":
                    int(sum(g > 0 for g in token_gaps)),
                "steps_off_the_reference_row":
                    int(sum(g > 0 for g in row_gaps)),
                **({"variant": variant} if variant else {}),
                **({"token_gaps": [float(g) for g in token_gaps],
                    "row_gaps": [float(g) for g in row_gaps],
                    "contexts": contexts} if keep_gaps else {})}

    def drive(self, *args, **kwargs) -> dict:
        """``serve.Served.drive`` with ``hybrid.Trace`` (which keeps the
        profile's seconds by program and operation) where it makes a
        ``harness.Trace``."""
        plain = harness.Trace
        harness.Trace = hybrid.Trace
        try:
            return super().drive(*args, **kwargs)
        finally:
            harness.Trace = plain


def require_diffusion_support(config: dict) -> None:
    """Leave at once, with a reason, where the program under test cannot
    build this configuration: a commit from before generation by diffusion
    refuses the per-head q/k norms by name, or would load the model as an
    autoregressive MoE and be measured as something it is not."""
    schema = import_module(f"{harness.PKG}.config.schema")
    try:
        model = schema.ModelConfig.from_dict(harness.model_dict(config))
    except Exception as e:
        raise SystemExit(f"benchmark/runners/diffusion.py: this program "
                         f"cannot read {config['name']}: {e}")
    built = (getattr(getattr(model, "diffusion", None), "block_length", 0),
             getattr(model, "qk_norm", "none"), model.moe.num_experts)
    wanted = (config["block_length"], config["qk_norm"],
              config["num_experts"])
    if built != wanted:
        raise SystemExit(
            f"benchmark/runners/diffusion.py: this program builds "
            f"{config['name']} with (block length, qk_norm, experts) = "
            f"{built}, the configuration says {wanted}: it cannot run this "
            "cell")


def window(served: Served, cell: dict, traffic_path: str, seed: int,
           seconds: float, trace: bool, t_process_start: float,
           device: dict) -> tuple[dict, list]:
    """Warm and drive a server that is up (``serve.measure``), read the
    traced programs' scopes, then stop the engine and free its pools: (the
    raw run, the window's sample for ``check_served``)."""
    traffic = traffic_mod.load(traffic_path)
    traffic["kind"] = "serve-" + traffic["kind"].split("-", 1)[1]
    with harness.scratch_dir("bench_diffusion_traffic_") as tmp:
        path = os.path.join(tmp, os.path.basename(traffic_path))
        with open(path, "w") as f:
            json.dump(traffic, f)
        raw = serve.measure(served, cell, path, seed, seconds, trace,
                            t_process_start, device)
    if raw["trace"].get("op_s"):
        # after the window, and in a traced run alone: the programs'
        # texts cost a compile each (read back from the compile cache),
        # lowered from the live arguments' shapes: before the pools go
        raw["trace"]["program_scope_s"] = program_scope_seconds(
            raw["trace"]["op_s"], served.server.engine.program_texts(),
            served.model_cfg.vocab_size)
        print(f"[bench] device seconds by program and scope "
              f"{raw['trace']['program_scope_s']}", file=sys.stderr)
        harness.mark("scopes of the traced operations", t_process_start)
    sample = served.window_sample(raw)
    served.release_pools()
    return raw, sample


def run(cell: dict, config: dict, traffic_path: str, seed: int,
        seconds: float, trace: bool, t_process_start: float,
        require_tpu: bool = True) -> dict:
    """One run of a diffusion serving cell; ``runners/serve.py run`` with
    the traffic file's kind handed on as the generators know it, and the
    check held on the window's requests."""
    require_diffusion_support(config)
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
