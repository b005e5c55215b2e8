"""Self-drafting over latent pages (``model_type: joyai_llm_flash``): latent
attention behind a query bottleneck, sigmoid experts behind a leading dense
layer, and ONE next-token prediction module that drafts while the main stack
verifies, on the CPU at ``joyai-test`` widths, held to the plain float32
reference (``benchmark/reference/selfdraft_decoder.py``) on LOGITS, main and
draft, and to plain greedy decoding token for token.

Tolerance: both sides compute in float32 with full-precision matmuls and
differ in the ORDER of their sums alone (absorbed against expanded
attention, online against plain softmax, a one-hot page merge, a sentinel
row whose softmax weight is exactly 0): logits of size ~0.5 agree to ~3e-7,
and TOL = 2e-5 leaves that two orders of room. A bfloat16 model read for the
float32 one moves the logits by more than 100 x TOL
(``test_the_full_forward_is_the_reference[bfloat16]``), and each departure
of the module the chip's check is asked to refuse by more than 20 x TOL.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serving_support as support
from benchmark.reference import selfdraft_decoder as ref
from distributed_llm_training_and_inference_system_tpu.config.presets import (
    JOYAI_TEST_PUBLISHED,
    get_model_config,
    joyai_test_share,
)
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ConfigError,
    ModelConfig,
    ServeConfig,
)
from distributed_llm_training_and_inference_system_tpu.models import gpt
from distributed_llm_training_and_inference_system_tpu.models.layers import (
    experts_mixer,
)
from distributed_llm_training_and_inference_system_tpu.serve import decode
from distributed_llm_training_and_inference_system_tpu.serve.engine import (
    InferenceEngine,
)
from distributed_llm_training_and_inference_system_tpu.serve.kv_cache import (
    REFUSED,
    PagedKVCache,
)
from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
    Request,
    SamplingParams,
)

TOL = 2e-5
C = JOYAI_TEST_PUBLISHED
PS = 8


@pytest.fixture(scope="module")
def cfg():
    return get_model_config("joyai-test")


def _seeded(cfg, seed=0):
    """Seeded weights with every norm's scale (the module's three among
    them) and the selection bias made non-trivial: at ``gpt.init``'s zeros
    a missing norm weight or bias would not show."""
    tree = support.params_of(cfg, seed)
    key = jax.random.PRNGKey(5 + seed)

    def seeded(path, x):
        names = [k.key for k in path]
        if "scale" in names or names[-1] == "bias":
            spread = 0.02 if names[-1] == "bias" else 0.4
            return x + jax.random.uniform(
                jax.random.fold_in(key, hash(tuple(names)) % 9973), x.shape,
                x.dtype, -spread, spread)
        return x
    return jax.tree_util.tree_map_with_path(seeded, tree)


@pytest.fixture(scope="module")
def params(cfg):
    return _seeded(cfg)


def _standing(params, leak=0.0):
    """Constructed weights on which every draft STANDS: every output
    projection of the main stack's layers and of the module's zero (the
    stream is the token's embedding), ``W_eh`` = [I | 0] and every norm
    plain, so that the module's logits for position i + 2 are the main
    stack's. ``leak`` > 0 lets a seeded part of the stream into the module:
    a MIX of drafts that stand and drafts that fall."""
    p = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x)
        if "scale" in [k.key for k in path] else x, params)
    blocks = {k: dict(v) for k, v in p["blocks"].items()}
    blocks["attn"]["o"] = {"kernel": jnp.zeros_like(
        blocks["attn"]["o"]["kernel"])}
    blocks["mlp"]["down"] = {"kernel": jnp.zeros_like(
        blocks["mlp"]["down"]["kernel"])}
    moe = dict(blocks["moe"])
    moe["down"] = {"kernel": jnp.zeros_like(moe["down"]["kernel"])}
    moe["shared"] = dict(moe["shared"], down={"kernel": jnp.zeros_like(
        moe["shared"]["down"]["kernel"])})
    blocks["moe"] = moe
    H = p["embed"]["embedding"].shape[1]
    noise = jax.random.normal(jax.random.PRNGKey(11), (H, H)) * leak
    mtp = dict(p["mtp"], eh_proj={"kernel": jnp.concatenate(
        [jnp.eye(H), noise]).astype(p["mtp"]["eh_proj"]["kernel"].dtype)})
    return dict(p, blocks=blocks, mtp=mtp)


def _reference(params, tokens, next_token=0, **kw):
    out = ref.forward(params, tokens, C, next_token=next_token, **kw)
    return np.asarray(out["main"]), np.asarray(out["draft"])


# -- the model against the reference ---------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_full_forward_is_the_reference(cfg, params, dtype):
    """Main and draft logits of a whole sequence, row i of the module read
    with token i + 1 (the last row's wraps around to token 0)."""
    tokens = support.tokens(40)
    c = dataclasses.replace(cfg, dtype=dtype)
    with jax.default_matmul_precision("highest"):
        main, draft = gpt.forward(params, jnp.asarray([tokens]), c,
                                  return_mtp=True)
    want_main, want_draft = _reference(params, tokens, next_token=tokens[0])
    off = max(np.abs(np.asarray(main[0]) - want_main).max(),
              np.abs(np.asarray(draft[0]) - want_draft).max())
    if dtype == "float32":
        assert off < TOL
    else:       # the tolerance is tight enough to tell bfloat16 from float32
        assert 100 * TOL < off < 0.2


@pytest.mark.parametrize("wrong", ref.MODULE_WRONGS + ("float8",))
def test_each_departure_moves_the_logits(params, wrong):
    """What the chip's check must refuse moves the reference's own draft
    logits (the module's departures) or both (float8 operands) by more than
    20 x TOL; the module's departures leave the main logits alone."""
    tokens = support.tokens(24, seed=2)
    main, draft = _reference(params, tokens)
    w_main, w_draft = _reference(params, tokens, wrong=wrong)
    assert np.abs(w_draft[:-1] - draft[:-1]).max() > 20 * TOL
    if wrong == "float8":
        assert np.abs(w_main - main).max() > 20 * TOL
    else:
        assert np.abs(w_main - main).max() == 0


def test_the_reference_padded_and_compiled_is_the_reference(params):
    tokens = support.tokens(45, seed=3)
    plain = ref.forward(params, tokens, C, next_token=7, with_scores=True)
    ref._compiled_sub_layers.cache_clear()
    got = ref.forward(params, tokens, C, next_token=7, round_to=64,
                      compiled=True, with_scores=True)
    ref._compiled_sub_layers.cache_clear()
    assert got["scores"].shape == (3, 45, 8)        # two routers + the module's
    for key in ("main", "draft", "margin", "draft_margin", "scores"):
        assert np.abs(np.asarray(got[key])
                      - np.asarray(plain[key])).max() < TOL, key


# -- windows through the latent pool ---------------------------------------------

@pytest.fixture(scope="module")
def window_program(cfg):
    """One window of every slot through main stack, module and both heads,
    jitted (a program a window length)."""
    def program(params, window, reads, starts, pool, tables, ok, mod_ok):
        step = decode.extend_step_forward(
            params, window, starts, pool, None, tables, cfg, write_ok=ok,
            return_stream=True)
        z, pool, _ = decode.mtp_window(
            params, cfg, reads, step.stream, starts, step.k_pages, tables,
            mod_ok, first=True)
        return step.logits, gpt.mtp_head(params, z, cfg), pool
    return jax.jit(program)


def _paged(cfg, params, tokens, windows, program):
    """Main and draft logits of ``tokens`` served through the latent pages
    in windows of the given lengths (prefill windows, then decode windows
    of 1 or 2 rows), one slot among three: what the chunk, suffix and
    draft-and-verify programs run, piece by piece. The module's row i reads
    token i + 1, so the last token has a main row and no module row."""
    kv = PagedKVCache(cfg, num_slots=3, max_seq_len=128, page_size=PS,
                      num_pages=40, dtype=jnp.float32)
    kv.allocate(1, len(tokens) + 1)
    tables = jnp.asarray(kv.block_tables)
    pool, main, draft, at = kv.k_pages, [], [], 0
    with jax.default_matmul_precision("highest"):
        for t in windows:
            window = np.zeros((3, t), np.int32)
            nxt = np.zeros((3, t), np.int32)
            window[1] = tokens[at:at + t]
            reads = tokens[at + 1:at + t + 1]
            nxt[1, :len(reads)] = reads
            ok = np.zeros((3, t), bool)
            ok[1] = True
            # (the last token's module row waits for the token after it)
            mod_ok = ok.copy()
            mod_ok[1, len(reads):] = False
            lg, dl, pool = program(
                params, jnp.asarray(window), jnp.asarray(nxt),
                jnp.asarray([0, at, 0], jnp.int32), pool, tables,
                jnp.asarray(ok), jnp.asarray(mod_ok))
            main.append(np.asarray(lg[1]))
            draft.append(np.asarray(dl[1]))
            at += t
    return np.concatenate(main), np.concatenate(draft)


@pytest.mark.parametrize("windows", [
    (40,), (16, 16, 8), (8, 16, 1, 2, 2, 1, 2, 2, 2, 2, 2)],
    ids=["one-window", "chunks-of-two-pages",
         "chunks-then-steps-over-page-boundaries"])
def test_windows_through_the_pool_are_the_reference(cfg, params, windows,
                                                    window_program):
    tokens = support.tokens(sum(windows), seed=4)
    main, draft = _paged(cfg, params, tokens, windows, window_program)
    want_main, want_draft = _reference(params, tokens)
    assert np.abs(main - want_main).max() < TOL
    # the last token's module row read nothing real on either side
    assert np.abs(draft[:-1] - want_draft[:-1]).max() < TOL


def test_the_sentinel_of_index_0_is_one_lane_of_the_rows_padding(cfg):
    """Cache index 0 of the module's layer has no row: what stands there
    is zeros but for ``MTP_SENTINEL`` in the first padding lane, which every
    query of the module meets with a 1 (a score of ``MTP_SENTINEL`` x
    scale: a softmax weight of exactly 0 in float32)."""
    row = decode.mtp_sentinel_row(cfg, jnp.zeros((1, cfg.mla.page_width)))
    assert cfg.mla.page_width > cfg.mla.latent_size
    assert float(row[cfg.mla.latent_size]) == decode.MTP_SENTINEL
    assert float(jnp.abs(row).sum()) == -decode.MTP_SENTINEL
    assert float(jnp.exp(jnp.float32(
        decode.MTP_SENTINEL * cfg.softmax_scale))) == 0.0


# -- the engine: every prefill path, then draft-and-verify steps ----------------

# three slots; chunks of 32 tokens; the module drafts whatever its
# acceptance: the counts and the two-token steps below are of this engine
DRAFTING = dict(max_batch_size=3, chunked_prefill_tokens=32,
                prefix_caching=True, speculative="mtp",
                speculative_min_acceptance=0.0)


@pytest.fixture(scope="module")
def drafting(cfg, params):
    """ONE engine that drafts for every test below (its programs take the
    weights as an argument: ``_serve`` swaps them)."""
    return support.engine(cfg, params, **DRAFTING)


@pytest.fixture(scope="module")
def plain(cfg, params):
    """... and one that serves the same model by ``decode_scan``."""
    return support.engine(cfg, params,
                          **{**DRAFTING, "speculative": "off"})


def _serve(eng, weights, prompts, n=16, flush=True, **sampling):
    """Serve ``prompts`` greedily on ``weights`` (the prefix cache emptied
    first: what it holds came from other weights or another test), and the
    delta of the engine's ``mtp`` counters."""
    eng.params = weights
    if flush:
        eng.kv.flush_prefix_cache()
    before = eng.stats()
    sampling.setdefault("temperature", 0.0)
    reqs = [Request(f"r{eng.total_tokens_credited}-{i}", list(p),
                    SamplingParams(max_tokens=m, **sampling))
            for i, (p, m) in enumerate(zip(
                prompts, n if isinstance(n, list) else [n] * len(prompts)))]
    for r in reqs:
        assert eng.scheduler.add_request(r)
    with jax.default_matmul_precision("highest"):
        eng.run_until_idle()
    after = eng.stats()
    return reqs, {k: after[f"mtp_{k}"] - before[f"mtp_{k}"] for k in (
        "drafts", "accepted", "slot_steps", "tokens")}


def _held_to_the_reference(params, req, config=C):
    """A served request's tokens are the reference's greedy tokens, and the
    drafts it verified the reference MODULE's (row p - 2 + j, which read
    served token j - 1, drafts served position j)."""
    prompt, served = list(req.prompt_tokens), list(req.generated_tokens)
    p, n = len(prompt), len(served)
    out = ref.forward(params, prompt + served[:-1], config, round_to=128,
                      compiled=True)
    main, draft = np.asarray(out["main"]), np.asarray(out["draft"])
    assert served == main[p - 1:].argmax(-1).tolist()
    assert len(req.draft_tokens) == n and req.draft_tokens[0] == -1
    made = draft[p - 1:p - 1 + n - 1].argmax(-1).tolist()
    for j in range(1, n):
        if req.draft_tokens[j] >= 0:
            assert req.draft_tokens[j] == made[j - 1], j
        else:       # a pair's second token: the draft before it stood
            assert req.draft_tokens[j - 1] == served[j - 1]


@pytest.mark.parametrize("path, n_prompt", [
    ("cold", 21), ("cold-over-a-page-boundary", 24), ("chunked", 75),
    ("chunked-in-whole-chunks", 64)])
def test_prefill_then_steps_serve_the_references_tokens_and_drafts(
        drafting, params, path, n_prompt):
    before = dict(drafting.stats()["compiled_programs"])
    (req,), mtp = _serve(drafting, params, [support.tokens(n_prompt, seed=8)], n=12)
    _held_to_the_reference(params, req)
    assert mtp["slot_steps"] == mtp["drafts"] > 0
    assert mtp["tokens"] == mtp["slot_steps"] + mtp["accepted"] == 11
    programs = drafting.stats()["compiled_programs"]
    kind = "chunk" if path.startswith("chunk") else "dense"
    assert programs[f"prefill_{kind}_buckets"] >= max(
        before[f"prefill_{kind}_buckets"], 1)


@pytest.mark.parametrize("cached, tail", [(16, 5), (24, 1), (48, 40)],
                         ids=["suffix", "a-suffix-of-one-token",
                              "a-suffix-in-chunks"])
def test_a_suffix_over_cached_pages_serves_the_references(
        drafting, params, cached, tail):
    """The second prompt finds ``cached`` tokens of whole pages: its window
    starts one row early (the module's row of the last cached position
    reads the first uncached token), and tokens AND drafts are the
    reference's."""
    shared = support.tokens(cached + 3, seed=9)
    _serve(drafting, params, [shared], n=2)
    (req,), _ = _serve(drafting, params,
                       [shared[:cached] + support.tokens(tail, seed=10)], n=10,
                       flush=False)
    assert req.prefix_cached_tokens == cached
    _held_to_the_reference(params, req)


def test_two_prompts_equal_over_a_page_and_different_behind_it_share_it(
        drafting, params):
    """A page is a function of its token prefix: the module's row for the
    page's LAST position reads the token AFTER the page, so it is stored at
    the next cache index (in the next page). Two prompts that agree on a
    page and differ in the very next token share the page, and both are
    served right."""
    page = support.tokens(PS, seed=12)
    a, b = page + [11] + support.tokens(5, seed=13), page + [12] + support.tokens(5, seed=14)
    (first,), _ = _serve(drafting, params, [a], n=8)
    (second,), _ = _serve(drafting, params, [b], n=8, flush=False)
    assert (first.prefix_cached_tokens, second.prefix_cached_tokens) == (0, PS)
    _held_to_the_reference(params, first)
    _held_to_the_reference(params, second)
    (alone,), _ = _serve(drafting, params, [b], n=8)
    assert alone.prefix_cached_tokens == 0
    assert alone.generated_tokens == second.generated_tokens
    assert alone.draft_tokens == second.draft_tokens


# -- the stream is plain greedy decoding at every acceptance pattern ------------

def _weights(params, which):
    return {"none-stands": params, "all-stand": _standing(params),
            "a-mix": _standing(params, leak=0.05)}[which]


PROMPTS = [support.tokens(19, seed=20), support.tokens(33, seed=21), support.tokens(8, seed=22)]


@pytest.mark.parametrize("which, share", [
    ("none-stands", (0.0, 0.2)), ("all-stand", (1.0, 1.0)),
    ("a-mix", (0.1, 0.9))])
def test_the_stream_is_plain_greedy_decoding(drafting, plain, params, which,
                                             share):
    """BOTH branches run, and a mix: the served stream equals
    ``decode_scan``'s (the same model served with ``speculative: off``)
    token for token, whatever stands."""
    weights = _weights(params, which)
    want, _ = _serve(plain, weights, PROMPTS, n=21)
    drafted, mtp = _serve(drafting, weights, PROMPTS, n=21)
    for a, b in zip(want, drafted):
        assert a.generated_tokens == b.generated_tokens
        assert a.finish_reason == b.finish_reason == "length"
    stood = mtp["accepted"] / mtp["drafts"]
    assert share[0] <= stood <= share[1], stood
    assert mtp["tokens"] == 3 * 20
    if which == "all-stand":
        # 20 tokens behind the first: 10 steps of 2 a request
        assert mtp["slot_steps"] == 30
        for req in drafted:
            assert req.draft_tokens[1::2] == req.generated_tokens[1::2]
            assert set(req.draft_tokens[2::2]) == {-1}
            assert req.draft_stood == [False] + [True, False] * 10


def test_max_tokens_on_the_first_token_of_a_standing_pair_drops_the_second(
        drafting, plain, params):
    weights = _standing(params)
    lengths = [1, 2, 3, 4, 7]
    prompts = [support.tokens(19, seed=50 + n) for n in lengths]
    want, _ = _serve(plain, weights, prompts, n=lengths)
    got, _ = _serve(drafting, weights, prompts, n=lengths)
    for n, a, b in zip(lengths, want, got):
        assert b.generated_tokens == a.generated_tokens
        assert len(b.generated_tokens) == n == len(b.draft_tokens)
        assert b.finish_reason == "length"


def test_a_stop_token_on_either_token_of_a_standing_pair_ends_there(
        drafting, plain, params):
    """Tokens 1 and 3 are a standing pair's FIRST (the second is dropped),
    2 and 4 its second."""
    weights = _standing(params)
    prompt = support.tokens(19, seed=25)
    (free,), _ = _serve(plain, weights, [prompt], n=9)
    tried = 0
    for at in (1, 2, 3, 4):
        stop = free.generated_tokens[at]
        if stop in free.generated_tokens[:at]:
            continue        # the stop token comes earlier in this stream
        tried += 1
        (want,), _ = _serve(plain, weights, [prompt], n=9,
                            stop_token_ids=(stop,))
        (req,), _ = _serve(drafting, weights, [prompt], n=9,
                           stop_token_ids=(stop,))
        assert req.generated_tokens == want.generated_tokens
        assert len(req.generated_tokens) == at + 1
        assert req.finish_reason == want.finish_reason == "stop"
    assert tried >= 2


def test_a_sampled_request_moves_one_token_a_step_and_keeps_its_stream(
        drafting, plain, params):
    """Temperature > 0: the step samples from the first row as a plain step
    would (the same key fold) and the draft never stands."""
    weights = _standing(params)
    (want,), _ = _serve(plain, weights, PROMPTS[:1], n=12, temperature=0.8,
                        seed=3)
    (req,), mtp = _serve(drafting, weights, PROMPTS[:1], n=12,
                         temperature=0.8, seed=3)
    assert req.generated_tokens == want.generated_tokens
    assert (mtp["drafts"], mtp["accepted"], mtp["slot_steps"]) == (0, 0, 11)
    # no draft is verified for it, so none is returned and none stood
    assert req.draft_tokens == [-1] * 12 and req.draft_stood == [False] * 12


def test_the_adaptive_switch_off_ends_drafting_and_keeps_the_stream(
        cfg, plain, params, monkeypatch):
    """``speculative_min_acceptance`` > 0: once enough dispatches have shown
    the drafts falling, the steps are ``decode_scan``'s again, mid-request;
    0 (every other test's engine) keeps the mechanism on whatever stands."""
    monkeypatch.setattr(InferenceEngine, "MTP_SWITCH_OFF_AFTER", 3)
    eng = support.engine(cfg, params, **{
        **DRAFTING, "speculative_min_acceptance": 0.5})
    want, _ = _serve(plain, params, PROMPTS, n=40)
    got, mtp = _serve(eng, params, PROMPTS, n=40)
    for a, b in zip(want, got):
        assert a.generated_tokens == b.generated_tokens
        # the fields keep ``token_ids``' length: -1 for every plain step
        assert len(b.draft_tokens) == len(b.draft_stood) == 40
        assert b.draft_tokens[-1] == -1 and max(b.draft_tokens) >= 0
    stats = eng.stats()
    assert stats["mtp"]["drafting"] is False
    assert 0 < mtp["slot_steps"] < 3 * 39       # the rest were plain steps
    assert stats["compiled_programs"]["decode"] == 1


def test_many_requests_through_few_slots_keep_their_streams(
        drafting, plain, params):
    """More requests than slots, a busy queue (the early hand-back gives a
    slot to its successor before the dispatch is fetched), standing pairs
    that end inside a dispatch: every stream is plain greedy decoding, and
    the slot-step ledger counts a step once whatever it made."""
    weights = _standing(params, leak=0.05)
    prompts = [support.tokens(9 + 3 * i, seed=30 + i) for i in range(9)]
    lengths = [5, 12, 7, 16, 3, 9, 14, 6, 11]
    want, _ = _serve(plain, weights, prompts, n=lengths)
    before = drafting.stats()
    got, mtp = _serve(drafting, weights, prompts, n=lengths)
    for a, b in zip(want, got):
        assert a.generated_tokens == b.generated_tokens
    after = drafting.stats()
    steps = {k: after["slot_steps"][k] - before["slot_steps"][k]
             for k in ("useful", "overrun", "prompt_wait", "empty")}
    assert steps["useful"] == mtp["slot_steps"]
    assert sum(steps.values()) == (
        after["decode_steps"] - before["decode_steps"]) * 3
    assert mtp["tokens"] == sum(lengths) - len(lengths)
    assert after["slot_steps"]["early_handbacks"] > before["slot_steps"][
        "early_handbacks"]


def test_the_server_returns_the_drafts_when_asked(cfg, params):
    """``return_draft_tokens`` on a completion and on a stream's last chunk:
    the draft verified at each position and whether it stood; 400 for
    anything but true / false and on a server that does not draft; the
    counters reach ``/v1/stats``."""
    import asyncio
    import json

    from aiohttp.test_utils import TestClient, TestServer

    from distributed_llm_training_and_inference_system_tpu.serve.server import (
        InferenceServer)
    weights = _standing(params, leak=0.05)
    opts = dict(model="joyai-test", dtype="float32", max_batch_size=2,
                max_seq_len=128, kv_block_size=PS, prefill_chunk=16,
                speculative_min_acceptance=0.0)
    server = InferenceServer(cfg, ServeConfig(speculative="mtp", **opts),
                             params=weights)
    other = InferenceServer(cfg, ServeConfig(**opts), params=weights)
    body = {"prompt": PROMPTS[0], "temperature": 0.0, "max_tokens": 9}

    async def main():
        server.start_engine()
        async with TestClient(TestServer(server.app)) as client:
            post = functools.partial(client.post, "/v1/completions")
            plain = await (await post(json=body)).json()
            asked = await (await post(
                json=dict(body, return_draft_tokens=True))).json()
            streamed = await (await post(json=dict(
                body, return_draft_tokens=True, stream=True))).text()
            bad = await post(json=dict(body, return_draft_tokens=1))
            stats = await (await client.get("/v1/stats")).json()
        async with TestClient(TestServer(other.app)) as client:
            refused = await client.post("/v1/completions", json=dict(
                body, return_draft_tokens=True))
        return plain, asked, streamed, bad.status, refused.status, stats
    try:
        with jax.default_matmul_precision("highest"):
            plain, asked, streamed, bad, refused, stats = asyncio.run(main())
    finally:
        server.stop_engine()
    assert "draft_tokens" not in plain["choices"][0]
    choice = asked["choices"][0]
    assert choice["token_ids"] == plain["choices"][0]["token_ids"]
    assert len(choice["draft_tokens"]) == len(choice["draft_stood"]) == 9
    assert choice["draft_tokens"][0] == -1
    assert choice["draft_stood"] == [
        d == t for d, t in zip(choice["draft_tokens"], choice["token_ids"])]
    assert any(choice["draft_stood"])
    last = [json.loads(line[6:]) for line in streamed.splitlines()
            if line.startswith("data: {")][-1]["choices"][0]
    assert last["draft_tokens"] == choice["draft_tokens"]
    assert bad == 400 and refused == 400
    engine = stats.get("engine", stats)
    assert engine["mtp_slot_steps"] > 0 and engine["mtp"]["drafting"] is True


# -- the chip's share of the experts ---------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(cfg, params):
    """The routed parts the two halves compute (experts 0-3 here, 4-7 on
    the absent chip) plus the shared expert counted ONCE equal the uncut
    layer, in the program and in the reference alike; the module's layer
    (the stacks' last) among them."""
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 9, cfg.hidden_size))
    for layer in (0, cfg.layers_of("E")):
        moe = jax.tree_util.tree_map(lambda a: a[layer],
                                     params["blocks"]["moe"])

        def half(first):
            c = joyai_test_share(4, first)
            held = dict(moe, **{n: {"kernel": moe[n]["kernel"][
                first:first + 4]} for n in ("gate", "up", "down")})
            with_shared, _ = experts_mixer(h, held, c, None, "dropless", None)
            routed, _ = experts_mixer(h, held, dataclasses.replace(
                c, moe=dataclasses.replace(c.moe, shared_expert_size=0)),
                None, "dropless", None)
            return routed, with_shared - routed
        (r0, shared), (r1, shared1) = half(0), half(4)
        uncut, _ = experts_mixer(h, moe, cfg, None, "dropless", None)
        assert np.abs(np.asarray(shared - shared1)).max() < 1e-8
        assert np.abs(np.asarray(r0 + r1 + shared - uncut)).max() < 1e-5
        assert np.abs(np.asarray(r0)).max() > 1e-3 < np.abs(
            np.asarray(r1)).max()
        # the reference's held share is the program's
        stacks = params["blocks"]["moe"]
        held = dict(stacks, **{n: {"kernel": stacks[n]["kernel"][:, :4]}
                               for n in ("gate", "up", "down")})
        want, _, _ = ref.experts(h[0], held, layer, dict(
            C, n_routed_experts=4, router_experts=8, first_expert=0))
        assert np.abs(np.asarray(want) - np.asarray(r0 + shared)[0]).max() \
            < 1e-5


def test_a_chips_share_serves_its_share_of_the_reference(cfg):
    """4 of 8 experts held and half the vocabulary: the engine's tokens and
    drafts are the reference's over the SAME held share."""
    share = joyai_test_share(4, 0, vocab=128)
    weights = _seeded(share, seed=1)
    config = dict(C, n_routed_experts=4, router_experts=8, first_expert=0,
                  vocab_size=128)
    eng = support.engine(share, weights, **DRAFTING)
    prompt = np.random.default_rng(40).integers(3, 128, 21).tolist()
    (req,), _ = _serve(eng, weights, [prompt], n=10)
    _held_to_the_reference(weights, req, config)
    moe = eng.stats()["moe"]
    assert 0.3 < moe["held_choices"] / moe["all_choices"] < 0.7


# -- what is refused, by name ----------------------------------------------------

def test_the_schema_reads_one_module_and_refuses_what_it_cannot_serve():
    assert ModelConfig.from_published(C).mtp_layers == 1
    assert ModelConfig.from_published(
        dict(C, num_nextn_predict_layers=0)).mtp_layers == 0
    with pytest.raises(ConfigError, match="num_nextn_predict_layers = 2: ONE "
                                          "next-token prediction module"):
        ModelConfig.from_published(dict(C, num_nextn_predict_layers=2))
    with pytest.raises(ConfigError, match=r"speculative must be off\|ngram\|mtp"):
        ServeConfig(speculative="medusa").validate()
    cfg = ModelConfig.from_published(C)
    assert (cfg.kv_layers, cfg.moe_layers, cfg.layer_pattern) == (
        4, 3, "*D*E*E")
    published = get_model_config("joyai-llm-flash")
    assert published.mtp_layers == 1 and published.kv_layers == 41
    # (48.9 B without the module: the published "48B")
    assert round(published.param_count / 1e9, 1) == 50.2


def test_mtp_on_a_model_without_a_module_is_refused_by_name(params):
    without = ModelConfig.from_published(dict(C, num_nextn_predict_layers=0))
    with pytest.raises(ValueError, match="speculative: mtp is refused .the "
                                         "model has no next-token prediction"):
        support.engine(without, **DRAFTING)


@pytest.mark.parametrize("serve, match", [
    (dict(speculative="ngram"), "drafts with its prediction module: "
                                "speculative is refused"),
    (dict(preemption="swap", swap_space_gb=0.1),
     "drafts with its prediction module: preemption: swap is refused"),
])
def test_what_self_drafting_refuses_is_refused_by_name(cfg, params, serve,
                                                       match):
    assert set(REFUSED["self_drafting"]) == {
        "riding", "preemption: swap", "measure_device_times", "speculative"}
    with pytest.raises(ValueError, match=match):
        support.engine(cfg, params, **{**DRAFTING, **serve})


def test_riding_is_off_and_counted_and_the_probe_is_refused(cfg, drafting):
    assert not decode.can_carry(cfg)
    assert set(drafting.stats()["mtp"]["refused"]) == {"riding"}
    assert drafting.stats()["mtp"]["drafting"] is True
    with pytest.raises(ValueError, match="measure_device_times is refused"):
        drafting.measure_device_times()
