"""LFM2-8B-A1B's architecture in small (``lfm2-test``): gated short
convolutions (``conv_L_cache`` 3: a slot's whole state is two rows a
layer) and, one layer in three here, grouped-query attention with heads of
64 (a PAIR of KV heads on the pool's 128 lanes), over two leading dense
MLPs (the ``CDCD`` head of the table) and then 8 sigmoid experts, 2 a
token, picked by score + bias. Against the plain reference
(benchmark/reference/shortconv_decoder.py) on seeded NON-trivial weights,
on the CPU.

Covers (ISSUE 55, Tentpole 4): the catalog row -> the 48-entry table and
the cut's 32; the mixer against the reference over a whole sequence;
cold prefill then paged decode through K/V pages AND the conv pool against
the reference's full forward (logits), with a prompt split across chunk
boundaries, a riding piece, and a slot reused after a longer request; the
bias that changes a pick and not a weight; the tied head; the engine end
to end; ``REFUSED`` asked feature by feature.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serving_support as support
from benchmark.reference import shortconv_decoder
from distributed_llm_training_and_inference_system_tpu.config import get_model_config
from distributed_llm_training_and_inference_system_tpu.config.presets import (
    LFM2_8B_A1B_PUBLISHED,
    LFM2_TEST_PUBLISHED as PUBLISHED,
)
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ConfigError,
    ModelConfig,
)
from distributed_llm_training_and_inference_system_tpu.models import gpt, layers
from distributed_llm_training_and_inference_system_tpu.ops import shortconv
from distributed_llm_training_and_inference_system_tpu.serve import (
    Request,
    SamplingParams,
    kv_cache,
)
from distributed_llm_training_and_inference_system_tpu.serve.decode import (
    Piece,
    can_carry,
    decode_step_forward,
    extend_step_forward,
    recurrent_ops,
)

# Float32 on the CPU with exact float32 matmuls: the program and the
# reference differ in the ORDER of additions alone (the dense MLP is one
# matmul where the reference adds column blocks; the experts are a grouped
# matmul where the reference adds an expert at a time). Over 6 decoder
# layers of width 256 with logits of size ~5 that is measured 3e-6 to
# 8e-6. 1e-4 is far above it and far under what bfloat16 anywhere moves
# the logits by (the stream rounded once: 2e-2), and under the least of the
# mutations (asserted below).
TOL = 1e-4
PS = 8
ROOT = Path(__file__).resolve().parents[1]
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


@pytest.fixture(scope="module")
def cfg():
    return get_model_config("lfm2-test")


def seeded(cfg, seed=0):
    """``gpt.init`` with the vectors it leaves trivial made visible: every
    norm's scale (q / k head norms too) and the experts' selection bias,
    at +-0.05, where it changes which experts are picked. (The taps are
    seeded asymmetrically by ``gpt.init`` itself: uniform a tap.)"""
    params = support.params_of(cfg, seed)
    key = jax.random.PRNGKey(seed + 100)
    count = iter(range(1000))

    def uniform(like, lo, hi):
        return jax.random.uniform(jax.random.fold_in(key, next(count)),
                                  like.shape, jnp.float32, lo, hi)

    def visible(path, leaf):
        names = tuple(k.key for k in path)
        if names[-1] == "scale":
            return uniform(leaf, -0.3, 0.3)
        if names[-2:] == ("router", "bias"):
            return uniform(leaf, -0.05, 0.05)
        return leaf
    return jax.tree_util.tree_map_with_path(visible, params)


@pytest.fixture(scope="module")
def params(cfg):
    return seeded(cfg)


def _ref(params, tokens, wrong=None):
    with jax.default_matmul_precision("highest"):
        return np.asarray(shortconv_decoder.logits(params, tokens, PUBLISHED,
                                                   wrong=wrong))


# -- the schema's reading of the row's keys ----------------------------------

CUT_TABLE = "CDCD*ECE" + "CECE*ECE" * 3
FULL_TABLE = CUT_TABLE + "CECE*ECE" + "CE*ECECE"


def _row():
    if not CATALOG.exists():
        pytest.skip("the catalog of architectures is not on this machine")
    for line in CATALOG.read_text().splitlines():
        row = json.loads(line)
        if row["name"] == "LFM2-8B-A1B":
            return row
    pytest.fail("the catalog has no LFM2-8B-A1B row")


def test_the_catalog_row_builds_the_48_entry_table_and_the_cut_32():
    config = _row()["config"]
    full = ModelConfig.from_published(config)
    assert full.layer_pattern == FULL_TABLE and full.num_layers == 48
    assert (full.conv_layers, full.kv_layers, full.layers_of("D"),
            full.moe_layers) == (18, 6, 2, 22)
    cut = ModelConfig.from_published({
        **config, "num_hidden_layers": 16,
        "layer_types": config["layer_types"][:16]})
    assert cut.layer_pattern == CUT_TABLE and cut.num_layers == 32
    assert (cut.conv_layers, cut.kv_layers, cut.moe_layers) == (12, 4, 14)
    for m in (full, cut):
        assert (m.head_dim, m.num_heads, m.num_kv_heads) == (64, 32, 8)
        assert m.qk_norm == "head" and m.shortconv_kernel == 3
        assert (m.ffn_size, m.dense_ffn_size) == (1792, 7168)
        assert (m.moe.num_experts, m.moe.experts_per_token) == (32, 4)
        assert m.moe.router_score == "sigmoid" and m.moe.selection_bias
        assert m.moe.norm_topk_prob and m.moe.routed_scaling_factor == 1.0
        assert m.rope.base == 1e6 and m.norm_eps == 1e-5
        assert m.recurrent_kind == "C" and m.is_recurrent
        assert m.recurrent_name == "gated short-convolution (C) layers"


def test_the_preset_is_the_row_and_the_family_ties_its_embeddings():
    config = _row()["config"]
    for key, value in config.items():
        assert LFM2_8B_A1B_PUBLISHED[key] == value, key
    m = get_model_config("lfm2-8b-a1b")
    assert m.tie_word_embeddings and m.layer_pattern == FULL_TABLE
    # 8.3B as published: one table, not the two a rough count takes
    assert 8.30e9 < m.param_count < 8.40e9


@pytest.mark.parametrize("change,word", [
    ({"conv_bias": True}, "conv_bias"),
    ({"layer_types": ["conv", "window", "conv", "conv", "conv",
                      "full_attention"]}, "window"),
    ({"layer_types": ["conv"] * 5}, "layer_types"),
    ({"layer_pattern": "CDX*", "num_hidden_layers": 4}, "no layer kind"),
])
def test_a_file_that_is_not_carried_is_refused(change, word):
    with pytest.raises(ConfigError, match=word):
        ModelConfig.from_published({**PUBLISHED, **change})


def test_one_recurrent_kind_a_model(cfg):
    for pattern, word in (("CDKD", "K and C"), ("CDMD", "M and C")):
        with pytest.raises(ConfigError, match=word):
            dataclasses.replace(
                cfg, layer_pattern=pattern, num_layers=4,
                ssm=dataclasses.replace(cfg.ssm, num_heads=4),
                kda=dataclasses.replace(cfg.kda, num_heads=4)).validate()


def test_param_count_is_the_tree(cfg, params):
    n = sum(int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(params))
    assert cfg.param_count == n


def test_the_periodic_part_of_the_cut_table():
    """``CDCD*ECE`` does not repeat; ``CECE*ECE`` x 3 does: a riding
    program's bodies walk the head by a Python loop and the rest by a
    loop over traced layer indices."""
    cut = ModelConfig.from_published({
        **LFM2_8B_A1B_PUBLISHED, "num_hidden_layers": 16,
        "layer_types": LFM2_8B_A1B_PUBLISHED["layer_types"][:16]})
    head, unit, reps = gpt.table_period(cut)
    assert "".join(k for k, _ in head) == "CDCD*ECE"
    assert "".join(k for k, _ in unit) == "CECE*ECE" and reps == 3
    assert unit[0] == ("C", 3) and unit[4] == ("*", 1)
    assert gpt.table_period_and_tail(cut) == (head, unit, reps, [])
    # the whole model's table does not END in its period (the last
    # attention layer comes a layer early): head, four repetitions, and the
    # rest as a tail, every layer once and in order
    full = get_model_config("lfm2-8b-a1b")
    assert len(gpt.table_period(full)[0]) == 36     # `*ECECE` x 2 alone
    head, unit, reps, tail = gpt.table_period_and_tail(full)
    assert "".join(k for k, _ in head) == "CDCD" and reps == 4
    assert "".join(k for k, _ in unit) == "*ECECECE"
    assert "".join(k for k, _ in tail) == "*ECECE*ECECE"
    per_rep = {k: sum(u == k for u, _ in unit) for k in "C*E"}
    walked = head + [(k, i + r * per_rep[k]) for r in range(reps)
                     for k, i in unit] + tail
    assert walked == gpt.table_layers(full)


# -- the mixer, and the forward, against the reference ----------------------------

def test_the_mixer_matches_the_reference_over_a_whole_sequence(cfg, params):
    """One ``C`` layer alone over 40 positions: the gates, the three taps
    in their order, zeros before the sequence, no activation."""
    c = jax.tree_util.tree_map(lambda a: a[1], params["blocks"]["conv"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        h = layers.rms_norm(x, c["norm"]["scale"], cfg.norm_eps)
        out, (tail,) = layers.shortconv_mixer(
            h, c, cfg, shortconv.recur_window(cfg))
        want = shortconv_decoder._shortconv(
            x[0], {"norm": c["norm"]["scale"],
                   "in_proj": c["in_proj"]["kernel"],
                   "conv": c["conv"]["kernel"],
                   "out_proj": c["out_proj"]["kernel"]},
            eps=cfg.norm_eps, float8=False, swap_bc=False,
            taps_reversed=False, stale_window=False, rope_heads=0,
            theta=1e6, drop_conv=False) - x[0]
    assert np.abs(np.asarray(out[0]) - np.asarray(want)).max() < 1e-5
    # the state is the last two z rows: B * u of positions 38 and 39
    bcu = np.asarray(h[0] @ c["in_proj"]["kernel"])
    H = cfg.hidden_size
    assert np.abs(np.asarray(tail[0]) - (bcu[:, :H] * bcu[:, 2 * H:])[38:]
                  ).max() < 1e-5
    # asymmetric taps: reversed they are another filter
    k = np.asarray(c["conv"]["kernel"])
    assert np.abs(k - k[::-1]).max() > 0.1


def test_forward_matches_the_reference(cfg, params):
    toks = support.tokens(50, seed=1)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(support.forward(params, [toks], cfg))[0]
    assert np.abs(got - _ref(params, toks)).max() < TOL


def test_the_head_is_the_embeddings_transpose(cfg, params):
    assert "lm_head" not in params
    toks = support.tokens(12, seed=2)
    x = shortconv_decoder.hidden(params, toks, PUBLISHED)[0]
    x = shortconv_decoder._norm(x, params["final_norm"]["scale"], eps=1e-5)
    want = np.asarray(jnp.matmul(x, params["embed"]["embedding"].T,
                                 precision=jax.lax.Precision.HIGHEST))
    assert np.abs(_ref(params, toks) - want).max() < 1e-5


def test_the_bias_changes_a_pick_and_not_a_weight(cfg, params):
    """A large bias on one expert makes every token pick it; the weights
    stay the chosen SCORES over their sum (the bias never enters them)."""
    moe = params["blocks"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (24, cfg.hidden_size))
    bias = jnp.zeros((8,)).at[5].set(10.0)
    scores, w0, e0 = layers.moe_route(x, moe["router"]["kernel"][0], cfg,
                                      jnp.zeros((8,)))
    _, w1, e1 = layers.moe_route(x, moe["router"]["kernel"][0], cfg, bias)
    assert np.all(np.asarray(e1)[:, 0] == 5)
    assert not np.all(np.any(np.asarray(e0) == 5, axis=1))
    picked = np.take_along_axis(np.asarray(scores), np.asarray(e1), 1)
    assert np.allclose(np.asarray(w1), picked / picked.sum(1, keepdims=True),
                       atol=1e-6)
    assert np.allclose(np.asarray(w1).sum(1), 1.0, atol=1e-6)


# a mutation of the reference the comparison must fail, and a floor under
# how far it moves the logits (measured: 0.098 to 0.42, but for the bias
# used as a weight, 0.0082: a bias of +-0.05 among scores that sum to ~1)
MUTATIONS = {"swap_bc": 0.1, "taps_reversed": 0.1, "stale_window": 0.05,
             "no_expert_bias": 0.05, "bias_as_weight": 4e-3,
             "no_qk_norm": 0.1, "rope_on_conv": 0.1, "no_rope": 0.1,
             "drop_conv": 0.1, "float8": 0.05}


@pytest.mark.parametrize("wrong", sorted(MUTATIONS))
def test_the_comparison_fails_each_mutation(cfg, params, wrong):
    toks = support.tokens(50, seed=1)
    moved = np.abs(_ref(params, toks, wrong) - _ref(params, toks)).max()
    assert moved > MUTATIONS[wrong] > 10 * TOL


def test_every_mutation_the_reference_knows_is_held():
    assert set(shortconv_decoder.WRONG) == set(MUTATIONS)


# -- prefill, then decode, chunks, a riding piece: pages AND the conv pool -------

SLOTS = 4
TABLE = np.zeros((SLOTS, 10), np.int32)
TABLE[1, :9] = range(3, 12)
TABLE[2, :10] = range(12, 22)


def _pools(cfg, n_pages=40):
    k = kv_cache.PagedKVCache(cfg, SLOTS, 80, page_size=PS, num_pages=n_pages,
                              dtype=jnp.float32)
    return k.k_pages, k.v_pages, k.state


def test_the_pools_are_pairs_of_heads_and_two_rows_a_slot(cfg):
    kp, vp, state = _pools(cfg)
    # 2 KV heads of 64 = ONE pair of 128 lanes; 2 attention layers
    assert kp.shape == vp.shape == (2, 40, 1, PS, 128)
    # the conv pool ALONE: [C layers, K-1, slots, H]
    assert set(state) == {"conv"}
    assert state["conv"].shape == (4, 2, SLOTS, cfg.hidden_size)
    assert recurrent_ops(cfg) is shortconv


def _cold_program(params, padded, live, *, cfg):
    return gpt.forward(
        params, padded, cfg,
        kv_cache=gpt.init_kv_cache(cfg, 1, padded.shape[1],
                                   dtype=jnp.float32),
        cache_offset=jnp.zeros((1,), jnp.int32), segment_ids=live,
        return_moe_stats=True, return_ssm_state=True)


def _chunk_program(params, rows, start, kp, vp, table, ok, state, slot, *,
                   cfg):
    return extend_step_forward(params, rows, start, kp, vp, table, cfg,
                               write_ok=ok, ssm_state=state, state_slot=slot)


def _decode_program(params, toks, pos, kp, vp, table, active, state, ride,
                    *, cfg):
    return decode_step_forward(params, toks, pos, kp, vp, table, cfg,
                               active=active, ssm_state=state, ride=ride)


def _cold_prefill(cfg, params, tokens, bucket, kp, vp, state, pages, slot):
    """What the engine's prefill program does: the dense forward over a
    padded bucket, every attention layer's K/V laid out as pages AND the
    slot's rows of the conv pool overwritten."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        write_prompt_to_pages)
    n = len(tokens)
    padded = np.full((1, bucket), 7, np.int32)      # garbage padding
    padded[0, :n] = tokens
    live = (jnp.arange(bucket)[None] < n).astype(jnp.int32)
    logits, cache, stats, windows = support.program(_cold_program, cfg)(
        params, jnp.asarray(padded), live)
    kp, vp = write_prompt_to_pages((kp, vp), cache,
                                   jnp.asarray(pages[:bucket // PS]))
    state = dict(zip(state, shortconv.arm_slot_state(
        *state.values(), slot, *windows)))
    return np.asarray(logits)[0, :n], kp, vp, state


def _decode(cfg, params, toks, pos, kp, vp, state, active, ride=None):
    return support.program(_decode_program, cfg)(
        params, jnp.asarray(toks, jnp.int32), jnp.asarray(pos, jnp.int32),
        kp, vp, jnp.asarray(TABLE), jnp.asarray(active), state, ride)


def _decode_one(cfg, params, tok, pos, kp, vp, state, ride=None):
    """One decode step of four slots of which slot 1 is live."""
    toks = np.full(SLOTS, 11, np.int32)                 # idle slots' garbage
    toks[1] = tok
    return _decode(cfg, params, toks, np.full(SLOTS, pos), kp, vp, state,
                   [False, True, False, False], ride)


def test_prefill_then_decode_matches_the_reference(cfg, params):
    """The whole served sequence, position by position: the prompt through
    cold prefill (padded bucket, garbage padding), which writes the
    attention layers' pages and arms the slot's windows, then eight decode
    steps that read and write both. Idle slots' windows stay."""
    seq, n = support.tokens(37 + 8, seed=2), 37
    kp, vp, state = _pools(cfg)
    state = {"conv": state["conv"] + 0.5}                    # leftovers
    got = np.zeros((len(seq), cfg.vocab_size), np.float32)
    with jax.default_matmul_precision("highest"):
        got[:n], kp, vp, state = _cold_prefill(
            cfg, params, seq[:n], 48, kp, vp, state, list(TABLE[1, :6]), 1)
        for pos in range(n, len(seq)):
            step = _decode_one(cfg, params, seq[pos], pos, kp, vp, state)
            kp, vp, state = step.k_pages, step.v_pages, step.state
            got[pos] = np.asarray(step.logits)[1]
    assert np.abs(got - _ref(params, seq)).max() < TOL
    assert set(state) == {"conv"}
    idle = np.asarray(state["conv"])[:, :, [0, 2, 3]]
    assert np.all(idle == 0.5)
    assert not np.any(np.asarray(state["conv"])[:, :, 1] == 0.5)


def test_a_prompt_split_across_chunks_matches_the_reference(cfg, params):
    """A prompt of 37 tokens as chunks of 16, 16 and 5 rows through the
    chunk program's forward (a window of ONE slot, ``state_slot``): each
    chunk reads the slot's two rows, and a chunk boundary carries them.
    The first chunk starts its sequence and reads ZEROS, whatever the slot
    held."""
    seq, n = support.tokens(37 + 3, seed=7), 37
    kp, vp, state = _pools(cfg)
    state = {"conv": state["conv"] + 0.5}                    # leftovers
    want = _ref(params, seq)
    with jax.default_matmul_precision("highest"):
        for start in range(0, n, 16):
            live = min(16, n - start)
            rows = np.full((1, 16), 9, np.int32)
            rows[0, :live] = seq[start:start + live]
            step = support.program(_chunk_program, cfg)(
                params, jnp.asarray(rows), jnp.asarray([start], jnp.int32),
                kp, vp, jnp.asarray(TABLE[1:2]),
                (jnp.arange(16) < live)[None], state, jnp.int32(1))
            kp, vp, state = step.k_pages, step.v_pages, step.state
            got = np.asarray(step.logits)[0, :live]
            assert np.abs(got - want[start:start + live]).max() < TOL
        for pos in range(n, len(seq)):
            step = _decode_one(cfg, params, seq[pos], pos, kp, vp, state)
            kp, vp, state = step.k_pages, step.v_pages, step.state
            assert np.abs(np.asarray(step.logits)[1] - want[pos]).max() < TOL
    assert np.all(np.asarray(state["conv"])[:, :, [0, 2, 3]] == 0.5)


@pytest.mark.parametrize("n", [16 + 5, 2 * 16, 1],
                         ids=["two pieces", "whole pieces", "one token"])
def test_a_riding_piece_matches_the_reference(cfg, params, n):
    """A prompt of ``n`` tokens rides slot 1's decode steps in pieces of 16
    rows into slot 2: the piece attends over its own slot's pages (pairs
    of heads) and its conv runs from its own slot's two rows. The last
    piece's last live row gives the prompt's logits, slot 1's rows stay the
    reference's, and slot 2 then decodes behind the pieces from what they
    left (a prompt of ONE token leaves a zero row before its own)."""
    assert can_carry(cfg)
    seq, prompt, C = support.tokens(30 + 6, seed=5), support.tokens(n + 3, seed=6), 16
    kp, vp, state = _pools(cfg)
    state = {"conv": state["conv"] + 0.5}           # slot 2's former occupant
    want_seq, want_prompt = _ref(params, seq), _ref(params, prompt)
    with jax.default_matmul_precision("highest"):
        _, kp, vp, state = _cold_prefill(
            cfg, params, seq[:30], 32, kp, vp, state, list(TABLE[1, :4]), 1)
        pos = 30
        for start in range(0, n, C):
            live = min(C, n - start)
            rows = np.full(C, 9, np.int32)          # garbage past the live
            rows[:live] = prompt[start:start + live]
            piece = Piece(jnp.int32(2), jnp.int32(start), jnp.int32(live),
                          jnp.int32(0), jnp.asarray(rows))
            step = _decode_one(cfg, params, seq[pos], pos, kp, vp, state,
                               piece)
            kp, vp, state = step.k_pages, step.v_pages, step.state
            lg = np.asarray(step.logits)
            assert lg.shape == (SLOTS + 1, cfg.vocab_size)
            assert np.abs(lg[1] - want_seq[pos]).max() < TOL
            pos += 1
        assert np.abs(lg[SLOTS] - want_prompt[n - 1]).max() < TOL
        # slot 2 decodes behind its pieces, slot 1 beside it
        for j in range(n, n + 3):
            toks = np.full(SLOTS, 11, np.int32)
            toks[1], toks[2] = seq[pos], prompt[j]
            step = _decode(cfg, params, toks, [0, pos, j, 0], kp, vp, state,
                           [False, True, True, False])
            kp, vp, state = step.k_pages, step.v_pages, step.state
            lg = np.asarray(step.logits)
            assert np.abs(lg[1] - want_seq[pos]).max() < TOL
            assert np.abs(lg[2] - want_prompt[j]).max() < TOL
            pos += 1


# -- the engine ------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine(cfg, params):
    return support.engine(cfg, params)


def test_engine_serves_the_references_tokens(cfg, params, engine):
    """Eight prompts over four slots: slots are REUSED after a release (a
    short prompt after a longer request: its windows start from zeros) and
    the later prompts RIDE the residents' decode steps. Every served token
    is the reference's argmax (or within float32 noise of it)."""
    prompts = [support.tokens(n, seed=s) for s, n in enumerate(
        (36, 20, 36, 20, 3, 1, 36, 2))]
    with jax.default_matmul_precision("highest"):
        reqs = engine.generate(prompts, SamplingParams(temperature=0.0,
                                                       max_tokens=10))
    for p, r in zip(prompts, reqs):
        assert len(r.generated_tokens) == 10
        assert support.gaps(_ref, params, p, r.generated_tokens).max() < TOL
    st = engine.stats()
    assert "ssm" not in st and "kda" not in st
    assert st["shortconv"]["state_bytes"] == engine.kv.state_bytes() \
        == 4 * 2 * 4 * cfg.hidden_size * 4
    assert st["shortconv"]["slot_steps"] > 0
    assert st["shortconv"]["prefill_tokens"] == sum(map(len, prompts))
    assert set(engine.kv.state) == {"conv"}
    assert st["moe"]["decode_experts_hit"] > 0


def test_a_prompt_rides_a_busy_engine_to_the_same_tokens(cfg, params):
    """Two residents decode (half the slots): what is admitted next rides
    their dispatches in pieces, through the conv layers' windows and the
    attention layers' pages; past its first piece a piece reads the
    slot's state (counted)."""
    eng = support.engine(cfg, params)
    long = SamplingParams(temperature=0.0, max_tokens=40)
    with jax.default_matmul_precision("highest"):
        for i, n in enumerate((9, 13)):
            assert eng.scheduler.add_request(Request(
                f"resident-{i}", support.tokens(n, seed=20 + i), long))
        while eng.active.sum() < 2:
            eng.step()
        prompt = support.tokens(45, seed=30)
        req = Request("rider", prompt, SamplingParams(temperature=0.0,
                                                      max_tokens=8))
        assert eng.scheduler.add_request(req)
        eng.run_until_idle()
    st = eng.stats()
    assert st["prefill_ride_tokens"] == 45
    assert st["shortconv"]["state_carry_chunks"] >= 1
    assert support.gaps(_ref, params, prompt,
                        req.generated_tokens).max() < TOL


def test_chunked_prefill_carries_the_windows(cfg, params):
    """``chunked_prefill_tokens``: a long prompt goes through the chunk
    programs, each chunk behind the two rows the last one left."""
    eng = support.engine(cfg, params, chunked_prefill_tokens=16)
    prompt = support.tokens(53, seed=31)
    with jax.default_matmul_precision("highest"):
        req, = eng.generate([prompt], SamplingParams(temperature=0.0,
                                                     max_tokens=6))
    assert eng.stats()["shortconv"]["state_carry_chunks"] >= 2
    assert support.gaps(_ref, params, prompt,
                        req.generated_tokens).max() < TOL


def test_a_repeated_prompt_is_prefilled_again(engine):
    """Prefix reuse by page hash is ON by default and wrong for a layer
    with a recurrent state: turned off and counted."""
    prompt = support.tokens(36, seed=5)
    before = engine.stats()
    sp = SamplingParams(temperature=0.0, max_tokens=4)
    a, = engine.generate([prompt], sp)
    b, = engine.generate([prompt], sp)
    after = engine.stats()
    assert a.generated_tokens == b.generated_tokens
    assert after["prefill_tokens"] - before["prefill_tokens"] == 72
    assert after["prefix_cached_tokens"] == 0
    assert after["shortconv"]["refused"]["prefix_caching"] \
        == before["shortconv"]["refused"]["prefix_caching"] + 2


# -- what the model is refused, by what it IS ------------------------------------

@pytest.mark.parametrize("feature", [
    "speculative", "preemption: swap", "page payload", "fleet prefix fetch",
    "fleet serving", "measure_device_times", "prefix_caching"])
def test_refused_is_asked_feature_by_feature(cfg, feature):
    """Every row of the ``recurrent`` kind holds for a ``C`` model, with a
    snapshot pool too (its layout has no take / arm pair), under its own
    name."""
    for entries in (0, 8):
        what, why = kv_cache.refused(cfg, feature, entries)
        assert "gated short-convolution (C) layers" in what and why
    with pytest.raises(ValueError, match="is refused"):
        kv_cache.refuse(cfg, feature)


@pytest.mark.parametrize("feature", ["chunked_prefill_tokens", "riding",
                                     "tensor_parallel"])
def test_what_a_conv_window_allows_stays_allowed(cfg, feature):
    """A chunk or a piece carries the two rows (``recur_chunk``): neither
    is refused, as for a ``K`` model and unlike an ``M`` one."""
    assert kv_cache.refused(cfg, feature) is None


@pytest.mark.parametrize("over,word", [
    ({"speculative": "ngram"}, "speculative"),
    ({"preemption": "swap"}, "preemption: swap"),
])
def test_the_engine_refuses_by_name(cfg, params, over, word):
    with pytest.raises(ValueError, match=word):
        support.engine(cfg, params, **over)
