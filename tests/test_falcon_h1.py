"""Falcon-H1's architecture in small (``falcon-h1-test``): every layer runs
attention heads (a query group of FIVE) AND a Mamba-2 mixer (2 groups) side
by side under ONE norm, then a gated MLP under a second; twelve muP
multipliers, all different from each other and from 1. Against the plain
reference (benchmark/reference/parallel_decoder.py) on seeded NON-trivial
weights, on the CPU.

Covers (ISSUE 49, Tentpole 4): logits of the training forward, of cold
prefill then paged decode through BOTH caches of every layer, and of a
piece riding a decode step, against the reference's full forward; the
gradient of the loss against the reference's ``jax.grad``; a table of
mutations the comparison must fail; the schema's reading of the
``falcon_h1`` keys; ``REFUSED`` asked feature by feature; the parameter and
byte counts of the benchmark's configuration file.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serving_support as support
from benchmark.reference import parallel_decoder
from distributed_llm_training_and_inference_system_tpu.config import get_model_config
from distributed_llm_training_and_inference_system_tpu.config.presets import (
    FALCON_H1_34B_PUBLISHED,
    FALCON_H1_TEST_PUBLISHED as PUBLISHED,
)
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ConfigError,
    ModelConfig,
    MupConfig,
)
from distributed_llm_training_and_inference_system_tpu.models import gpt
from distributed_llm_training_and_inference_system_tpu.serve import (
    Request,
    SamplingParams,
    kv_cache,
)
from distributed_llm_training_and_inference_system_tpu.serve.decode import (
    Piece,
    can_carry,
    decode_step_forward,
)

# Float32 on the CPU with exact float32 matmuls: the program and the
# reference differ in the ORDER of additions alone (the chunked scan sums a
# chunk's contributions in a matmul where the reference steps through t;
# the MLP is one matmul where the reference adds column blocks). Over 2
# published layers of width 64 with logits of size ~2 that is measured 3e-6
# to 6e-6. 1e-4 is far above it and under what the least of the mutations
# moves the logits by (bfloat16 state: 1.2e-3; asserted below).
TOL = 1e-4
PS = 8
ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def cfg():
    return get_model_config("falcon-h1-test")


def seeded(cfg, seed=0):
    """``gpt.init`` with the vectors it leaves trivial made visible: the
    skip ``D``, the gated norm's scale and every layer norm's scale (a
    dropped ``D`` or a unit norm hides behind its own absence)."""
    params = support.params_of(cfg, seed)
    key = jax.random.PRNGKey(seed + 100)

    def uniform(i, like, lo, hi):
        return jax.random.uniform(jax.random.fold_in(key, i), like.shape,
                                  jnp.float32, lo, hi)
    par, mlp = dict(params["blocks"]["par"]), dict(params["blocks"]["mlp"])
    par["D"] = uniform(0, par["D"], 0.5, 1.5)
    par["gate_norm"] = {"scale": uniform(1, par["gate_norm"]["scale"],
                                         -0.5, 0.5)}
    par["norm"] = {"scale": uniform(2, par["norm"]["scale"], -0.3, 0.3)}
    mlp["norm"] = {"scale": uniform(3, mlp["norm"]["scale"], -0.3, 0.3)}
    return dict(params, blocks={"par": par, "mlp": mlp},
                final_norm={"scale": uniform(
                    4, params["final_norm"]["scale"], -0.3, 0.3)})


@pytest.fixture(scope="module")
def params(cfg):
    return seeded(cfg)


def _ref(params, tokens, wrong=None):
    return np.asarray(parallel_decoder.logits(params, tokens, PUBLISHED,
                                              wrong=wrong))


# -- the schema ------------------------------------------------------------------

def test_published_keys_build_the_preset(cfg):
    assert cfg.layer_pattern == "PDPD" and cfg.num_layers == 4
    assert cfg.kv_layers == cfg.ssm_layers == cfg.layers_of("P") == 2
    assert cfg.is_recurrent and not cfg.is_latent and not cfg.is_moe
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (10, 2, 16)
    assert dataclasses.astuple(cfg.ssm) == (8, 8, 16, 2, 4, 16)
    assert cfg.ssm.inner_size == PUBLISHED["mamba_d_ssm"] \
        != PUBLISHED["mamba_expand"] * cfg.hidden_size
    assert cfg.rope.base == 1e11 and cfg.position_embedding == "rope"
    assert cfg.mup == MupConfig(
        embedding=2.5, lm_head=0.6, attention_in=1.3, attention_out=0.8,
        key=1.7, ssm_in=0.7, ssm_out=1.4, ssm=(0.9, 1.2, 0.75, 1.5, 1.1),
        mlp=(0.65, 1.6))
    values = [*dataclasses.astuple(cfg.mup)[:7], *cfg.mup.ssm, *cfg.mup.mlp]
    assert len(set(values)) == 14 and 1.0 not in values


def test_the_published_model_is_read_whole():
    big = ModelConfig.from_published(FALCON_H1_34B_PUBLISHED)
    assert big.layer_pattern == "PD" * 72
    assert big.kv_layers == big.ssm_layers == 72
    assert big.ssm.inner_size == 4096 and big.ssm.state_size == 256
    assert big.mup.attention_in == 1.0 and big.mup.key == pytest.approx(
        0.011048543456039804)
    # 33.6 B parameters: the published count
    assert big.param_count == pytest.approx(33.6e9, rel=0.01)
    # K and V of 4 heads of 128 in every one of the 72 layers
    assert big.kv_bytes_per_token() == 72 * 2 * 4 * 128 * 2


def test_a_model_without_multipliers_has_none(cfg):
    plain = get_model_config("nemotron-h-test")
    assert plain.mup == MupConfig() and gpt._mup_init_std(plain) == {}
    assert set(gpt._mup_init_std(cfg)) == {
        "embed", "q", "k", "v", "o", "in_proj", "out_proj", "gate", "up",
        "down", "lm_head"}


@pytest.mark.parametrize("change,word", [
    ({"mamba_norm_before_gate": True}, "mamba_norm_before_gate"),
    ({"mamba_rms_norm": False}, "mamba_rms_norm"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias"),
    ({"attn_layer_indices": [0]}, "attn_layer_indices"),
    ({"mamba_d_ssm": 128}, "mamba_d_ssm"),
    ({"ssm_multipliers": [1.0, 2.0]}, "ssm_multipliers"),
    ({"mamba_n_groups": 3}, "multiple of ssm.n_groups"),
])
def test_a_file_that_is_not_carried_is_refused(change, word):
    with pytest.raises(ConfigError, match=word):
        ModelConfig.from_published({**PUBLISHED, **change})


@pytest.mark.parametrize("pattern", ["P*", "PM", "PK"])
def test_a_parallel_layer_shares_its_pools_with_no_other_kind(cfg, pattern):
    with pytest.raises(ConfigError, match="P"):
        dataclasses.replace(cfg, layer_pattern=pattern,
                            num_layers=2).validate()


def test_param_count_is_the_tree(cfg, params):
    leaves = jax.tree_util.tree_leaves(params)
    assert cfg.param_count == sum(int(np.prod(a.shape)) for a in leaves)
    par = params["blocks"]["par"]
    assert par["norm"]["scale"].shape == (2, 64)        # ONE norm a layer
    assert par["in_proj"]["kernel"].shape == (2, 64, 2 * 64 + 2 * 32 + 8)


def test_the_benchmarks_configuration_counts_as_the_issue_reckons():
    """benchmark/configs/falcon-h1-34b-4l.json: 4 whole layers, every
    width, the whole vocabulary."""
    config = json.loads((ROOT / "benchmark/configs/falcon-h1-34b-4l.json"
                         ).read_text())
    c = ModelConfig.from_published(config)
    assert c.layer_pattern == "PDPDPDPD"
    layer = (5120 * (2560 + 2 * 512) + 2560 * 5120          # attention
             + 5120 * (4096 + 5120 + 32) + 4096 * 5120      # in, out
             + 5 * 5120 + 3 * 32 + 4096                     # conv, vectors
             + 3 * 5120 * 21504 + 2 * 5120)                 # MLP, two norms
    assert layer == pytest.approx(430.12e6, rel=1e-4)
    assert c.param_count == 4 * layer + 2 * 261120 * 5120 + 5120
    assert 2 * c.param_count == pytest.approx(8.79e9, rel=0.01)
    s = c.ssm
    assert s.num_heads * s.head_dim * s.state_size * 4 == 4_194_304
    assert (s.conv_kernel - 1) * s.conv_channels * 2 == 30_720
    assert c.kv_bytes_per_token() == 8192
    for key, value in FALCON_H1_34B_PUBLISHED.items():
        if key not in ("name", "num_hidden_layers"):
            assert config[key] == value, key


# -- the forward, the gradient, the mutations ------------------------------------

def test_forward_matches_the_reference(cfg, params):
    toks = support.tokens(50)
    with jax.default_matmul_precision("highest"):
        got = support.forward(params, [toks], cfg)[0]
    want = _ref(params, toks)
    assert np.abs(np.asarray(got) - want).max() < TOL
    assert want.std() > 1.0             # logits that say something


def test_the_gradient_is_the_references(cfg, params):
    """The mean next-token loss of one sequence: ``jax.grad`` through the
    program's forward (the chunked scan, both mixers under one norm, every
    multiplier) against ``jax.grad`` through the reference's loop. Float32
    on both sides; leaves are held to 1e-3 of their own largest entry (the
    order of additions again, through a backward pass)."""
    toks = jnp.asarray(support.tokens(40, seed=3))

    def loss_of(logits):
        logp = jax.nn.log_softmax(logits[:-1], -1)
        return -jnp.mean(jnp.take_along_axis(logp, toks[1:, None], -1))

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda p: loss_of(
            gpt.forward(p, toks[None], cfg)[0])))(params)
    want = jax.grad(lambda p: loss_of(
        parallel_decoder.logits(p, toks, PUBLISHED)))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        scale = float(jnp.abs(w).max())
        assert scale > 0, path             # every leaf takes a gradient
        assert float(jnp.abs(g - w).max()) < 1e-3 * scale, path


# the least a mutation must move the logits by, a tenth of what it was
# measured to (a prompt of 50 tokens, seed 0)
MUTATIONS = {
    "drop_attention": 0.8, "drop_ssm": 0.5, "one:embedding": 0.5,
    "one:lm_head": 0.5, "one:attention_in": 0.2, "one:attention_out": 0.1,
    "one:key": 0.2, "one:ssm_in": 0.1, "one:ssm_out": 0.1, "one:ssm0": 0.02,
    "one:ssm1": 0.05, "one:ssm2": 0.005, "one:ssm3": 0.01, "one:ssm4": 0.005,
    "one:mlp0": 0.3, "one:mlp1": 0.2, "norm_before_gate": 0.4,
    "one_group": 0.05, "swap_bc": 0.04, "drop_D": 0.5,
    "bfloat16_state": 5 * TOL, "no_rope": 0.3, "float8": 0.05,
}


@pytest.mark.parametrize("wrong", sorted(MUTATIONS))
def test_the_comparison_fails_each_mutation(cfg, params, wrong):
    """The reference made wrong in ONE way no longer agrees with the
    program: the tolerance sees a dropped branch, each multiplier set to 1,
    the gate moved behind the norm, one group for two, B and C swapped, the
    skip dropped, a bfloat16 state, no rope, float8 operands."""
    toks = support.tokens(50)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(support.forward(params, [toks], cfg)[0])
    moved = np.abs(got - _ref(params, toks, wrong)).max()
    assert moved > MUTATIONS[wrong] > TOL


def test_every_mutation_the_reference_knows_is_held():
    assert set(parallel_decoder.WRONG) == set(MUTATIONS) | {
        "key_multiplier_on_q"}


# -- prefill, then decode, then a riding piece, through both caches --------------

def _pools(cfg, slots=4, n_pages=40):
    shape = (cfg.kv_layers, n_pages, cfg.num_kv_heads, PS, cfg.head_dim)
    s = cfg.ssm
    state = {"conv": jnp.zeros((cfg.ssm_layers, slots, s.conv_kernel - 1,
                                s.conv_channels), jnp.float32),
             "ssm": jnp.zeros((cfg.ssm_layers, slots, s.num_heads,
                               s.head_dim, s.state_size), jnp.float32)}
    return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32), state


def _cold_program(params, padded, live, *, cfg):
    return gpt.forward(
        params, padded, cfg,
        kv_cache=gpt.init_kv_cache(cfg, 1, padded.shape[1],
                                   dtype=jnp.float32),
        cache_offset=jnp.zeros((1,), jnp.int32), segment_ids=live,
        return_ssm_state=True)


def _decode_program(params, toks, pos, kp, vp, table, active, state, ride,
                    *, cfg):
    return decode_step_forward(params, toks, pos, kp, vp, table, cfg,
                               active=active, ssm_state=state, ride=ride)


def _cold_prefill(cfg, params, tokens, bucket, kp, vp, state, pages, slot):
    """What the engine's prefill program does: the dense forward over a
    padded bucket, every layer's K/V scattered into ``pages`` AND the
    slot's rows of both state pools overwritten."""
    n = len(tokens)
    padded = np.full((1, bucket), 7, np.int32)      # garbage padding
    padded[0, :n] = tokens
    live = (jnp.arange(bucket)[None] < n).astype(jnp.int32)
    logits, (kd, vd), (tails, hs) = support.program(_cold_program, cfg)(
        params, jnp.asarray(padded), live)

    def paged(d):
        return d[:, 0].reshape(cfg.kv_layers, bucket // PS, PS,
                               cfg.num_kv_heads, cfg.head_dim
                               ).transpose(0, 1, 3, 2, 4)
    entries = jnp.asarray(pages[:bucket // PS])
    state = {"conv": state["conv"].at[:, slot].set(tails[:, 0]),
             "ssm": state["ssm"].at[:, slot].set(hs[:, 0])}
    return (np.asarray(logits)[0, :n], kp.at[:, entries].set(paged(kd)),
            vp.at[:, entries].set(paged(vd)), state)


TABLE = np.zeros((4, 8), np.int32)
TABLE[1, :7] = [3, 4, 5, 6, 7, 8, 9]
TABLE[2, :8] = [10, 11, 12, 13, 14, 15, 16, 17]


def _decode(cfg, params, tok, pos, kp, vp, state, ride=None):
    """One decode step of four slots of which slot 1 is live."""
    toks = np.full(4, 11, np.int32)                 # idle slots' garbage
    toks[1] = tok
    return support.program(_decode_program, cfg)(
        params, jnp.asarray(toks), jnp.full((4,), pos, jnp.int32), kp, vp,
        jnp.asarray(TABLE), jnp.asarray([False, True, False, False]), state,
        ride)


def test_prefill_then_decode_matches_the_reference(cfg, params):
    """The whole served sequence, position by position: the prompt through
    cold prefill (padded bucket, garbage padding), which writes every
    layer's pages and arms the slot's state, then eight decode steps that
    read and write both pools of every layer. Idle slots' state stays."""
    seq, n = support.tokens(37 + 8, seed=2), 37
    kp, vp, state = _pools(cfg)
    state = jax.tree_util.tree_map(lambda a: a + 0.5, state)   # leftovers
    got = np.zeros((len(seq), cfg.vocab_size), np.float32)
    with jax.default_matmul_precision("highest"):
        got[:n], kp, vp, state = _cold_prefill(
            cfg, params, seq[:n], 48, kp, vp, state, list(TABLE[1, :6]), 1)
        for pos in range(n, len(seq)):
            step = _decode(cfg, params, seq[pos], pos, kp, vp, state)
            kp, vp, state = step.k_pages, step.v_pages, step.state
            got[pos] = np.asarray(step.logits)[1]
    assert np.abs(got - _ref(params, seq)).max() < TOL
    for name in ("conv", "ssm"):
        idle = np.asarray(state[name])[:, [0, 2, 3]]
        assert np.all(idle == 0.5), name
        assert not np.any(np.asarray(state[name])[:, 1] == 0.5)
    # both kinds of state a layer: pages of BOTH layers hold slot 1's rows
    assert np.all(np.abs(np.asarray(kp)[:, 3]).max(axis=(1, 2, 3)) > 0)


def test_the_key_multiplier_sits_on_the_keys(cfg, params):
    """``key_multiplier`` on q instead of k gives the same scores (they are
    bilinear) and the same logits: no comparison of logits can fail it.
    What tells the two apart is what the cache KEEPS: the first layer's
    key rows in the pages are (a W_k) * key_multiplier, rotated."""
    toks = support.tokens(16, seed=4)
    kp, vp, state = _pools(cfg)
    with jax.default_matmul_precision("highest"):
        _, kp, _, _ = _cold_prefill(cfg, params, toks, 16, kp, vp, state,
                                    [3, 4], 1)
    assert np.abs(_ref(params, toks, "key_multiplier_on_q")
                  - _ref(params, toks)).max() < 1e-5
    par = params["blocks"]["par"]
    x = params["embed"]["embedding"][jnp.asarray(toks)] * 2.5
    h = parallel_decoder._norm(x, par["norm"]["scale"][0], eps=1e-5)
    k = jnp.matmul(h * 1.3, par["k"]["kernel"][0],
                   precision=jax.lax.Precision.HIGHEST) * 1.7
    want = np.asarray(parallel_decoder._rope(k.reshape(16, 2, 16), 1e11))
    got = np.asarray(kp)[0, 3:5].transpose(0, 2, 1, 3).reshape(16, 2, 16)
    assert np.abs(got - want).max() < 1e-5
    assert np.abs(got - want / 1.7).max() > 0.1


@pytest.mark.parametrize("n", [16 + 5, 2 * 16, 7],
                         ids=["two pieces", "whole pieces", "one short"])
def test_a_riding_piece_matches_the_reference(cfg, params, n):
    """A prompt of ``n`` tokens rides slot 1's decode steps in pieces of 16
    rows into slot 2: in ONE layer the piece attends over its own slot's
    pages and scans from its own slot's state. The last piece's last live
    row gives the prompt's logits, slot 1's rows stay the reference's, and
    slot 2 then decodes behind the pieces from what they left."""
    assert can_carry(cfg)
    seq, prompt, C = support.tokens(30 + 6, seed=5), support.tokens(n + 3, seed=6), 16
    kp, vp, state = _pools(cfg)
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
            step = _decode(cfg, params, seq[pos], pos, kp, vp, state, piece)
            kp, vp, state = step.k_pages, step.v_pages, step.state
            lg = np.asarray(step.logits)
            assert lg.shape == (5, cfg.vocab_size)
            assert np.abs(lg[1] - want_seq[pos]).max() < TOL
            pos += 1
        assert np.abs(lg[4] - want_prompt[n - 1]).max() < TOL
        # slot 2 decodes behind its pieces, slot 1 beside it
        for j in range(n, n + 3):
            toks = np.full(4, 11, np.int32)
            toks[1], toks[2] = seq[pos], prompt[j]
            step = support.program(_decode_program, cfg)(
                params, jnp.asarray(toks),
                jnp.asarray([0, pos, j, 0], jnp.int32), kp, vp,
                jnp.asarray(TABLE), jnp.asarray([False, True, True, False]),
                state, None)
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
    """Six prompts over four slots (slots are REUSED after a release, and
    the later prompts RIDE the residents' decode steps): every served
    token is the reference's argmax."""
    prompts = [support.tokens(n, seed=s) for s, n in enumerate((36, 20, 36, 20, 36,
                                                         20))]
    with jax.default_matmul_precision("highest"):
        reqs = engine.generate(prompts, SamplingParams(temperature=0.0,
                                                       max_tokens=10))
    for p, r in zip(prompts, reqs):
        assert len(r.generated_tokens) == 10
        assert support.gaps(_ref, params, p,
                            r.generated_tokens).max() == 0.0
    st = engine.stats()
    assert st["ssm"]["state_bytes"] == engine.kv.state_bytes() > 0
    assert st["ssm"]["slot_steps"] > 0
    assert st["ssm"]["prefill_tokens"] == sum(map(len, prompts))
    # K/V pools and state pools both count the SAME two layers
    assert engine.kv.k_pages.shape[0] == engine.kv.state["ssm"].shape[0] == 2


def test_a_prompt_rides_a_busy_engine_to_the_same_tokens(cfg, params):
    """Two residents decode (half the slots): what is admitted next rides
    their dispatches, through both mixers of every layer."""
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
    assert eng.stats()["prefill_ride_tokens"] == 45
    assert support.gaps(_ref, params, prompt,
                        req.generated_tokens).max() == 0.0


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
    assert after["ssm"]["refused"]["prefix_caching"] \
        == before["ssm"]["refused"]["prefix_caching"] + 2


# -- what the model is refused, by what it IS ------------------------------------

@pytest.mark.parametrize("feature,kind", [
    ("chunked_prefill_tokens", "state-space layers"),
    ("speculative", "state-space layers"),
    ("preemption: swap", "state-space layers"),
    ("page payload", "state-space layers"),
    ("fleet prefix fetch", "state-space layers"),
    ("fleet serving", "state-space layers"),
    ("measure_device_times", "state-space layers"),
    ("prefix_caching", "state-space layers"),
])
def test_refused_is_asked_feature_by_feature(cfg, feature, kind):
    """The model is BOTH a K/V model and a recurrent one: every row of the
    ``state_space`` and ``recurrent`` kinds holds for it, with a snapshot
    pool too (its layout has no take / arm pair)."""
    for entries in (0, 8):
        what, why = kv_cache.refused(cfg, feature, entries)
        assert kind in what and why
    with pytest.raises(ValueError, match="is refused"):
        kv_cache.refuse(cfg, feature)


@pytest.mark.parametrize("feature", ["kv_quantization", "tensor_parallel",
                                     "riding"])
def test_what_the_kv_side_allows_stays_allowed(cfg, feature):
    assert kv_cache.refused(cfg, feature) is None


@pytest.mark.parametrize("over,word", [
    ({"chunked_prefill_tokens": 32}, "chunked_prefill_tokens"),
    ({"speculative": "ngram"}, "speculative"),
    ({"preemption": "swap"}, "preemption: swap"),
    ({"quantization": "int8"}, "layer table"),
    ({"tensor_parallel": 2}, "layer table"),
])
def test_the_engine_refuses_by_name(cfg, params, over, word):
    with pytest.raises(ValueError, match=word):
        support.engine(cfg, params, **over)


def test_page_transfers_and_fleets_are_refused_by_name(cfg, params, engine):
    from distributed_llm_training_and_inference_system_tpu.serve.fleet.replica import (
        EngineReplica)
    for call, word in [
            (lambda: engine.kv.extract_slot(0), "extract_slot"),
            (lambda: engine.kv.extract_pages([1]), "extract_pages"),
            (lambda: engine.kv.restore_slot(0, {}), "restore_slot"),
            (lambda: engine.kv.write_slot_pages(0, {}), "write_slot_pages"),
            (lambda: engine.kv.insert_prefix_pages([], {}),
             "insert_prefix_pages"),
            (lambda: setattr(engine, "prefix_fetch_hook", lambda *a: None),
             "prefix fetch"),
            (lambda: engine.measure_device_times(), "measure_device_times"),
            (lambda: EngineReplica(0, cfg, support.serve_config(cfg.name),
                                   params=params),
             "fleet serving is refused")]:
        with pytest.raises(ValueError, match=word):
            call()


# -- a query group of FIVE through the kernels (interpret mode) -------------------

def _paged_inputs(T, seed):
    B, Nq, Nkv, D, NP = 3, 20, 4, 128, 14
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, Nq, D), jnp.float32)
    k_pages = jax.random.normal(ks[1], (NP, Nkv, 16, D), jnp.float32)
    v_pages = jax.random.normal(ks[2], (NP, Nkv, 16, D), jnp.float32)
    tables = jnp.asarray([[3, 7, 1, 2], [4, 5, 6, 8], [9, 10, 11, 0]],
                         jnp.int32)
    return q, k_pages, v_pages, tables


def test_the_decode_page_kernel_at_a_group_of_five():
    """20 query heads over 4 K/V heads of 128 (every accepted configuration
    groups a power of two; five rows are no whole number of the 8
    sublanes): the page-walking kernel in interpret mode against the gather
    path, partial last pages and a length of 1 among the slots."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        paged_attention)
    q, kp, vp, tables = _paged_inputs(1, 0)
    lengths = jnp.asarray([37, 64, 1], jnp.int32)
    want = paged_attention(q[:, 0], kp, vp, tables, lengths, impl="gather")
    got = paged_attention(q[:, 0], kp, vp, tables, lengths, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("T", [8, 24], ids=["one tile", "three pages"])
def test_the_window_page_kernel_at_a_group_of_five(T):
    """The riding piece's window form (``paged_attention_mq``): T queries a
    slot, each seeing the paged prefix and the window's earlier rows."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        paged_attention_multi)
    q, kp, vp, tables = _paged_inputs(T, 1)
    starts = jnp.asarray([13, 40 - T, 0], jnp.int32)
    want = paged_attention_multi(q, kp, vp, tables, starts, impl="gather")
    got = paged_attention_multi(q, kp, vp, tables, starts, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_the_page_writers_at_four_heads_of_a_group_of_five():
    """A token's and a window's K rows reach ``pages[layer, page]`` whole,
    padding to the scratch page, whatever the query group."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        write_window_to_pages)
    _, kp, _, tables = _paged_inputs(1, 2)
    pool = jnp.stack([kp, kp + 1.0])                     # two layers
    rows = jax.random.normal(jax.random.PRNGKey(9), (3, 20, 4, 128))
    starts = jnp.asarray([13, 30, 0], jnp.int32)
    ok = jnp.arange(20)[None] < jnp.asarray([20, 7, 1])[:, None]
    new = np.asarray(write_window_to_pages(pool, rows, tables, starts, ok, 1))
    assert np.array_equal(new[0], np.asarray(pool[0]))   # the other layer
    for b, (start, live) in enumerate(((13, 20), (30, 7), (0, 1))):
        for j in range(live):
            page = int(tables[b, (start + j) // 16])
            np.testing.assert_array_equal(
                new[1, page, :, (start + j) % 16], np.asarray(rows[b, j]))


def test_the_flash_kernel_at_a_group_of_five():
    """The flash kernel (the trainer's attention route; cold prefill attends
    through XLA over a dense cache) at 20 / 4 heads of 128 in interpret
    mode against ``dot_product_attention``."""
    from distributed_llm_training_and_inference_system_tpu.models.layers import (
        attention_mask, dot_product_attention)
    from distributed_llm_training_and_inference_system_tpu.ops.attention import (
        flash_attention)
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (1, 256, 20, 128), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 4, 128), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 4, 128), jnp.float32)
    pos = jnp.arange(256)[None]
    want = dot_product_attention(q, k, v, attention_mask(pos, pos))
    got = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
