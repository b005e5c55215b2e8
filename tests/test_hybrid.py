"""Nemotron-3-Nano's architecture in small (``nemotron-h-test``): a layer
table of Mamba-2 state-space mixers, GQA attention without rope and
sigmoid-routed squared-ReLU experts of which this chip holds half, against
the plain reference (benchmark/reference/hybrid_decoder.py) on seeded
NON-trivial weights, on the CPU.

Covers (ISSUE 31, Tentpole 4): logits of prefill-then-decode through the
serve programs against the reference's full forward; the chunked scan
against the recurrence across chunk and bucket boundaries and with padding;
the share test of the model-configs guide; a slot reused after release; the
refusal, by name, of every serving feature a recurrent state cannot follow.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serving_support as support
from benchmark.reference import hybrid_decoder
from distributed_llm_training_and_inference_system_tpu.config import get_model_config
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ConfigError,
    ModelConfig,
    ServeConfig,
)
from distributed_llm_training_and_inference_system_tpu.models import gpt
from distributed_llm_training_and_inference_system_tpu.models.layers import (
    experts_mixer,
    moe_route,
)
from distributed_llm_training_and_inference_system_tpu.ops import moe_gmm, ssm
from distributed_llm_training_and_inference_system_tpu.serve import (
    InferenceEngine,
    SamplingParams,
)
from distributed_llm_training_and_inference_system_tpu.serve.decode import (
    decode_step_forward,
    extend_step_forward,
)

# Float32 on the CPU with exact float32 matmuls: the program and the
# reference differ in the ORDER of additions alone (the chunked scan sums a
# chunk's contributions in a matmul where the reference steps through t;
# the routed experts' outputs are reduced at once where the reference adds
# expert after expert). Over 7 layers of width 64 with logits of size ~0.6
# that is measured 2e-7 to 6e-7. 1e-4 is far above it and far under what
# any missing piece moves the logits by (asserted below: the least of the
# wrong models moves them 4e-3).
TOL = 1e-4
STATE_TOL = 4e-6

PUBLISHED = {   # the keys of a published nemotron_h config.json, tiny values
    "name": "nemotron-h-test", "model_type": "nemotron_h",
    "num_hidden_layers": 7, "hybrid_override_pattern": "MEMEM*E",
    "hidden_size": 64, "intermediate_size": 32, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 48, "n_shared_experts": 1,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "max_position_embeddings": 256,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 16,
    "n_routed_experts": 4, "router_experts": 8, "first_expert": 0,
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "mlp_hidden_act": "relu2",
    "layer_norm_epsilon": 1e-5, "rope_theta": 10000,
    "position_embedding": "none", "tie_word_embeddings": False,
    "dtype": "float32"}
PS = 8


@pytest.fixture(scope="module")
def cfg():
    return get_model_config("nemotron-h-test")


def seeded(cfg, seed=0):
    """Seeded weights with every vector the init leaves trivial made
    NON-trivial: norm scales, the gated norm's scale, ``D``, the selection
    bias (a zero bias or a unit norm hides its own absence)."""
    p = support.params_of(cfg, seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))
    b = p["blocks"]

    def uniform(like, lo, hi):
        return jax.random.uniform(next(keys), like.shape, jnp.float32, lo, hi)
    for kind in ("ssm", "attn", "moe"):
        b[kind]["norm"]["scale"] = uniform(b[kind]["norm"]["scale"], -.3, .3)
    b["ssm"]["gate_norm"]["scale"] = uniform(b["ssm"]["gate_norm"]["scale"],
                                             -.5, .5)
    b["ssm"]["D"] = uniform(b["ssm"]["D"], .5, 1.5)
    b["moe"]["router"]["bias"] = uniform(b["moe"]["router"]["bias"], -.2, .2)
    # a router sharp enough that the scores differ beside the bias, and
    # queries and keys large enough that attention is not uniform (at
    # width 64 a 0.02 init gives scores of 0.03: no fault in them shows)
    b["moe"]["router"]["kernel"] = b["moe"]["router"]["kernel"] * 20.0
    for n in ("q", "k"):
        b["attn"][n]["kernel"] = b["attn"][n]["kernel"] * 8.0
    return p


@pytest.fixture(scope="module")
def params(cfg):
    return seeded(cfg)


def _ref(params, tokens, wrong=None, **over):
    return np.asarray(hybrid_decoder.logits(
        params, tokens, dict(PUBLISHED, **over), wrong=wrong,
        prompt_len=over.get("prompt_len", 0), pad_to=over.get("pad_to", 0)))


def _pools(cfg, slots=4, n_pages=24, state_dtype=jnp.float32):
    shape = (cfg.kv_layers, n_pages, cfg.num_kv_heads, PS, cfg.head_dim)
    s = cfg.ssm
    return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32),
            {"conv": jnp.zeros((cfg.ssm_layers, slots, s.conv_kernel - 1,
                                s.conv_channels), jnp.float32),
             "ssm": jnp.zeros((cfg.ssm_layers, slots, s.num_heads,
                               s.head_dim, s.state_size), state_dtype)})


# -- the configuration ---------------------------------------------------------

def test_published_keys_build_the_preset(cfg):
    built = ModelConfig.from_dict(PUBLISHED)
    assert built == cfg
    assert (cfg.ssm_layers, cfg.kv_layers, cfg.moe_layers) == (3, 1, 3)
    assert gpt.table_layers(cfg) == [("M", 0), ("E", 0), ("M", 1), ("E", 1),
                                     ("M", 2), ("*", 0), ("E", 2)]
    assert cfg.is_recurrent and not cfg.moe.holds_all
    assert cfg.moe.router_width == 8 and cfg.moe.stats_size == 4 + 2


@pytest.mark.parametrize("name", ["nemotron-h-test"])
def test_param_count_is_the_tree(name):
    c = get_model_config(name)
    shapes = jax.eval_shape(lambda k: gpt.init(c, k), jax.random.PRNGKey(0))
    assert c.param_count == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))


def test_param_count_at_the_published_sizes():
    """ISSUE 31's cross-check: 23 M x 38.74 M + 6 * x 23.40 M + 23 E x
    (128 x 9.978 M + 19.96 M + 0.34 M) + 2 x 131,072 x 2,688 = 31.58 B."""
    c = get_model_config("nemotron-3-nano-30b-a3b")
    assert (c.ssm_layers, c.kv_layers, c.moe_layers) == (23, 6, 23)
    assert round(c.param_count / 1e9, 2) == 31.58


@pytest.mark.parametrize("change,word", [
    ({"hybrid_override_pattern": "MEMEM-E"}, "no layer kind"),
    ({"hybrid_override_pattern": "MEM"}, "num_layers"),
    ({"first_expert": 6}, "held experts"),
    ({"mamba_num_heads": 7}, "multiple of ssm.n_groups"),
    ({"position_embedding": "alibi"}, "position_embedding"),
])
def test_a_table_that_cannot_be_built_is_refused(change, word):
    with pytest.raises(ConfigError, match=word):
        ModelConfig.from_dict(dict(PUBLISHED, **change))


# -- the forward against the reference -------------------------------------------

def test_forward_matches_the_reference(cfg, params):
    tokens = support.tokens(45)
    got = np.asarray(support.forward(params, [tokens], cfg))[0]
    assert np.abs(got - _ref(params, tokens)).max() < TOL


@pytest.mark.parametrize("wrong,least", [
    ("float8", 1e-2), ("norm_before_gate", 1e-2), ("softmax_scores", 1e-2),
    ("bias_as_weight", 4e-3), ("rope", 1e-2), ("padding_in_state", 1e-2)])
def test_the_tolerance_sees_each_wrong_model(cfg, params, wrong, least):
    """Each of the six wrong models the chip check is shown to catch moves
    the reference's own logits by far more than ``TOL``: a program that
    computed it would fail the logit tests here."""
    tokens = support.tokens(45)
    right = _ref(params, tokens)
    moved = np.abs(_ref(params, tokens, wrong=wrong, prompt_len=37,
                        pad_to=48)[-8:] - right[-8:]).max()
    assert moved > least, (wrong, moved)


def _mixer_inputs(cfg, params, bucket, seed=0):
    """Pre-activation xBC and raw dt of one state-space layer for a window
    of ``bucket`` random rows, and the layer's parameters."""
    layer = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["ssm"])
    k0, k1 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k0, (1, bucket, cfg.ssm.conv_channels)),
            jax.random.normal(k1, (1, bucket, cfg.ssm.num_heads)), layer)


@pytest.mark.parametrize("n,bucket", [(5, 16), (16, 16), (17, 32), (31, 32),
                                      (37, 48), (48, 48), (50, 64), (2, 16)])
def test_chunked_scan_is_the_recurrence(cfg, params, n, bucket):
    """The window form (conv from a zero tail, chunked scan, chunk 16)
    against ``n`` one-token steps over a state pool: prompts that end
    inside a chunk, on a chunk boundary, on the bucket's edge and before
    the conv's width, the bucket's padding (rows past ``n``) kept out.
    Outputs of the live rows, the state and the conv tail all hold."""
    xbc, dt, layer = _mixer_inputs(cfg, params, bucket, seed=n)
    live = jnp.arange(bucket)[None] < n
    y, (tail, h) = ssm.recur_window(cfg, live)(xbc, dt, layer)
    s = cfg.ssm
    conv = jnp.zeros((1, 1, s.conv_kernel - 1, s.conv_channels))
    state = jnp.zeros((1, 1, s.num_heads, s.head_dim, s.state_size))
    for t in range(n):
        y_t, (conv, state) = ssm.recur_step(cfg, conv, state, 0)(
            xbc[:, t:t + 1], dt[:, t:t + 1], layer)
        assert np.abs(np.asarray(y_t[:, 0] - y[:, t])).max() < 1e-5
    assert np.abs(np.asarray(state[0] - h)).max() < 1e-6
    assert np.abs(np.asarray(conv[0] - tail)).max() == 0
    assert h.dtype == jnp.float32 and np.abs(np.asarray(h)).max() > 1e-3


def test_padding_let_into_the_state_is_seen(cfg, params):
    """Without the live mask the bucket's padding enters the state: the
    state differs, by far more than the tolerance."""
    xbc, dt, layer = _mixer_inputs(cfg, params, 48)
    live = jnp.arange(48)[None] < 37
    _, (tail, h) = ssm.recur_window(cfg, live)(xbc, dt, layer)
    _, (tail_all, h_all) = ssm.recur_window(cfg, None)(xbc, dt, layer)
    assert np.abs(np.asarray(h - h_all)).max() > 1e-2
    assert np.abs(np.asarray(tail - tail_all)).max() > 1e-2


# -- a window from a state: the form a riding piece and a chunk run (PR 44) -----

def _scan_inputs(cfg, rows, live, seed=0):
    """``ssm_scan_prefill``'s arguments for a window of ``rows`` rows of
    which the first ``live`` are tokens (``dt`` = 0 on the rest)."""
    s = cfg.ssm
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(k[1], (1, rows, s.num_heads)))
    return dict(
        x=jax.random.normal(k[0], (1, rows, s.num_heads, s.head_dim)),
        dt=jnp.where(jnp.arange(rows)[None, :, None] < live, dt, 0.0),
        A=-jnp.exp(jax.random.normal(k[2], (s.num_heads,))),
        Bm=jax.random.normal(k[3], (1, rows, s.n_groups, s.state_size)),
        Cm=jax.random.normal(k[4], (1, rows, s.n_groups, s.state_size)),
        D=jax.random.normal(k[5], (s.num_heads,)))


@pytest.mark.parametrize("at", [1, 2, 16, 17],
                         ids=["1", "K-2", "one chunk", "one chunk + 1"])
@pytest.mark.parametrize("live", [48, 41, 9], ids=["all", "padded", "short"])
def test_the_scan_from_a_handed_state_is_the_whole_windows(cfg, at, live):
    """``ssm_scan_prefill`` over a window of 48 rows (three chunks of 16)
    split after ``at`` rows, the first part's state handed to the second
    as ``h0``: the outputs and the final state of the whole window, where
    the split falls inside a chunk, on a chunk's edge or one row past it,
    and where the padding (``dt`` = 0 past ``live``) fills the second part
    or reaches into the first. No ``h0`` is a zero ``h0``."""
    assert cfg.ssm.conv_kernel - 2 == 2 and cfg.ssm.chunk_size == 16
    a = _scan_inputs(cfg, 48, live, seed=at)
    rows = {k: v for k, v in a.items() if v.ndim > 1}

    def scan(lo, hi, h0=None):
        return ssm.ssm_scan_prefill(
            **{k: v[:, lo:hi] for k, v in rows.items()}, A=a["A"],
            D=a["D"], chunk=16, h0=h0)
    y, h = scan(0, 48)
    y1, h1 = scan(0, at)
    y2, h2 = scan(at, 48, h1)
    assert h1.dtype == h2.dtype == jnp.float32
    np.testing.assert_allclose(jnp.concatenate([y1, y2], axis=1), y,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h2, h, rtol=1e-5, atol=1e-6)
    y0, h0 = scan(0, 48, jnp.zeros_like(h))
    np.testing.assert_array_equal(y0, y)
    np.testing.assert_array_equal(h0, h)
    assert np.abs(np.asarray(h)).max() > 1e-3


@pytest.mark.parametrize("n", [2 * 16 + 1, 2 * 16 + 2, 16 + 5, 3 * 16, 7],
                         ids=["a last piece of 1 row", "of 2 rows",
                              "of 5 rows", "whole pieces", "one short piece"])
def test_pieces_from_the_slots_state_are_the_whole_prompt(cfg, params, n):
    """A prompt of ``n`` tokens through one state-space layer as pieces of
    16 rows (garbage past a piece's live rows), each ``recur_chunk`` from
    what ``slot_state`` reads of slot 1 and written back by
    ``write_slot_state``, in pools that hold a former occupant's rows in
    every slot: the live rows' outputs, the slot's final conv tail (a last
    piece of fewer than K-1 rows keeps columns of the piece before) and its
    state are ``recur_window``'s over the whole prompt; the first piece
    read nothing of the former occupant; the other slots' rows stay bit
    for bit."""
    C, s = 16, cfg.ssm
    bucket = -(-n // C) * C
    xbc, dt, layer = _mixer_inputs(cfg, params, bucket, seed=n)
    y, (tail, h) = ssm.recur_window(
        cfg, jnp.arange(bucket)[None] < n)(xbc, dt, layer)
    rng = np.random.default_rng(n)
    conv0 = jnp.asarray(rng.normal(size=(1, 3, s.conv_kernel - 1,
                                         s.conv_channels)), jnp.float32)
    ssm0 = jnp.asarray(rng.normal(size=(1, 3, s.num_heads, s.head_dim,
                                        s.state_size)), jnp.float32)
    conv, pool, slot = conv0, ssm0, jnp.int32(1)
    for start in range(0, n, C):
        live = min(C, n - start)
        garbage = jnp.arange(C)[None, :, None] >= live
        tails, states = ssm.slot_state(conv, pool, slot,
                                       jnp.asarray([start]))
        assert states.dtype == jnp.float32
        out, after = ssm.recur_chunk(
            cfg, tails[0], states[0], jnp.arange(C)[None] < live)(
                jnp.where(garbage, 9.0, xbc[:, start:start + C]),
                jnp.where(garbage, 9.0, dt[:, start:start + C]), layer)
        np.testing.assert_allclose(out[:, :live], y[:, start:start + live],
                                   rtol=1e-5, atol=1e-5)
        conv, pool = ssm.write_slot_state(
            conv, pool, slot, after[0][None], after[1][None], True)
    np.testing.assert_array_equal(conv[0, 1], tail[0])
    np.testing.assert_allclose(pool[0, 1], h[0], rtol=1e-5, atol=1e-6)
    for got, was in ((conv, conv0), (pool, ssm0)):
        np.testing.assert_array_equal(got[:, [0, 2]], was[:, [0, 2]])
    # a step that carries nothing (``live`` False) writes nothing
    kept = ssm.write_slot_state(conv, pool, slot, after[0][None] + 1,
                                after[1][None] + 1, False)
    np.testing.assert_array_equal(kept[0], conv)
    np.testing.assert_array_equal(kept[1], pool)


# -- prefill, then decode, through the pools -------------------------------------

def _cold_program(params, padded, live, *, cfg):
    return gpt.forward(
        params, padded, cfg,
        kv_cache=gpt.init_kv_cache(cfg, 1, padded.shape[1],
                                   dtype=jnp.float32),
        cache_offset=jnp.zeros((1,), jnp.int32), segment_ids=live,
        return_moe_stats=True, return_ssm_state=True)


def _decode_program(params, toks, pos, kp, vp, table, active, state, *, cfg):
    return decode_step_forward(params, toks, pos, kp, vp, table, cfg,
                               active=active, return_moe_stats=True,
                               ssm_state=state)


def _cold_prefill(cfg, params, tokens, bucket, kp, vp, state, pages, slot):
    """What the engine's prefill program does: the dense forward over a
    padded bucket, the attention layers' K/V scattered into ``pages`` and
    the slot's rows of both state pools overwritten."""
    n = len(tokens)
    padded = np.full((1, bucket), 7, np.int32)      # garbage padding
    padded[0, :n] = tokens
    live = (jnp.arange(bucket)[None] < n).astype(jnp.int32)
    logits, (kd, vd), stats, (tails, hs) = support.program(
        _cold_program, cfg)(params, jnp.asarray(padded), live)

    def paged(d):
        return d[:, 0].reshape(cfg.kv_layers, bucket // PS, PS,
                               cfg.num_kv_heads, cfg.head_dim
                               ).transpose(0, 1, 3, 2, 4)
    entries = jnp.asarray(pages[:bucket // PS])
    state = {"conv": state["conv"].at[:, slot].set(tails[:, 0]),
             "ssm": state["ssm"].at[:, slot].set(
                 hs[:, 0].astype(state["ssm"].dtype))}
    return (np.asarray(logits)[0, :n], kp.at[:, entries].set(paged(kd)),
            vp.at[:, entries].set(paged(vd)), state, np.asarray(stats))


def _serve_sequence(cfg, params, seq, n, state_dtype=jnp.float32):
    """Logits of every position of ``seq``: the first ``n`` through cold
    prefill (bucket 48), the rest through decode steps in a batch of four
    slots of which slot 1 is live; and the pools afterwards."""
    kp, vp, state = _pools(cfg, state_dtype=state_dtype)
    # a former occupant's leftovers in EVERY slot: the prefill must
    # overwrite slot 1's and the idle slots' must stay as they are
    state = jax.tree_util.tree_map(lambda a: a + 0.5, state)
    table = np.zeros((4, 8), np.int32)
    table[1, :7] = [3, 4, 5, 6, 7, 8, 9]
    got = np.zeros((len(seq), cfg.vocab_size), np.float32)
    got[:n], kp, vp, state, stats = _cold_prefill(
        cfg, params, seq[:n], 48, kp, vp, state, list(table[1, :6]), 1)
    assert stats[-1] == n * cfg.moe_layers * cfg.moe.experts_per_token
    assert stats[:4].sum() < stats[-1]          # some choices are absent
    for pos in range(n, len(seq)):
        toks = np.full(4, 11, np.int32)             # idle slots' garbage
        toks[1] = seq[pos]
        lg, kp, vp, stats, state = support.program(_decode_program, cfg)(
            params, jnp.asarray(toks), jnp.full((4,), pos, jnp.int32), kp,
            vp, jnp.asarray(table),
            jnp.asarray([False, True, False, False]), state)
        got[pos] = np.asarray(lg)[1]
        assert np.asarray(stats)[-1] == cfg.moe_layers * 3   # one live token
    return got, state


def test_prefill_then_decode_matches_the_reference(cfg, params):
    """The whole served sequence, position by position, against the
    reference's full forward: the prompt through cold prefill (padded
    bucket, garbage padding), then eight decode steps over the state
    pools. Idle slots' state stays as it was."""
    seq = support.tokens(37 + 8, seed=2)
    got, state = _serve_sequence(cfg, params, seq, 37)
    assert np.abs(got - _ref(params, seq)).max() < TOL
    for name in ("conv", "ssm"):
        idle = np.asarray(state[name])[:, [0, 2, 3]]
        assert np.all(idle == 0.5), name            # write_ok False: untouched
        assert not np.any(np.asarray(state[name])[:, 1] == 0.5)


def test_the_float32_state_is_held_on_logits(cfg, params):
    """What a token check on the chip cannot separate: the state cached in
    bfloat16 between decode steps. At this size the state is a small part
    of the stream, so the decode positions are held to a tolerance of
    their own: float32 state reads 2e-7 to 6e-7 over the eight decode
    steps, bfloat16 state 1.6e-5 (its 8 bits of mantissa, step after
    step); ``STATE_TOL`` lies between with room on both sides."""
    seq = support.tokens(37 + 8, seed=2)
    want = _ref(params, seq)[37:]
    got, _ = _serve_sequence(cfg, params, seq, 37)
    low, _ = _serve_sequence(cfg, params, seq, 37, state_dtype=jnp.bfloat16)
    assert np.abs(got[37:] - want).max() < STATE_TOL \
        < np.abs(low[37:] - want).max()


def test_a_window_over_the_state_pools_is_refused(cfg, params):
    kp, vp, state = _pools(cfg)
    with pytest.raises(ValueError, match="window of 8 tokens"):
        extend_step_forward(params, jnp.zeros((4, 8), jnp.int32),
                            jnp.zeros((4,), jnp.int32), kp, vp,
                            jnp.zeros((4, 8), jnp.int32), cfg,
                            ssm_state=state)


# -- the chip's share of the experts ---------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(cfg, params):
    """The model-configs guide's share test: the routed parts the two
    halves compute (experts 0-3 here, 4-7 on the absent chip) plus the
    shared expert counted ONCE equal the uncut layer, which holds all 8
    experts."""
    moe = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["moe"])
    other = seeded(dataclasses.replace(cfg), seed=7)["blocks"]["moe"]
    other = jax.tree_util.tree_map(lambda a: a[0], other)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 9, cfg.hidden_size))

    def half(first, experts):
        c = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, first_expert=first))
        layer = dict(moe, **{n: {"kernel": experts[n]["kernel"]}
                             for n in ("up", "down")})
        return c, layer
    c0, l0 = half(0, moe)
    c1, l1 = half(4, other)
    whole_cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=8, router_experts=0))
    whole = dict(moe, **{n: {"kernel": jnp.concatenate(
        [moe[n]["kernel"], other[n]["kernel"]])} for n in ("up", "down")})

    def routed_and_shared(c, layer):
        with_shared, stats = experts_mixer(h, layer, c, None, "dropless",
                                           None)
        no_shared = dataclasses.replace(c, moe=dataclasses.replace(
            c.moe, shared_expert_size=0))
        routed, _ = experts_mixer(h, layer, no_shared, None, "dropless", None)
        return routed, with_shared - routed, np.asarray(stats)
    r0, shared, s0 = routed_and_shared(c0, l0)
    r1, shared1, s1 = routed_and_shared(c1, l1)
    uncut, _ = experts_mixer(h, whole, whole_cfg, None, "dropless", None)
    assert np.abs(np.asarray(shared - shared1)).max() < 1e-8
    assert np.abs(np.asarray(r0 + r1 + shared - uncut)).max() < 1e-5
    assert np.abs(np.asarray(r0)).max() > 1e-3 < np.abs(np.asarray(r1)).max()
    # every live choice falls on exactly one of the two halves
    assert s0[-1] == s1[-1] == 18 * 3
    assert s0[:4].sum() + s1[:4].sum() == 18 * 3


@pytest.mark.parametrize("width,up_shape", [(32, (32, 64)),
                                            (128, (64, 128)),
                                            (64, (64, 64))])
def test_the_expert_stack_lies_as_its_width_says(cfg, width, up_shape):
    """A width that is no multiple of 128 lanes is stored (out, in) and
    takes the transposed grouped matmul; a 128-multiple lies (in, out) like
    the uniform stack's and takes the plain one. ``moe_block`` reads the
    order off the stack (no model attribute), and so does the reference:
    both agree at every width (F == H lies (in, out), which its shape
    cannot tell)."""
    c = dataclasses.replace(cfg, ffn_size=width)
    p = seeded(c, seed=3)
    assert p["blocks"]["moe"]["up"]["kernel"].shape[-2:] == up_shape
    assert p["blocks"]["moe"]["down"]["kernel"].shape[-2:] == (width, 64)
    tokens = support.tokens(21, seed=width)
    got = np.asarray(gpt.forward(p, jnp.asarray([tokens]), c)[0])
    want = _ref(p, tokens, moe_intermediate_size=width)
    assert np.abs(got - want).max() < TOL


def test_the_reference_gives_the_routing_margin(cfg, params):
    """``with_margin``: for each position the least, over the expert
    layers, of the k-th largest biased score less the k+1-th; the logits
    beside it are the plain ones. ``float8_experts`` rounds the routed
    experts' operands alone: it moves the logits, less than ``float8``."""
    tokens = support.tokens(23, seed=5)
    lg, margin = hybrid_decoder.logits(params, tokens, PUBLISHED,
                                       with_margin=True, round_to=16)
    assert lg.shape == (23, 256) and margin.shape == (23,)
    assert np.abs(np.asarray(lg) - _ref(params, tokens)).max() < 1e-6
    b = params["blocks"]["moe"]
    x, _ = hybrid_decoder.hidden(params, tokens[:1], PUBLISHED)
    # by hand at the first expert layer (layer 1 of MEMEM*E), position 0
    first = hybrid_decoder._mamba(
        hybrid_decoder._f32(params["embed"]["embedding"][jnp.asarray(
            tokens[:1])]),
        {"norm": params["blocks"]["ssm"]["norm"]["scale"][0],
         "in_proj": params["blocks"]["ssm"]["in_proj"]["kernel"][0],
         "conv_kernel": params["blocks"]["ssm"]["conv"]["kernel"][0],
         "conv_bias": params["blocks"]["ssm"]["conv"]["bias"][0],
         "dt_bias": params["blocks"]["ssm"]["dt_bias"][0],
         "A_log": params["blocks"]["ssm"]["A_log"][0],
         "D": params["blocks"]["ssm"]["D"][0],
         "gate_norm": params["blocks"]["ssm"]["gate_norm"]["scale"][0],
         "out_proj": params["blocks"]["ssm"]["out_proj"]["kernel"][0]},
        jnp.ones((1,)), nh=8, p=8, n=16, g=2, eps=1e-5, float8=False,
        norm_before_gate=False)
    u = hybrid_decoder._rms_norm(first, b["norm"]["scale"][0], 1e-5)
    pick = np.sort(np.asarray(jax.nn.sigmoid(u @ b["router"]["kernel"][0])
                              + b["router"]["bias"][0])[0])[::-1]
    assert float(margin[0]) <= pick[2] - pick[3] + 1e-6
    assert np.all(np.asarray(margin) >= 0)
    right = _ref(params, tokens)
    experts = np.abs(_ref(params, tokens, wrong="float8_experts") - right)
    everything = np.abs(_ref(params, tokens, wrong="float8") - right)
    assert 1e-3 < experts.max() < everything.max()


def test_the_selection_bias_picks_and_does_not_weigh(cfg):
    x = jax.random.normal(jax.random.PRNGKey(0), (6, cfg.hidden_size))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (cfg.hidden_size, 8))
    bias = jnp.asarray([5., 0, 0, 0, 0, 0, 0, -5.])
    scores, w, e = moe_route(x, kernel, cfg, bias)
    plain_scores, w0, e0 = moe_route(x, kernel, cfg, None)
    assert np.all(np.asarray(e)[:, 0] == 0) and 7 not in np.asarray(e)
    assert np.allclose(np.asarray(w).sum(-1), 2.5, atol=1e-5)
    # the weights are the chosen SCORES renormalised: no bias in them
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(e), -1)
    assert np.allclose(np.asarray(w), 2.5 * chosen / chosen.sum(-1,
                                                                keepdims=True),
                       atol=1e-6)
    assert np.asarray(scores).min() > 0 and np.asarray(scores).max() < 1


@pytest.mark.parametrize("rows,tm", [(64, 16), (96, 32)])
def test_transposed_grouped_matmul_kernel_is_the_xla_route(rows, tm):
    """The kernel for stacks stored (out, in), K cut into blocks under an
    accumulator (interpret mode), against ``ragged_dot`` on the same
    layout; unused tiles write zeros."""
    K, N, E = 384, 160, 4
    k0, k1 = jax.random.split(jax.random.PRNGKey(0))
    lhs = jax.random.normal(k0, (rows, K), jnp.float32)
    rhs = jax.random.normal(k1, (2, E, N, K), jnp.float32)
    n_tiles = rows // tm
    group = jnp.minimum(jnp.arange(n_tiles), E - 1).astype(jnp.int32)
    used = jnp.int32(n_tiles - 1)
    old = moe_gmm._ODD_BLOCK_BYTES
    moe_gmm._ODD_BLOCK_BYTES = N * 128 * 4      # force three K blocks
    try:
        got = moe_gmm.moe_gmm(lhs, rhs, group, used, 1, tm=tm,
                              interpret=True, rhs_transposed=True)
    finally:
        moe_gmm._ODD_BLOCK_BYTES = old
    want = moe_gmm.grouped_matmul(lhs, rhs, group, used, 1, tm=tm,
                                  rhs_transposed=True)
    live = (n_tiles - 1) * tm
    assert np.abs(np.asarray(got - want))[:live].max() < 1e-3
    assert np.all(np.asarray(got)[live:] == 0)


def test_column_and_k_tiles_at_the_published_widths():
    """OLMoE's tiles stay what they were; Nemotron's down kernel (1856 ->
    2688) cuts 2688 = 21 x 128 into 896s, its up kernel (stored 1856 x
    2688) takes 1856 whole and K in 896s: 3.3 MB a weight block."""
    assert moe_gmm._col_tile(2048, 1024, 2) == 512
    assert moe_gmm._col_tile(1024, 2048, 2) == 1024
    assert moe_gmm._col_tile(1856, 2688, 2) == 896
    assert moe_gmm._k_tile(2688, 1856, 2) == 896


# -- the engine ------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine(cfg, params):
    return support.engine(cfg, params)


def test_engine_serves_the_references_tokens(cfg, params, engine):
    """Six prompts over four slots (so slots are REUSED after a release):
    every served token is the reference's argmax."""
    prompts = [support.tokens(n, seed=s) for s, n in enumerate((36, 20, 36, 20, 36,
                                                         20))]
    reqs = engine.generate(prompts, SamplingParams(temperature=0.0,
                                                   max_tokens=10))
    for p, r in zip(prompts, reqs):
        assert len(r.generated_tokens) == 10
        assert support.gaps(_ref, params, p,
                            r.generated_tokens).max() == 0.0
    st = engine.stats()
    assert st["ssm"]["state_bytes"] == engine.kv.state_bytes() > 0
    assert st["kv"]["state_bytes"] == st["ssm"]["state_bytes"]
    assert st["ssm"]["slot_steps"] > 0
    assert st["ssm"]["prefill_tokens"] == sum(map(len, prompts))
    assert st["ssm"]["prefill_padded_tokens"] > st["ssm"]["prefill_tokens"]
    assert 0 < st["moe"]["held_choices"] < st["moe"]["all_choices"]
    assert st["moe"]["layer_steps"] % cfg.moe_layers == 0
    assert engine.kv.k_pages.shape[0] == cfg.kv_layers == 1


def test_a_repeated_prompt_is_prefilled_again_to_the_same_tokens(engine):
    """Prefix reuse by page hash is ON by default and wrong for a recurrent
    layer: the engine registers and looks up no hash, says so, and a
    repeated prompt is computed again, to the same tokens."""
    prompt = support.tokens(36, seed=5)
    before = engine.stats()
    a, = engine.generate([prompt], SamplingParams(temperature=0.0,
                                                  max_tokens=6))
    b, = engine.generate([prompt], SamplingParams(temperature=0.0,
                                                  max_tokens=6))
    after = engine.stats()
    assert a.generated_tokens == b.generated_tokens
    assert after["prefill_tokens"] - before["prefill_tokens"] == 72
    assert after["prefix_cached_tokens"] == 0
    assert after["kv"]["prefix_cached_pages"] == 0
    assert after["ssm"]["refused"]["prefix_caching"] \
        == before["ssm"]["refused"]["prefix_caching"] + 2


def test_a_reused_slot_starts_from_a_zero_state(cfg, params):
    """One slot, two requests one after the other: the second is served as
    on a fresh engine, whatever the first left in the slot's state."""
    one = support.engine(cfg, params, max_batch_size=1)
    first, second = support.tokens(20, seed=8), support.tokens(36, seed=9)
    sp = SamplingParams(temperature=0.0, max_tokens=10)
    one.generate([first], sp)
    assert np.abs(np.asarray(one.kv.state["ssm"])).max() > 0   # left behind
    reused, = one.generate([second], sp)
    assert support.gaps(_ref, params, second,
                        reused.generated_tokens).max() == 0.0


def test_recompute_preemption_rebuilds_the_state(cfg, params):
    """The default preemption re-prefills prompt plus generated tokens,
    which rebuilds the recurrent state: a pool too small for both requests
    preempts one, and both still serve the reference's tokens."""
    small = support.engine(cfg, params, max_batch_size=2, kv_num_blocks=9)
    prompts = [support.tokens(20, seed=3), support.tokens(20, seed=4)]
    reqs = small.generate(prompts, SamplingParams(temperature=0.0,
                                                  max_tokens=26))
    assert small.stats()["preemptions"] >= 1
    for p, r in zip(prompts, reqs):
        assert len(r.generated_tokens) == 26
        assert support.gaps(_ref, params, p,
                            r.generated_tokens).max() == 0.0


@pytest.mark.parametrize("over,word", [
    ({"chunked_prefill_tokens": 32}, "chunked_prefill_tokens"),
    ({"speculative": "ngram"}, "speculative"),
    ({"preemption": "swap"}, "preemption: swap"),
    ({"quantization": "int8"}, "layer table"),
])
def test_a_feature_the_state_cannot_follow_is_refused_by_name(cfg, params,
                                                              over, word):
    with pytest.raises(ValueError, match=word):
        support.engine(cfg, params, **over)


def test_page_transfers_and_fleets_are_refused_by_name(cfg, params, engine):
    """Fleet migration, handoff, prefix fetch and the tiered store move K/V
    pages alone: each entry point refuses a model with recurrent state."""
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


def test_uniform_models_carry_nothing_of_this():
    """A dense model's engine has no state pool, its programs no extra
    argument, its stats no ``ssm`` block."""
    c = get_model_config("gpt-test")
    e = InferenceEngine(c, ServeConfig(model="gpt-test", max_batch_size=2,
                                       max_seq_len=64, dtype="float32"))
    assert e.kv.state is None and e.kv.state_bytes() == 0
    assert "ssm" not in e.stats() and not e.turned_off
    assert c.kv_layers == c.num_layers and not c.is_recurrent


# -- the ops ---------------------------------------------------------------------

def test_ssm_decode_is_one_step_of_the_scan():
    """x, dt, B, C of 20 tokens: the chunked scan's outputs and final
    state against 20 one-step updates from zero."""
    nh, P, G, N, S = 4, 8, 2, 16, 20
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (1, S, nh, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    Bm = jax.random.normal(ks[3], (1, S, G, N))
    Cm = jax.random.normal(ks[4], (1, S, G, N))
    D = jax.random.normal(ks[5], (nh,))
    y, h = ssm.ssm_scan_prefill(x, dt, A, Bm, Cm, D, chunk=8)
    state = jnp.zeros((1, nh, P, N))
    for t in range(S):
        y_t, state = ssm.ssm_decode(x[:, t], dt[:, t], A, Bm[:, t],
                                    Cm[:, t], D, state)
        assert np.abs(np.asarray(y_t - y[:, t])).max() < 1e-4
    assert np.abs(np.asarray(state - h)).max() < 1e-4


# write_ok over 5 slots: who moves this step
_LIVE = {"all": [1, 1, 1, 1, 1], "every_other": [1, 0, 1, 0, 1],
         "one": [0, 0, 1, 0, 0], "none": [0, 0, 0, 0, 0],
         "last_dead": [1, 1, 1, 1, 0]}
# (heads, head_dim, state, groups) of the two cells that run the update
_CELL_HEADS = {"parallel": (32, 128, 256, 2), "hybrid": (64, 64, 128, 8)}


@pytest.mark.parametrize("layer_is", ["static", "traced"])
@pytest.mark.parametrize("live", list(_LIVE))
@pytest.mark.parametrize("heads", list(_CELL_HEADS))
def test_ssm_decode_pool_is_ssm_decode_in_place(heads, live, layer_is):
    """The Pallas form of the one-token update (interpret mode) over a
    three-layer pool at both cells' head shapes against ``ssm_decode`` and
    the select ``step_pools`` keeps off the chip: ``y`` and the live
    slots' states to 1e-5, a slot that ``write_ok`` leaves out and every
    other layer of the pool BIT FOR BIT (a step with nobody live too) and
    its ``y`` 0, the layer a Python int or traced."""
    nh, P, N, G = _CELL_HEADS[heads]
    S, Lm, layer = 5, 3, 1
    ok = jnp.asarray(_LIVE[live], bool)
    ks = jax.random.split(jax.random.PRNGKey(7), 7)
    ops = (jax.random.normal(ks[0], (S, nh, P)),
           jax.nn.softplus(jax.random.normal(ks[1], (S, nh))),
           -jnp.exp(0.5 * jax.random.normal(ks[2], (nh,))),
           jax.random.normal(ks[3], (S, G, N)),
           jax.random.normal(ks[4], (S, G, N)),
           jax.random.uniform(ks[5], (nh,), minval=0.5, maxval=1.5))
    pool = jax.random.normal(ks[6], (Lm, S, nh, P, N))
    want_y, want = ssm.ssm_decode(*ops, pool[layer])
    step = jax.jit(lambda ops, pool, at: ssm.ssm_decode_pool(
        *ops, pool, layer if layer_is == "static" else at, ok[:, None],
        interpret=True))
    y, got = step(ops, pool, jnp.int32(layer))
    y, got, before = np.asarray(y), np.asarray(got), np.asarray(pool)
    alive = np.asarray(ok)
    scale = np.abs(np.asarray(want_y)).max()
    assert np.abs(y - np.asarray(want_y))[alive].max(initial=0) < 1e-5 * scale
    assert np.abs(got[layer] - np.asarray(want))[alive].max(initial=0) < 1e-5
    assert np.array_equal(got[layer][~alive], before[layer][~alive])
    assert np.array_equal(got[[0, 2]], before[[0, 2]])
    assert not y[~alive].any()


def test_gated_norm_gates_then_norms():
    y = jax.random.normal(jax.random.PRNGKey(0), (3, 32))
    z = jax.random.normal(jax.random.PRNGKey(1), (3, 32))
    scale = jax.random.normal(jax.random.PRNGKey(2), (32,)) * 0.3
    got = np.asarray(ssm.ssm_gated_norm(y, z, scale, groups=4, eps=1e-5))
    g = np.asarray(y * jax.nn.silu(z)).reshape(3, 4, 8)
    want = (g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)).reshape(
        3, 32) * (1 + np.asarray(scale))
    assert np.abs(got - want).max() < 1e-5
