"""Kimi Delta Attention (the ``K`` layer kind) beside NoPE latent attention
without a query bottleneck, the state pools beside the latent pool, and
chunked prefill that carries recurrent state, on the CPU at
``kimi-linear-test`` widths with seeded random weights, held to the plain
float32 reference (``benchmark/reference/linear_decoder.py``) on LOGITS.

Tolerance: both sides compute in float32 with full-precision matmuls and
differ in the ORDER of their sums alone (the chunked WY form against the
token-by-token recurrence, absorbed against expanded attention, a one-hot
page merge): logits of size ~0.6 agree to ~5e-7, and TOL = 2e-5 leaves that
more than an order of room. Every departure the chip's check is asked to
refuse moves the reference's logits by more than 20 x TOL
(``test_each_departure_moves_the_logits``).
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serving_support as support
from benchmark.reference import linear_decoder as ref
from distributed_llm_training_and_inference_system_tpu.config.presets import (
    KIMI_LINEAR_TEST_PUBLISHED,
    get_model_config,
)
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ConfigError,
    ModelConfig,
)
from distributed_llm_training_and_inference_system_tpu.models import gpt
from distributed_llm_training_and_inference_system_tpu.models.layers import (
    experts_mixer,
)
from distributed_llm_training_and_inference_system_tpu.ops import kda
from distributed_llm_training_and_inference_system_tpu.serve import decode
from distributed_llm_training_and_inference_system_tpu.serve.kv_cache import (
    PagedKVCache,
)
from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
    SamplingParams,
)

TOL = 2e-5
C = KIMI_LINEAR_TEST_PUBLISHED
PS = 8
ROOT = Path(__file__).resolve().parents[1]


# a chunk of 8 in place of ``ops/kda.py CHUNK`` = 64, so that the tiny windows
# of this file (a prompt of 40 tokens, an engine chunk of 32) run several
# chunks with the state carried between them
pytestmark = pytest.mark.usefixtures("short_kda_chunks")


@pytest.fixture(scope="module")
def cfg():
    return get_model_config("kimi-linear-test")


def seeded(cfg, seed=0):
    """Seeded weights with every norm's scale and the selection bias made
    non-trivial (at ``gpt.init``'s zeros a missing norm weight or bias
    would not show), and a router sharp enough that its scores differ."""
    tree = support.params_of(cfg, seed)
    key = jax.random.PRNGKey(seed + 5)

    def one(path, x):
        names = [k.key for k in path]
        if "scale" in names or names[-1] == "bias":
            spread = 0.02 if names[-1] == "bias" else 0.4
            return x + jax.random.uniform(
                jax.random.fold_in(key, hash(tuple(names)) % 9973), x.shape,
                x.dtype, -spread, spread)
        return x
    tree = jax.tree_util.tree_map_with_path(one, tree)
    router = tree["blocks"]["moe"]["router"]
    router["kernel"] = router["kernel"] * 20.0
    return tree


@pytest.fixture(scope="module")
def params(cfg):
    return seeded(cfg)


def _reference(params, tokens, positions=None, wrong=None):
    return np.asarray(ref.logits(params, tokens, C, positions=positions,
                                 wrong=wrong))


def _pools(cfg, slots=3):
    kv = PagedKVCache(cfg, num_slots=slots, max_seq_len=128, page_size=PS,
                      num_pages=48, dtype=jnp.float32)
    return kv, kv.k_pages, kv.state


def _cold_program(params, padded, live, *, cfg):
    return gpt.forward(params, padded, cfg, segment_ids=live,
                       return_latent=True, return_moe_stats=True,
                       return_ssm_state=True)


def _cold(cfg, params, kv, pool, state, slot, tokens, bucket):
    """Cold prefill as the engine's program does it: the forward over a
    padded bucket from a zero state, the latent rows written to the slot's
    pages and the slot's rows of both state pools overwritten."""
    n = len(tokens)
    padded = np.full((1, bucket), 7, np.int32)       # garbage padding
    padded[0, :n] = tokens
    live = (jnp.arange(bucket)[None] < n).astype(jnp.int32)
    logits, rows, _, (tails, states) = support.program(_cold_program, cfg)(
        params, jnp.asarray(padded), live)
    entries = jnp.asarray(kv.block_tables[slot, :bucket // PS])
    rows = jnp.pad(rows[:, 0], ((0, 0), (0, 0),
                                (0, pool.shape[-1] - rows.shape[-1])))
    pool = pool.at[:, entries].set(rows.reshape(
        cfg.kv_layers, bucket // PS, 1, PS, -1))
    state = {"conv": state["conv"].at[:, :, slot].set(tails[:, 0]),
             "ssm": state["ssm"].at[:, slot].set(states[:, 0])}
    return np.asarray(logits)[0, :n], pool, state


def _chunk_program(params, window, start, pool, table, ok, state, slot, *,
                   cfg):
    return decode.extend_step_forward(
        params, window, start, pool, None, table, cfg, write_ok=ok,
        ssm_state=state, state_slot=slot)


def _chunk(cfg, params, kv, pool, state, slot, tokens, start, bucket):
    """One chunk of ``slot``'s prompt through the chunk program's forward:
    (logits of its live rows, pool, state)."""
    m = len(tokens)
    window = np.full((1, bucket), 9, np.int32)
    window[0, :m] = tokens
    lg, pool, _, _, state = support.program(_chunk_program, cfg)(
        params, jnp.asarray(window), jnp.asarray([start], jnp.int32), pool,
        jnp.asarray(kv.block_tables[slot][None]),
        jnp.arange(bucket)[None] < m, state, jnp.int32(slot))
    return np.asarray(lg)[0, :m], pool, state


def _decode_program(params, tokens, positions, pool, tables, active, state,
                    *, cfg):
    return decode.decode_step_forward(
        params, tokens, positions, pool, None, tables, cfg, active=active,
        ssm_state=state)


def _decode(cfg, params, kv, pool, state, tokens, positions, active):
    """One decode step of every slot: (logits [slots, V], pool, state)."""
    lg, pool, _, _, state = support.program(_decode_program, cfg)(
        params, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(positions, jnp.int32), pool,
        jnp.asarray(kv.block_tables), jnp.asarray(active), state)
    return np.asarray(lg), pool, state


# -- the model against the reference ---------------------------------------------

def test_the_full_forward_is_the_reference(cfg, params):
    tokens = support.tokens(45)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(support.forward(params, [tokens], cfg))[0]
    want = _reference(params, tokens)
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < TOL


def test_the_reference_padded_and_compiled_is_the_reference(params):
    tokens = support.tokens(40, seed=3)
    want = _reference(params, tokens)
    got, margin = ref.logits(params, tokens, C, pad_to=64, compiled=True,
                             with_margin=True)
    assert got.shape == want.shape and margin.shape == (40,)
    assert np.abs(np.asarray(got) - want).max() < 1e-6


def test_cold_prefill_then_decode_through_the_pools(cfg, params):
    """A prompt through cold prefill (a padded bucket), then decode steps
    over the latent pool and the state pools in a batch of three slots, two
    of them idle: every position's logits are the reference's."""
    seq, n = support.tokens(40, seed=1), 29
    kv, pool, state = _pools(cfg)
    kv.allocate(1, len(seq))
    with jax.default_matmul_precision("highest"):
        got, pool, state = _cold(cfg, params, kv, pool, state, 1, seq[:n], 32)
        out = [got]
        for at in range(n, len(seq)):
            lg, pool, state = _decode(cfg, params, kv, pool, state,
                                      [0, seq[at], 0], [0, at, 0],
                                      [False, True, False])
            out.append(lg[1][None])
    got = np.concatenate(out)
    assert np.abs(got - _reference(params, seq)).max() < TOL
    # the idle slots' rows of the state pools were left as they were
    assert not np.asarray(state["ssm"][:, 0]).any()
    assert not np.asarray(state["conv"][:, :, 2]).any()


def test_a_chunked_prompt_carries_its_state_and_equals_the_cold_path(
        cfg, params):
    """A prompt of more than one chunk through the chunk program (the
    slot's state and conv window read and written a chunk, the last chunk
    padded), then decode: equal to the reference, and the slot's state and
    every decode logit equal to the cold path's on the same prompt. The
    slot's rows start FULL of another sequence's state: a chunk that starts
    its sequence reads none of it."""
    seq, n = support.tokens(70, seed=2), 61
    kv, pool, state = _pools(cfg)
    kv.allocate(2, len(seq))
    state = jax.tree_util.tree_map(lambda a: a + 3.0, state)   # a former
    with jax.default_matmul_precision("highest"):                # occupant
        out, at = [], 0
        for m, bucket in ((24, 24), (24, 24), (13, 16)):
            lg, pool, state = _chunk(cfg, params, kv, pool, state, 2,
                                     seq[at:at + m], at, bucket)
            out.append(lg)
            at += m
        carried = jax.tree_util.tree_map(np.asarray, state)
        for at in range(n, len(seq)):
            lg, pool, state = _decode(cfg, params, kv, pool, state,
                                      [0, 0, seq[at]], [0, 0, at],
                                      [False, False, True])
            out.append(lg[2][None])
        kv2, pool2, state2 = _pools(cfg)
        kv2.allocate(2, len(seq))
        _, pool2, state2 = _cold(cfg, params, kv2, pool2, state2, 2,
                                 seq[:n], 64)
    assert np.abs(np.concatenate(out) - _reference(params, seq)).max() < TOL
    assert np.abs(carried["ssm"][:, 2] - np.asarray(state2["ssm"][:, 2])
                  ).max() < 1e-5
    assert np.abs(carried["conv"][:, :, 2]
                  - np.asarray(state2["conv"][:, :, 2])).max() < 1e-6
    # the other slots' rows were not touched by the chunks
    assert np.abs(carried["ssm"][:, 0] - 3.0).max() == 0.0


def test_two_slots_of_different_lengths_do_not_leak_state(cfg, params):
    """Two sequences decode side by side in one batch, prefilled to
    different lengths: each slot's logits are its own sequence's."""
    a, b = support.tokens(30, seed=4), support.tokens(44, seed=5)
    na, nb = 9, 31
    kv, pool, state = _pools(cfg)
    kv.allocate(0, len(a) + 8)
    kv.allocate(2, len(b) + 8)
    with jax.default_matmul_precision("highest"):
        _, pool, state = _cold(cfg, params, kv, pool, state, 0, a[:na], 16)
        _, pool, state = _cold(cfg, params, kv, pool, state, 2, b[:nb], 32)
        got_a, got_b = [], []
        for i in range(13):
            lg, pool, state = _decode(
                cfg, params, kv, pool, state, [a[na + i], 0, b[nb + i]],
                [na + i, 0, nb + i], [True, False, True])
            got_a.append(lg[0])
            got_b.append(lg[2])
    want_a = _reference(params, a, positions=range(na, na + 13))
    want_b = _reference(params, b, positions=range(nb, nb + 13))
    assert np.abs(np.stack(got_a) - want_a).max() < TOL
    assert np.abs(np.stack(got_b) - want_b).max() < TOL


@pytest.mark.parametrize("wrong", ["bf16_state", "rotated_pe", "no_beta",
                                   "per_head_decay", "no_renorm", "float8"])
def test_each_departure_moves_the_logits(params, wrong):
    tokens = support.tokens(45)
    moved = np.abs(_reference(params, tokens, wrong=wrong)
                   - _reference(params, tokens)).max()
    assert moved > 20 * TOL, f"{wrong} moves the logits by {moved:.2e}"


# -- the two forms of the delta rule ---------------------------------------------

def _token_by_token(q, k, v, g, beta, S):
    out = []
    for t in range(q.shape[1]):
        o, S = kda.kda_decode(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t],
                              S)
        out.append(o)
    return jnp.stack(out, 1), S


def _window(B, S, nh, d, strength, beta_from=0.0):
    """(q, k, v, g, beta, S0) of a window: normalised q and k, log decays
    uniform in (-``strength``, 0) a channel, beta ``beta_from`` + a sigmoid,
    a non-zero state."""
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = kda.l2norm(jax.random.normal(ks[0], (B, S, nh, d))) * d ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], (B, S, nh, d)))
    v = jax.random.normal(ks[2], (B, S, nh, d))
    g = -strength * jax.random.uniform(ks[3], (B, S, nh, d))
    beta = beta_from + jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, nh)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (B, nh, d, d))


# (decay strength, [B, S, nh, d], beta's lower end): the first three are one
# window at three strengths; then the cells' head width over four whole
# chunks; a window shorter than a chunk whose length is no multiple of 16
# (its pairs formed as ONE block); beta in (1, 2), the sessions
# configuration's negative eigenvalues
_DECAY_CASES = {
    "weak": (0.05, (2, 150, 3, 16), 0.0),
    "strong": (3.0, (2, 150, 3, 16), 0.0),
    "extreme": (60.0, (2, 150, 3, 16), 0.0),
    "cell_heads": (1.0, (1, 256, 2, 128), 0.0),
    "one_block": (3.0, (2, 40, 3, 16), 0.0),
    "reflecting": (3.0, (2, 150, 3, 16), 1.0),
}


@pytest.mark.parametrize("case", list(_DECAY_CASES))
def test_the_chunked_form_is_the_recurrence_at_any_decay(case):
    """The chunked form against the one-step form applied token by token,
    from a non-zero state, over a window that is no whole number of chunks,
    with decays up to exp(-60) a token (exp(-3,840) over a chunk: the
    cumulative decay underflows and its inverse would overflow; the pair
    decays exp(G_t - G_s), s <= t, formed whole inside a sub-block of 16 rows
    and as exp(G_t - G_r) exp(G_r - G_s) through the first row r of t's
    sub-block across sub-blocks, do neither)."""
    strength, shape, beta_from = _DECAY_CASES[case]
    q, k, v, g, beta, S0 = _window(*shape, strength, beta_from)
    with jax.default_matmul_precision("highest"):
        o, S1 = kda.kda_chunk_prefill(q, k, v, g, beta, S0, 64)
    want_o, want_S = _token_by_token(q, k, v, g, beta, S0)
    assert np.isfinite(np.asarray(o)).all()
    assert np.abs(np.asarray(o - want_o)).max() < 5e-6
    assert np.abs(np.asarray(S1 - want_S)).max() < 5e-6
    if case == "one_block":
        # the same 40 rows inside a padded 64 (sub-blocks of 16 and the
        # matmuls between them) leave the same state
        n = shape[1]
        pad = lambda a: jnp.pad(a, ((0, 0), (0, 64 - n)) + ((0, 0),) * (
            a.ndim - 2))
        with jax.default_matmul_precision("highest"):
            o64, S64 = kda.kda_chunk_prefill(
                *(pad(a) for a in (q, k, v, g, beta)), S0, 64)
        assert np.abs(np.asarray(S64 - S1)).max() < 5e-6
        assert np.abs(np.asarray(o64[:, :n] - o)).max() < 5e-6


def _pairs_over_the_whole_chunk(a, k, G):
    """The pair products as ONE block of Q x Q x dk on the vector unit (the
    form before PR 62): (kk, ak) [..., Q, Q]."""
    Q = G.shape[-2]
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    pair = jnp.exp(jnp.where(
        causal[:, :, None], G[..., :, None, :] - G[..., None, :, :],
        -jnp.inf))
    return (jnp.sum(k[..., :, None, :] * k[..., None, :, :] * pair, -1),
            jnp.sum(a[..., :, None, :] * k[..., None, :, :] * pair, -1))


@pytest.mark.parametrize("strength", [3.0, 60.0], ids=["strong", "extreme"])
def test_the_sub_block_pairs_are_the_whole_chunks_pairs(strength):
    """``pair_products`` over a chunk of 64 (sub-blocks of 16 on the vector
    unit, float32 matmuls at full precision between them) against every pair
    formed at once: ``A`` (what enters the triangular inverse) and ``qk``
    to 1e-6, also where a sub-block's decay underflows (exp(-60) a token:
    a factor that reads 0 stands for a product that is smaller still)."""
    q, k, _, g, beta, _ = _window(2, 64, 3, 16, strength)
    qc, kc, G = (jnp.moveaxis(a, 1, 2) for a in (q, k, jnp.cumsum(g, 1)))
    assert kda._pair_block(64) == 16
    kk, qk = kda.pair_products(qc, kc, G)
    want_kk, want_qk = _pairs_over_the_whole_chunk(qc, kc, G)
    strict = jnp.arange(64)[:, None] > jnp.arange(64)[None, :]
    A, want_A = (jnp.where(strict, jnp.moveaxis(beta, 1, 2)[..., None] * x,
                           0.0) for x in (kk, want_kk))
    assert np.isfinite(np.asarray(A)).all()
    assert np.abs(np.asarray(A - want_A)).max() < 1e-6
    assert np.abs(np.asarray(qk - want_qk)).max() < 1e-6
    assert float(jnp.abs(want_A).max()) > 1e-3      # not all underflowed


def test_padding_leaves_the_state_as_it_was():
    """Positions with beta = 0 and g = 0 (a bucket's padding) change
    nothing: the state after 40 live tokens and 24 of padding is the state
    after the 40."""
    B, S, nh, d, n = 1, 64, 2, 16, 40
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    q, k, v = (jax.random.normal(ks[i], (B, S, nh, d)) for i in range(3))
    k = kda.l2norm(k)
    alive = (jnp.arange(S) < n)[None, :, None]
    g = jnp.where(alive[..., None], -jax.random.uniform(
        ks[3], (B, S, nh, d)), 0.0)
    beta = jnp.where(alive, jax.nn.sigmoid(
        jax.random.normal(ks[4], (B, S, nh))), 0.0)
    S0 = jax.random.normal(ks[5], (B, nh, d, d))
    with jax.default_matmul_precision("highest"):
        _, padded = kda.kda_chunk_prefill(q, k, v, g, beta, S0, 16)
        _, live = kda.kda_chunk_prefill(q[:, :n], k[:, :n], v[:, :n],
                                        g[:, :n], beta[:, :n], S0, 16)
    assert np.abs(np.asarray(padded - live)).max() < 1e-6


@pytest.mark.parametrize("strength", [0.05, 60.0], ids=["weak", "extreme"])
def test_the_pallas_one_step_kernel_is_the_recurrence(strength):
    """``kda_decode_pool`` (the kernel the chip runs, here interpreted) over
    one layer of a state pool against its jnp twin ``kda_decode`` and the
    token-by-token reference's update: 5 slots, 32 heads in two blocks of
    16, two of the slots with ``beta = 0, g = 0`` (idle, or past their stop
    position), whose state must come back bit for bit, as must the other
    layers of the pool."""
    B, nh, d, layers, layer = 5, 32, 16, 3, 1
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    q = kda.l2norm(jax.random.normal(ks[0], (B, nh, d))) * d ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], (B, nh, d)))
    v = jax.random.normal(ks[2], (B, nh, d))
    moves = jnp.array([True, False, True, True, False])
    g = jnp.where(moves[:, None, None],
                  -strength * jax.random.uniform(ks[3], (B, nh, d)), 0.0)
    beta = jnp.where(moves[:, None], jax.nn.sigmoid(
        jax.random.normal(ks[4], (B, nh))), 0.0)
    pool = jax.random.normal(ks[5], (layers, B, nh, d, d))
    o, new_pool = kda.kda_decode_pool(q, k, v, g, beta, pool, layer,
                                      interpret=True)
    want_o, want_S = kda.kda_decode(q, k, v, g, beta, pool[layer])
    assert np.abs(np.asarray(o - want_o)).max() < 2e-6
    assert np.abs(np.asarray(new_pool[layer] - want_S)).max() < 2e-6
    still = np.asarray(~moves)
    assert np.array_equal(np.asarray(new_pool[layer])[still],
                          np.asarray(pool[layer])[still])
    assert np.array_equal(np.asarray(new_pool[0]), np.asarray(pool[0]))
    assert np.array_equal(np.asarray(new_pool[2]), np.asarray(pool[2]))
    # and the reference's own update, a head a slot
    Sd = np.asarray(pool[layer], np.float64) * np.exp(
        np.asarray(g, np.float64))[..., None]
    u = np.asarray(beta, np.float64)[..., None] * (np.asarray(
        v, np.float64) - np.einsum("bhkv,bhk->bhv", Sd,
                                   np.asarray(k, np.float64)))
    ref_S = Sd + np.einsum("bhk,bhv->bhkv", np.asarray(k, np.float64), u)
    ref_o = np.einsum("bhkv,bhk->bhv", ref_S, np.asarray(q, np.float64))
    assert np.abs(np.asarray(new_pool[layer]) - ref_S).max() < 2e-6
    assert np.abs(np.asarray(o) - ref_o).max() < 2e-6


# -- the chip's share of the experts ---------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer(cfg, params):
    """The model-configs guide's share test: the routed parts that eight
    shares of ONE expert each compute (experts 0..7 of the router's 8) plus
    the shared expert counted ONCE equal what the uncut reference gives for
    the whole layer with all 8 experts."""
    moe = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["moe"])
    other = jax.tree_util.tree_map(
        lambda a: a[0], seeded(cfg, seed=7)["blocks"]["moe"])
    whole = {n: jnp.concatenate([moe[n]["kernel"], other[n]["kernel"]])
             for n in ("gate", "up", "down")}
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 19, cfg.hidden_size))

    def share(e):
        c = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, num_experts=1, first_expert=e, shared_expert_size=0))
        layer = dict(moe, **{n: {"kernel": whole[n][e:e + 1]} for n in whole})
        return experts_mixer(h, layer, c, None, "dropless", None)[0]
    with jax.default_matmul_precision("highest"):
        routed = sum(share(e) for e in range(8))
        one = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, num_experts=1))
        with_shared = experts_mixer(h, dict(moe, **{
            n: {"kernel": whole[n][:1]} for n in whole}), one, None,
            "dropless", None)[0]
        shared = with_shared - share(0)
    stack = {n: {"kernel": whole[n][None]} for n in whole}
    uncut, _ = ref._experts(
        jnp.asarray(h[0]), dict(
            stack, router=jax.tree_util.tree_map(
                lambda a: a[None], moe["router"]),
            shared=jax.tree_util.tree_map(lambda a: a[None], moe["shared"])),
        0, 19, dict(C, num_experts=8, first_expert=0), None)
    assert np.abs(np.asarray(shared)).max() > 1e-3
    assert np.abs(np.asarray(routed[0] + shared[0] - uncut)).max() < 1e-5


# -- the engine: the latent pool and the state pools in one cache ----------------

# chunks of 32 tokens where the shared shapes have a riding piece's 16, and 8
# steps a dispatch: the counts below are of prompts over 32 tokens, 32 a
# chunk, admitted when dispatches of 8 steps free their slots
CHUNKS_OF_32 = dict(chunked_prefill_tokens=32, decode_steps_per_dispatch=8)


def _last_logits(params):
    # (one compiled length for every step of every prompt)
    return lambda context: ref.logits(
        params, context, C, pad_to=192, compiled=True,
        positions=[len(context) - 1])[0]


def test_the_engine_serves_cold_and_chunked_prompts_from_one_cache(cfg,
                                                                   params):
    eng = support.engine(cfg, params, **CHUNKS_OF_32)
    kv = eng.stats()["kv"]
    assert kv["kind"] == "latent" and eng.kv.v_pages is None
    assert kv["bytes_per_token"] == 2 * cfg.mla.page_width * 4
    k = cfg.kda
    assert eng.kv.state["ssm"].shape == (6, 4, k.num_heads, 16, 16)
    assert eng.kv.state["conv"].shape == (6, 3, 4, k.conv_channels)
    assert kv["state_bytes"] == 6 * 4 * (4 * 16 * 16 + 3 * 192) * 4
    greedy = SamplingParams(temperature=0.0, max_tokens=6)
    with jax.default_matmul_precision("highest"):
        for prompt in (support.tokens(20, 1), support.tokens(100, 2), support.tokens(77, 3)):
            got = eng.generate([prompt], greedy)[0].generated_tokens
            assert got == support.greedy(_last_logits(params), prompt, 6)
        # a batch of cold and chunked prompts, more prompts than slots: a
        # released slot serves the next request from a zero state
        prompts = [support.tokens(n, 10 + n) for n in (20, 90, 33, 70, 12, 65, 9)]
        got = eng.generate(prompts, greedy)
        assert [r.generated_tokens for r in got] == [
            support.greedy(_last_logits(params), p, 6) for p in prompts]
    st = eng.stats()
    assert "ssm" not in st
    # 100 and 77 tokens, then 90, 33, 70 and 65, went chunk by chunk (32 a
    # chunk), each chunk reading the slot's state: 4 + 3 + 3 + 2 + 3 + 3
    assert st["kda"]["state_carry_chunks"] == 18
    assert st["kda"]["state_carry_tokens"] == 100 + 77 + 90 + 33 + 70 + 65
    assert st["kda"]["slot_steps"] > 0
    assert st["kda"]["refused"] == {"prefix_caching": 10}
    assert st["prefix_cached_tokens"] == 0
    programs = st["compiled_programs"]
    assert programs["prefill_chunk_buckets"] == 1
    assert programs["prefill_extend_buckets"] >= 1


@pytest.mark.parametrize("serve,match", [
    (dict(speculative="ngram"), r"\(K\) layers: speculative is refused"),
    (dict(preemption="swap", swap_space_gb=0.1),
     r"\(K\) layers: preemption: swap is refused"),
    (dict(kv_quantization="int8"), "kv_quantization int8 is refused"),
    (dict(tensor_parallel=2), "a model with a layer table serves plain"),
])
def test_what_a_k_state_cannot_follow_is_refused_by_name(cfg, params, serve,
                                                         match):
    with pytest.raises(ValueError, match=match):
        support.engine(cfg, params, **CHUNKS_OF_32, **serve)


def test_prefix_reuse_is_turned_off_and_page_transfer_refused(cfg, params):
    eng = support.engine(cfg, params, prefix_caching=True,
                         **CHUNKS_OF_32)
    assert not eng._prefix_caching
    eng.generate([support.tokens(20)], SamplingParams(temperature=0.0, max_tokens=2))
    assert eng.stats()["kda"]["refused"] == {"prefix_caching": 1}
    with pytest.raises(ValueError, match="fleet prefix fetch is refused"):
        eng.prefix_fetch_hook = lambda req, hashes: None
    with pytest.raises(ValueError, match=r"\(K\) layers: fleet prefix export"):
        eng.kv.extract_pages([1])
    with pytest.raises(ValueError, match="dropless inference forward only"):
        gpt.forward(params, jnp.asarray([support.tokens(8)]), cfg,
                    moe_impl="capacity")


def test_state_space_layers_still_refuse_chunked_prefill_by_name():
    hybrid = get_model_config("nemotron-h-test")
    with pytest.raises(ValueError, match="has state-space layers: "
                                         "chunked_prefill_tokens is refused"):
        support.engine(hybrid, chunked_prefill_tokens=32)


# -- the schema ------------------------------------------------------------------

def test_the_published_config_parses_to_the_54_entry_table():
    m = ModelConfig.from_published(
        support.catalog_row("Kimi-Linear-48B-A3B-Instruct"))
    assert len(m.layer_pattern) == m.num_layers == 54
    assert m.layer_pattern == "KDKEKE*E" + "KEKEKE*E" * 5 + "KEKE*E"
    assert (m.kda_layers, m.kv_layers, m.moe_layers) == (20, 7, 26)
    assert m.mla.q_lora_rank == 0 and m.position_embedding == "none"
    assert (m.moe.num_experts, m.moe.experts_per_token) == (256, 8)
    assert m.moe.router_score == "sigmoid" and m.moe.selection_bias
    assert m.moe.norm_topk_prob and m.moe.routed_scaling_factor == 2.446
    assert (m.moe.shared_expert_size, m.dense_ffn_size) == (1024, 9216)
    assert abs(m.param_count / 1e9 - 49.1) < 0.1


def test_the_cells_configuration_counts_3177_m_parameters():
    config = json.loads((ROOT / "benchmark" / "configs"
                         / "kimi-linear-48b-a3b-12l-ep8.json").read_text())
    m = ModelConfig.from_published(config)
    assert m.layer_pattern == "KDKEKE*EKEKEKE*EKEKEKE*E"
    assert (m.moe.num_experts, m.moe.router_width, m.vocab_size) == (
        32, 256, 20480)
    assert m.param_count == 3_176_867_744             # 6.35 GB in bfloat16
    shapes = jax.eval_shape(lambda k: gpt.init(m, k, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        shapes)) == m.param_count


@pytest.mark.parametrize("change,match", [
    (dict(num_expert_group=4), "num_expert_group = 4"),
    (dict(topk_group=2), "topk_group = 2"),
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers = 1"),
    (dict(linear_attn_config=dict(
        C["linear_attn_config"], kda_layers=[1, 2, 3])), "name each of the"),
])
def test_what_the_schema_does_not_carry_is_refused_by_name(change, match):
    with pytest.raises(ConfigError, match=match):
        ModelConfig.from_published(dict(C, **change))


def test_one_recurrent_kind_a_model():
    with pytest.raises(ConfigError, match="M and K layers"):
        ModelConfig.from_dict(dict(
            name="both", num_layers=2, hidden_size=64, num_heads=4,
            vocab_size=256, layer_pattern="MK",
            ssm=dict(num_heads=4, head_dim=8), kda=dict(num_heads=4)))
