"""``solar_open2``: delta-rule (``K``) layers whose beta reaches 2 beside a
gated NoPE softmax layer over plain K/V pages, every feed-forward sigmoid-
routed experts with a shared one, and prefix reuse THROUGH the recurrent
state (one snapshot a prompt, at its last whole page boundary), on the CPU
at ``solar-open2-test`` widths with seeded random weights, held to the plain
float32 reference (``benchmark/reference/sessions_decoder.py``).

Tolerance: both sides compute in float32 with full-precision matmuls and
differ in the ORDER of their sums alone (the chunked WY form against the
token-by-token recurrence, paged against dense attention): logits of size
~0.16 agree to ~6e-7, and TOL = 2e-5 leaves that more than an order of
room. Every departure the chip's check is asked to refuse moves the
reference's logits by more than 20 x TOL
(``test_each_departure_moves_the_logits``). Served TOKENS are held to the
reference's greedy tokens: a second turn that was armed from a wrong state
leaves them at once.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serving_support as support
from benchmark.reference import sessions_decoder as ref
from distributed_llm_training_and_inference_system_tpu.config.presets import (
    SOLAR_OPEN2_TEST_PUBLISHED,
    get_model_config,
    solar_open2_test_share,
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
from distributed_llm_training_and_inference_system_tpu.ops import kda
from distributed_llm_training_and_inference_system_tpu.serve import decode
from distributed_llm_training_and_inference_system_tpu.serve.kv_cache import (
    PagedKVCache,
    refused,
)
from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
    Request,
    SamplingParams,
)

TOL = 2e-5
C = SOLAR_OPEN2_TEST_PUBLISHED
PS = 8
ROOT = Path(__file__).resolve().parents[1]


pytestmark = pytest.mark.usefixtures("short_kda_chunks")


@pytest.fixture(scope="module")
def cfg():
    return get_model_config("solar-open2-test")


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


def _reference(params, tokens, positions=None, wrong=None, config=C, **kw):
    """(one compiled length: a sub-layer compiles once a fault that
    concerns it)"""
    lg = ref.logits(params, tokens, config, wrong=wrong, pad_to=72,
                    compiled=True, positions=positions, **kw)
    return np.asarray(lg)


# -- the equations -------------------------------------------------------------

@pytest.mark.parametrize("held,first", [(16, 0), (4, 0), (4, 8)])
def test_the_forward_is_the_reference(cfg, params, held, first):
    """The whole forward (gated NoPE softmax layers, delta-rule layers with
    beta in (0, 2), the held share of the experts and the shared one) at
    every position of a 70-token sequence, all experts held and a quarter
    of them."""
    share = cfg if held == 16 else solar_open2_test_share(held, first)
    tree = params
    if held != 16:
        moe = dict(params["blocks"]["moe"], **{
            n: {"kernel": params["blocks"]["moe"][n]["kernel"][
                :, first:first + held]} for n in ("gate", "up", "down")})
        tree = dict(params, blocks=dict(params["blocks"], moe=moe))
    tokens = support.tokens(70, 1)
    with jax.default_matmul_precision("highest"):
        got = gpt.forward(tree, jnp.asarray([tokens]), share)[0]
    want = _reference(tree, tokens, config=dict(
        C, n_routed_experts=held, router_experts=16, first_expert=first))
    assert np.abs(np.asarray(got) - want).max() < TOL
    assert want.std() > 0.1


@pytest.mark.parametrize("wrong", [
    "beta_unscaled", "no_gate", "rope", "bf16_state", "zero_at_hit",
    "stale_at_hit", "no_renorm"])
def test_each_departure_moves_the_logits(params, wrong):
    tokens = support.tokens(70, 1)
    right = _reference(params, tokens)
    moved = _reference(params, tokens, wrong=wrong, hit=32, page=PS)
    assert np.abs(moved - right).max() > 20 * TOL


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(cfg, params):
    """Four chips' shares of one expert layer (4 of 16 experts each, the
    shared expert counted ONCE) add up to the layer with all 16 held."""
    moe = jax.tree_util.tree_map(lambda a: a[1], params["blocks"]["moe"])
    whole = {n: moe[n]["kernel"] for n in ("gate", "up", "down")}
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 19, cfg.hidden_size))

    def share(first, shared=False):
        c = solar_open2_test_share(4, first)
        if not shared:
            c = dataclasses.replace(c, moe=dataclasses.replace(
                c.moe, shared_expert_size=0))
        layer = dict(moe, **{n: {"kernel": whole[n][first:first + 4]}
                             for n in whole})
        return experts_mixer(h, layer, c, None, "dropless", None)[0]
    with jax.default_matmul_precision("highest"):
        routed = sum(share(first) for first in (0, 4, 8, 12))
        shared = share(0, shared=True) - share(0)
        uncut = experts_mixer(h, moe, cfg, None, "dropless", None)[0]
    assert np.abs(np.asarray(shared)).max() > 1e-3
    assert np.abs(np.asarray(routed + shared - uncut)).max() < 1e-5


def _delta_rule(q, k, v, g, beta, S):
    """The gated delta rule token by token in float64 numpy."""
    q, k, v, g, beta, S = (np.asarray(a, np.float64)
                           for a in (q, k, v, g, beta, S))
    out = np.zeros(v.shape)
    for b in range(q.shape[0]):
        for t in range(q.shape[1]):
            Sd = S[b] * np.exp(g[b, t])[:, :, None]
            u = beta[b, t][:, None] * (v[b, t] - np.einsum(
                "hkv,hk->hv", Sd, k[b, t]))
            S[b] = Sd + k[b, t][:, :, None] * u[:, None, :]
            out[b, t] = np.einsum("hkv,hk->hv", S[b], q[b, t])
    return out, S


def test_beta_up_to_two_through_the_chunked_form_and_the_step(cfg):
    """``beta = 2 sigmoid(b)`` reaches past 1 (the factor ``I - beta k k^T``
    then reflects), and both forms of the recurrence follow the
    token-by-token rule there: the chunked WY form over a window from a
    non-zero state, and the one-step update."""
    kd = cfg.kda
    assert kd.allow_neg_eigval
    nh, d, B, S = kd.num_heads, kd.head_dim, 2, 27
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 8))
    act = jax.random.normal(next(keys), (B, S, 3 * nh * d))
    f = jax.random.normal(next(keys), (B, S, nh * d))
    b = 3.0 * jax.random.normal(next(keys), (B, S, nh))
    p = {"A_log": jnp.log(jax.random.uniform(next(keys), (nh,), minval=1.0,
                                             maxval=4.0)),
         "dt_bias": jax.random.normal(next(keys), (nh * d,)) - 3.0}
    q, k, v, g, beta = kda._heads(act, f, b, p, kd)
    assert float(beta.max()) > 1.5 and float(beta.min()) < 0.5
    plain = dataclasses.replace(kd, allow_neg_eigval=False)
    assert np.allclose(kda._heads(act, f, b, p, plain)[4] * 2, beta)
    S0 = 0.3 * jax.random.normal(next(keys), (B, nh, d, d))
    want, S_want = _delta_rule(q, k, v, g, beta, S0)
    with jax.default_matmul_precision("highest"):
        got, S_got = kda.kda_chunk_prefill(q, k, v, g, beta, S0, 8)
    assert np.abs(np.asarray(got) - want).max() < TOL
    assert np.abs(np.asarray(S_got) - S_want).max() < TOL
    one, S_one = kda.kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0], S0)
    want1, S_want1 = _delta_rule(q[:, :1], k[:, :1], v[:, :1], g[:, :1],
                                 beta[:, :1], S0)
    assert np.abs(np.asarray(one) - want1[:, 0]).max() < TOL
    assert np.abs(np.asarray(S_one) - S_want1).max() < TOL


# -- the programs over the pools: logits ---------------------------------------

def _pools(cfg, slots=3, entries=2):
    kv = PagedKVCache(cfg, num_slots=slots, max_seq_len=128, page_size=PS,
                      num_pages=48, dtype=jnp.float32,
                      snapshot_entries=entries)
    return kv


def _programs(cfg):
    """The body of the chunk and suffix programs (ONE slot's window of 16
    rows from its own state) and of a decode step, jitted once."""
    def window(params, tokens, start, live, kp, vp, table, state, slot):
        out = decode.extend_step_forward(
            params, tokens, start, kp, vp, table, cfg,
            write_ok=jnp.arange(16)[None] < live, ssm_state=state,
            state_slot=slot)
        return out.logits[0], out.k_pages, out.v_pages, out.state

    def step(params, toks, pos, kp, vp, tables, active, state):
        out = decode.decode_step_forward(
            params, toks, pos, kp, vp, tables, cfg, active=active,
            ssm_state=state)
        return out.logits, out.k_pages, out.v_pages, out.state
    return jax.jit(window), jax.jit(step)


def _window(window, params, kv, slot, tokens, start):
    n = len(tokens)
    padded = np.full((1, 16), 7, np.int32)           # garbage padding
    padded[0, :n] = tokens
    lg, kv.k_pages, kv.v_pages, kv.state = window(
        params, padded, np.array([start], np.int32), np.int32(n),
        kv.k_pages, kv.v_pages, kv.block_tables[slot][None], kv.state,
        np.int32(slot))
    return np.asarray(lg)[:n]


def test_windows_a_snapshot_and_decode_steps_over_the_pools(cfg, params):
    """Slot 1 prefills a prompt window by window (each from the slot's own
    state and conv window, K and V into its pages), its rows of the state
    pools are copied to entry 1 at token 32, and it decodes behind the
    prompt: every logit against the reference's full forward. Slot 2, over
    the SAME first four pages, is armed from the entry and prefills the
    rest: the reference's logits again, and those of a slot that starts
    there from whatever it held are not."""
    window, step = _programs(cfg)
    tokens = support.tokens(60, 2)
    want = _reference(params, tokens)
    kv = _pools(cfg)
    kv.allocate(1, 64)
    with jax.default_matmul_precision("highest"):
        got = [_window(window, params, kv, 1, tokens[0:16], 0),
               _window(window, params, kv, 1, tokens[16:32], 16)]
        snaps = kv.snapshots
        conv, ssm = kda.kda_snapshot_take(
            kv.state["conv"], kv.state["ssm"], snaps["conv"], snaps["ssm"],
            jnp.int32(1), jnp.int32(1))
        assert np.array_equal(np.asarray(ssm[:, 1]),
                              np.asarray(kv.state["ssm"][:, 1]))
        assert not np.asarray(ssm[:, 0]).any()
        got.append(_window(window, params, kv, 1, tokens[32:45], 32))
        for i, t in enumerate(tokens[45:52]):           # decode steps
            toks, pos = np.zeros((2, 3), np.int32)
            toks[1], pos[1] = t, 45 + i
            lg, kv.k_pages, kv.v_pages, kv.state = step(
                params, toks, pos, kv.k_pages, kv.v_pages, kv.block_tables,
                np.arange(3) == 1, kv.state)
            got.append(np.asarray(lg)[1:2])
        assert np.abs(np.concatenate(got) - want[:52]).max() < TOL
        # slot 2 takes the prefix's four pages and fresh ones behind them
        kv.pin_pages(list(kv.block_tables[1, :4]))
        kv.allocate(2, 64, prefix_pages=list(kv.block_tables[1, :4]))
        unarmed = _window(window, params, kv, 2, tokens[32:45], 32)
        a, b = kda.kda_snapshot_arm(kv.state["conv"], kv.state["ssm"], conv,
                                    ssm, jnp.int32(2), jnp.int32(1))
        kv.state = {"conv": a, "ssm": b}
        armed = _window(window, params, kv, 2, tokens[32:45], 32)
    assert np.abs(armed - want[32:45]).max() < TOL
    assert np.abs(unarmed - want[32:45]).max() > 100 * TOL


def test_an_entry_goes_with_the_page_it_stands_on(cfg):
    """The host's bookkeeping alone: a chain is followed as far as a
    snapshot stands, and when an allocation evicts the page the entry
    stands on, the entry is free again."""
    from distributed_llm_training_and_inference_system_tpu.serve.kv_cache \
        import prefix_page_hashes
    kv = PagedKVCache(cfg, num_slots=2, max_seq_len=64, page_size=PS,
                      num_pages=6, dtype=jnp.float32, snapshot_entries=2)
    hashes = prefix_page_hashes(support.tokens(24, 9), PS)
    kv.allocate(0, 24)
    pages = [int(p) for p in kv.block_tables[0, :3]]
    kv.register_pages(list(zip(hashes, pages)))
    assert kv.lookup_prefix(hashes) == [] and kv.hashed_pages(hashes) == 3
    entry = kv.claim_snapshot(hashes[1])
    assert kv.claim_snapshot(hashes[1]) is None        # it has one
    assert kv.lookup_prefix(hashes) == pages[:2]
    assert kv.lookup_prefix(hashes[:1]) == []
    kv.release(0)
    assert kv.snapshot_at(hashes[1]) == entry and kv.free_pages == 5
    kv.allocate(1, 40)          # 5 pages: 2 free ones and the chain's 3
    assert kv.snapshot_at(hashes[1]) is None and kv.lookup_prefix(hashes) == []
    assert (kv.snapshot_evictions, len(kv._snap_free)) == (1, 2)
    # room: the least recently used entry goes, a pinned one never
    other = prefix_page_hashes(support.tokens(24, 10), PS)
    e0, e1 = kv.claim_snapshot(other[0]), kv.claim_snapshot(other[1])
    kv.pin_snapshot(other[0])
    assert kv.claim_snapshot(other[2]) == e1 and kv.snapshot_at(other[1]) is None
    kv.pin_snapshot(other[2])
    assert kv.claim_snapshot(hashes[0]) is None        # every entry pinned
    kv.unpin_snapshot(other[0])
    assert kv.claim_snapshot(hashes[0]) == e0
    kv.flush_prefix_cache()
    assert not kv._snap_of and not kv._snap_lru and len(kv._snap_free) == 2


# -- the engine ------------------------------------------------------------------

# chunks of 32 tokens and snapshots of the state at 8 of the page boundaries
# they end on: the properties below are of prefix reuse behind a snapshot
SNAPSHOTS = dict(chunked_prefill_tokens=32, state_snapshot_entries=8)


def _last_logits(params):
    # (one compiled length for every step of every prompt)
    return lambda context: ref.logits(
        params, context, C, pad_to=192, compiled=True,
        positions=[len(context) - 1])[0]


GREEDY = SamplingParams(temperature=0.0, max_tokens=6)


_resident_seeds = iter(range(500, 600))


def _busy(eng, tag):
    """Two residents decode (half the slots): what is admitted next rides."""
    long = SamplingParams(temperature=0.0, max_tokens=40)
    for i, n in enumerate((9, 13)):
        assert eng.scheduler.add_request(Request(
            f"res-{tag}-{i}", support.tokens(n, next(_resident_seeds)), long))
    while eng.active.sum() < 2:     # (two chunks each: one ends at the cut)
        eng.step()


def _serve(eng, prompt, tag, busy=False):
    if busy:
        _busy(eng, tag)
    req = Request(f"p-{tag}", prompt, GREEDY)
    assert eng.scheduler.add_request(req)
    eng.run_until_idle()
    return req.generated_tokens


@pytest.fixture(scope="module")
def engine(cfg, params):
    return support.engine(cfg, params, **SNAPSHOTS)


def test_cold_and_chunked_prompts_and_the_decode_steps_behind_them(
        cfg, params, engine):
    kv = engine.stats()["kv"]
    assert kv["kind"] == "kv" and engine.kv.v_pages is not None
    k = cfg.kda
    assert engine.kv.state["ssm"].shape == (3, 4, k.num_heads, 16, 16)
    assert engine.kv.snapshots["ssm"].shape == (3, 8, k.num_heads, 16, 16)
    assert engine.kv.snapshots["conv"].shape == (3, 3, 8, k.conv_channels)
    assert decode.can_carry(cfg) and engine._ride_rows == 2 * PS
    with jax.default_matmul_precision("highest"):
        # under a page: the cold program from a zero state, no snapshot;
        # longer: chunk by chunk with one chunk ending at the cut
        for n, seed in ((7, 1), (20, 2), (100, 3)):
            prompt = support.tokens(n, seed)
            assert _serve(engine, prompt, f"plain{n}") == support.greedy(
                _last_logits(params), prompt, 6)
    st = engine.stats()
    assert st["compiled_programs"]["prefill_dense_buckets"] == 1
    assert st["kda"]["snapshots_taken"] == 2
    assert st["kda"]["snapshot_hits"] == st["kda"]["snapshot_misses"] == 0


@pytest.mark.parametrize("busy", [False, True], ids=["chunked", "riding"])
def test_a_second_turn_is_served_from_its_snapshot(cfg, params, engine, busy):
    """Turn 1 leaves its pages and ONE snapshot at its last page boundary;
    turn 2 (turn 1, its reply, a new message) is armed from it and
    prefills from there, by the chunk programs of an idle engine or riding
    the decode steps of a busy one: the reference's tokens either way."""
    tag = "ride" if busy else "chunk"
    before = engine.stats()
    first = support.tokens(70, 20 + busy)
    with jax.default_matmul_precision("highest"):
        reply = _serve(engine, first, f"t1{tag}", busy)
        assert reply == support.greedy(_last_logits(params), first, 6)
        second = first + reply + support.tokens(21, 30 + busy)
        got = _serve(engine, second, f"t2{tag}", busy)
        assert got == support.greedy(_last_logits(params), second, 6)
    after = engine.stats()
    d = {k: after["kda"][k] - before["kda"][k] for k in (
        "snapshots_taken", "snapshot_hits", "snapshot_misses",
        "snapshot_tokens_skipped")}
    # (the residents of a busy engine are under two pages: one cut each)
    assert d == {"snapshots_taken": 2 + 4 * busy, "snapshot_hits": 1,
                 "snapshot_misses": 0, "snapshot_tokens_skipped": 64}
    assert (after["prefix_cached_tokens"] - before["prefix_cached_tokens"]
            == 64)
    rode = after["prefill_ride_tokens"] - before["prefill_ride_tokens"]
    assert rode == (70 + len(second) - 64 if busy else 0)


def test_a_chain_without_a_snapshot_is_prefilled_from_zero(cfg, params):
    """ONE entry: a second session's snapshot takes the first's room. The
    first session's next turn finds its pages hashed and no snapshot on
    them: a miss, prefilled from zero, and still right."""
    eng = support.engine(cfg, params, **{**SNAPSHOTS,
                                       "state_snapshot_entries": 1})
    a, b = support.tokens(70, 40), support.tokens(70, 41)
    with jax.default_matmul_precision("highest"):
        ra = _serve(eng, a, "a1")
        _serve(eng, b, "b1")
        assert eng.kv.snapshot_evictions == 1
        again = a + ra + support.tokens(11, 42)
        assert _serve(eng, again, "a2") == support.greedy(
            _last_logits(params), again, 6)
    st = eng.stats()["kda"]
    assert (st["snapshot_hits"], st["snapshot_misses"]) == (0, 1)
    assert st["snapshot_tokens_skipped"] == 0
    assert st["snapshot_entries_live"] == 1


def test_a_snapshot_pool_adds_two_programs_and_changes_none(cfg, params,
                                                            engine):
    """``state_snapshot_entries`` 0 is the engine as it was: no pool, prefix
    reuse off and counted, and the SAME decode program text as with a pool
    (the copies are programs of their own)."""
    plain = support.engine(cfg, params, **{**SNAPSHOTS,
                                         "state_snapshot_entries": 0})
    assert plain.kv.snapshots is None and not plain._prefix_caching
    assert plain._snapshot_take is None
    with jax.default_matmul_precision("highest"):
        prompt = support.tokens(20, 80)        # the cold program, three pages
        assert _serve(plain, prompt, "off") == support.greedy(
            _last_logits(params), prompt, 6)
    st = plain.stats()
    assert st["compiled_programs"]["prefill_dense_buckets"] == 1
    assert st["kda"]["refused"] == {"prefix_caching": 1}
    assert "snapshots_taken" not in st["kda"]
    assert "snapshot" not in st["compiled_programs"]

    def text(eng):
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (eng.params, eng.kv.k_pages, eng.kv.v_pages,
             *eng._decode_head_args(), *eng._shared_decode_args(),
             *eng._decode_tail_args()))
        return eng._decode_jit.lower(*shapes).as_text()
    assert text(plain) == text(engine)
    assert engine.stats()["compiled_programs"]["snapshot"] == 2


def test_what_keeps_no_snapshot_keeps_prefix_reuse_off():
    hybrid = get_model_config("nemotron-h-test")
    linear = get_model_config("kimi-linear-test")
    assert refused(hybrid, "prefix_caching", 8)         # M layers: no pair
    assert refused(linear, "prefix_caching", 0)
    assert refused(linear, "prefix_caching", 8) is None
    assert refused(get_model_config("gpt-test"), "prefix_caching") is None
    kv = PagedKVCache(hybrid, num_slots=2, max_seq_len=64, page_size=PS,
                      num_pages=8, dtype=jnp.float32, snapshot_entries=4)
    assert kv.snapshots is None and kv.snapshot_entries == 0
    for feature in ("speculative", "preemption: swap", "page payload",
                    "fleet serving", "fleet prefix fetch"):
        assert refused(linear, feature, 8)


# -- the schema ------------------------------------------------------------------

def test_the_published_config_parses_to_the_96_entry_table():
    m = ModelConfig.from_published(
        support.catalog_row("Solar-Open2-250B"))
    assert m == dataclasses.replace(get_model_config("solar-open2-250b"),
                                    name=m.name)
    assert len(m.layer_pattern) == m.num_layers == 96
    assert m.layer_pattern == "*EKEKEKE" * 12
    assert (m.kda_layers, m.kv_layers, m.moe_layers) == (36, 12, 48)
    assert m.position_embedding == "none" and m.attention_gate
    assert m.kda == dataclasses.replace(m.kda, num_heads=64, head_dim=128,
                                        conv_kernel=4, allow_neg_eigval=True)
    assert (m.num_heads, m.num_kv_heads, m.head_dim) == (64, 8, 128)
    assert (m.moe.num_experts, m.moe.experts_per_token) == (320, 8)
    assert m.moe.router_score == "sigmoid" and m.moe.selection_bias
    assert m.moe.norm_topk_prob and m.moe.routed_scaling_factor == 1.0
    assert (m.moe.shared_expert_size, m.dense_ffn_size, m.ffn_size) == (
        1280, 0, 1280)
    # by hand: a routed expert 3 x 4096 x 1280; a layer's 320 + the shared
    # one + the router; a gated softmax mixer q, o, gate 4096 x 8192 and k,
    # v 4096 x 1024; a K mixer 4096 x (3 x 8192 + 2 x 128 + 64) in, 8192 x
    # 4096 out, 2 x 128 x 8192 low-rank; embedding and head
    expert = 3 * 4096 * 1280
    by_hand = (48 * (321 * expert + 4096 * 320)
               + 12 * (3 * 4096 * 8192 + 2 * 4096 * 1024)
               + 36 * (4096 * 24896 + 8192 * 4096 + 2 * 128 * 8192)
               + 2 * 196608 * 4096)
    assert abs(by_hand / 250e9 - 1) < 0.02
    assert abs(m.param_count / by_hand - 1) < 0.001


def test_the_cells_configuration_counts_its_parameters():
    config = json.loads((ROOT / "benchmark" / "configs"
                         / "solar-open2-250b-4l-ep8.json").read_text())
    m = ModelConfig.from_published(config)
    assert m.layer_pattern == "*EKEKEKE"
    assert (m.moe.num_experts, m.moe.router_width, m.moe.first_expert,
            m.vocab_size) == (40, 320, 0, 24576)
    assert decode.can_carry(m) and gpt.table_period(m)[2] == 3
    shapes = jax.eval_shape(lambda k: gpt.init(m, k, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        shapes)) == m.param_count
    assert abs(m.param_count * 2 / 1e9 - 6.6) < 0.1      # bfloat16
    serve = ServeConfig(model=config["name"], **config["serve"])
    assert serve.state_snapshot_entries > 128 and serve.prefix_caching


@pytest.mark.parametrize("change,match", [
    (dict(kda_use_full_proj=True), "kda_use_full_proj"),
    (dict(gqa_layers=[0, 7]), "gqa_layers"),
    (dict(gqa_layers=[4, 0]), "gqa_layers"),
    (dict(linear_attn_config=dict(C["linear_attn_config"], num_kv_heads=2)),
     "num_kv_heads"),
])
def test_what_the_schema_does_not_carry_is_refused_by_name(change, match):
    with pytest.raises(ConfigError, match=match):
        ModelConfig.from_published(dict(C, **change))


def test_a_gate_lives_on_the_tables_kv_attention():
    with pytest.raises(ConfigError, match="attention_gate"):
        ModelConfig.from_dict(dict(name="g", num_layers=2, hidden_size=64,
                                   num_heads=4, vocab_size=256,
                                   attention_gate=True))
