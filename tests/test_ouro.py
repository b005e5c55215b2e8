"""Ouro-2.6B's architecture in small (``ouro-test``): a uniform stack of
sandwich-normed layers (a norm before AND after the attention, before AND
after the MLP, the second of each pair inside the residual) walked
``total_ut_steps`` times over ONE set of weights; the final norm closes
every pass and its output is what the next pass reads; an exit gate reads
each pass's normed state; K and V are kept per (pass, layer), so a pool has
passes x layers planes. Against the plain reference
(benchmark/reference/looped_decoder.py) on seeded NON-trivial weights (every
norm's scale, the gate's kernel and bias), on the CPU.

Covers (ISSUE 60): the schema's reading of the catalog row; ``gpt.forward``
(logits, every pass's state and gate; over a dense cache); cold prefill then
paged decode against the reference's full forward, LOGITS; the same through
a chunked prefill, a riding piece, a prefix-cache hit, both kinds of
preemption and n-gram verification; the pools' planes; the kernels' layer
operand; ``REFUSED`` asked feature by feature; training, pipeline stages and
a threshold below 1 refused by name; the checkpoint key map.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serving_support as support
from benchmark.reference import looped_decoder
from distributed_llm_training_and_inference_system_tpu.config import get_model_config
from distributed_llm_training_and_inference_system_tpu.config.presets import (
    OURO_2_6B_PUBLISHED,
    OURO_TEST_PUBLISHED as PUBLISHED,
)
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ConfigError,
    ModelConfig,
    ParallelConfig,
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
    extend_step_forward,
)

# Float32 on the CPU with exact float32 matmuls: the program and the
# reference differ in the ORDER of additions alone (a fused reduction against
# ``jnp.mean``, the gate as a multiply-and-sum against a matmul). Over 2
# passes of 3 layers of width 128 with logits of size ~1 that is measured
# 0 to 2e-6. 1e-4 is far above it, far under what bfloat16 anywhere moves
# the logits by (the stream rounded once: 1e-2), and under the least of the
# wrong references (0.4, asserted below).
TOL = 1e-4
PS = 8
SLOTS = 4
TABLE = np.zeros((SLOTS, 10), np.int32)
TABLE[1, :9] = range(3, 12)
TABLE[2, :10] = range(12, 22)
SP = SamplingParams(temperature=0.0, max_tokens=10)


@pytest.fixture(scope="module")
def cfg():
    return get_model_config("ouro-test")


def seeded(cfg, seed=0):
    """``gpt.init`` with what it leaves trivial made visible: every norm's
    scale (all four a layer and the final one; a unit scale hides a missing
    norm) and the gate's kernel and bias, in U(-0.3, 0.3)."""
    params = support.params_of(cfg, seed)
    key = jax.random.PRNGKey(seed + 100)
    count = iter(range(1000))

    def visible(path, leaf):
        names = tuple(k.key for k in path)
        if names[-1] == "scale" or names[0] == "exit_gate":
            return jax.random.uniform(jax.random.fold_in(key, next(count)),
                                      leaf.shape, jnp.float32, -0.3, 0.3)
        return leaf
    return jax.tree_util.tree_map_with_path(visible, params)


@pytest.fixture(scope="module")
def params(cfg):
    return seeded(cfg)


def _ref(params, tokens, wrong=None):
    with jax.default_matmul_precision("highest"):
        return np.asarray(looped_decoder.logits(params, tokens, PUBLISHED,
                                                wrong=wrong))


# -- the schema's reading of the row's keys ----------------------------------

def test_the_catalog_row_builds_the_looped_stack():
    row = support.catalog_row("Ouro-2.6B")
    cfg = ModelConfig.from_published(row)
    assert (cfg.num_layers, cfg.num_passes, cfg.exit_threshold) == (48, 4, 1.0)
    assert cfg.sandwich_norm and cfg.is_looped and not cfg.layer_pattern
    assert (cfg.hidden_size, cfg.ffn_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size) == (2048, 5632, 16, 16, 128, 49152)
    assert (cfg.rope.base, cfg.norm_eps) == (1e6, 1e-6)
    assert not cfg.tie_word_embeddings


def test_the_preset_is_the_row():
    row = support.catalog_row("Ouro-2.6B")
    assert {k: v for k, v in OURO_2_6B_PUBLISHED.items()
            if k != "name"} == row
    assert dataclasses.replace(ModelConfig.from_published(row),
                               name="ouro-2.6b") == get_model_config(
                                   "ouro-2.6b")


def test_the_pool_has_passes_times_layers_planes(cfg):
    big = get_model_config("ouro-2.6b")
    assert big.kv_layers == 192
    # 2 (K and V) x 16 heads x 128 x 2 B = 8,192 B a plane
    assert big.kv_bytes_per_token() == 192 * 8192 == 1_572_864
    assert cfg.kv_layers == 6
    assert cfg.kv_bytes_per_token(4) == 6 * 2 * 2 * 64 * 4


def test_param_count_is_the_sum_shown():
    big = get_model_config("ouro-2.6b")
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    # 48 layers + embedding and head + the final norm + the gate and its bias
    assert big.param_count == (48 * layer + 2 * 49152 * 2048 + 2048
                               + 2049) == 2_667_974_657


def test_param_count_is_the_tree(cfg, params):
    assert cfg.param_count == sum(
        leaf.size for leaf in jax.tree_util.tree_leaves(params))
    assert params["exit_gate"]["kernel"].shape == (cfg.hidden_size, 1)
    assert params["blocks"]["attn_out_norm"]["scale"].shape == (
        3, cfg.hidden_size)


def test_a_threshold_below_1_is_refused_at_load():
    with pytest.raises(ConfigError) as e:
        ModelConfig.from_published({**PUBLISHED, "early_exit_threshold": 0.9})
    assert "early_exit_threshold = 0.9" in str(e.value)
    assert "writes no K/V in the planes of passes" in str(e.value)


@pytest.mark.parametrize("change", [
    {"layer_pattern": "*D*D*D", "num_hidden_layers": 6},
    {"num_experts": 4, "num_experts_per_tok": 2},
    {"diffusion": {"block_length": 4, "denoising_steps": 4,
                   "mask_token_id": 5}},
], ids=["a layer table", "experts", "diffusion"])
def test_the_loop_is_carried_by_the_dense_uniform_stack(change):
    d = {k: v for k, v in PUBLISHED.items() if not isinstance(v, list)}
    with pytest.raises(ConfigError, match="looped stack"):
        ModelConfig.from_dict({**d, **change})


def test_one_pass_and_plain_norms_is_the_stack_as_it_was():
    plain = get_model_config("gpt-test")
    assert (plain.num_passes, plain.sandwich_norm, plain.is_looped) == (
        1, False, False)
    assert plain.kv_layers == plain.num_layers
    tree = support.params_of(plain)
    assert "exit_gate" not in tree and "attn_out_norm" not in tree["blocks"]


# -- the forward against the reference ---------------------------------------

def _forward_passes(params, tokens, *, cfg):
    return gpt.forward(params, tokens, cfg, return_passes=True)


@pytest.fixture(scope="module")
def forwarded(cfg, params):
    """(tokens, the program's (logits, (z, g)), the reference's)."""
    tokens = support.tokens(29, seed=1)
    with jax.default_matmul_precision("highest"):
        got = support.program(_forward_passes, cfg)(
            params, jnp.asarray([tokens]))
        want = looped_decoder.logits(params, tokens, PUBLISHED,
                                     with_passes=True)
    return tokens, got, want


def test_forward_logits_match_the_reference(forwarded):
    _, (logits, _), (want, _) = forwarded
    assert np.abs(np.asarray(logits)[0] - np.asarray(want)).max() < TOL


def test_every_pass_state_matches_the_reference(forwarded):
    _, (_, (z, _)), (_, (want, _)) = forwarded
    assert z.shape == (2, 1, 29, 128)
    assert np.abs(np.asarray(z)[:, 0] - np.asarray(want)).max() < TOL
    # (the passes differ: a pass is not a copy of the one before)
    assert np.abs(np.asarray(want)[0] - np.asarray(want)[1]).max() > 0.1


def test_every_pass_gate_matches_the_reference(forwarded):
    _, (_, (_, g)), (_, (_, want)) = forwarded
    assert g.shape == (2, 1, 29) and g.dtype == jnp.float32
    assert np.abs(np.asarray(g)[:, 0] - np.asarray(want)).max() < 1e-5
    # the seeded gate is not the trivial one half
    assert np.abs(np.asarray(want) - 0.5).max() > 0.05


def test_the_exit_rule_at_threshold_1_leaves_after_the_last_pass(forwarded):
    _, _, (_, (_, g)) = forwarded
    assert np.all(np.asarray(looped_decoder.exit_pass(g, 1.0)) == 1)
    # ... and under a lower threshold the reference's rule leaves early
    # where the first gate is open enough (what the program refuses to load)
    early = np.asarray(looped_decoder.exit_pass(g, 0.5))
    assert np.array_equal(early == 0, np.asarray(g)[0] >= 0.5)


def test_forward_over_a_dense_cache_fills_every_plane(cfg, params, forwarded):
    tokens, (logits, _), _ = forwarded
    with jax.default_matmul_precision("highest"):
        cached, (k, v) = gpt.forward(
            params, jnp.asarray([tokens]), cfg,
            kv_cache=gpt.init_kv_cache(cfg, 1, 32, jnp.float32),
            cache_offset=jnp.zeros((1,), jnp.int32))
    assert k.shape == v.shape == (6, 1, 32, 2, 64)
    assert np.abs(np.asarray(cached) - np.asarray(logits)).max() < TOL
    assert all(np.abs(np.asarray(k)[p, 0, :29]).max() > 0 for p in range(6))


def test_return_passes_needs_a_looped_stack():
    plain = get_model_config("gpt-test")
    with pytest.raises(ValueError, match="looped stack"):
        gpt.forward(support.params_of(plain), jnp.zeros((1, 4), jnp.int32),
                    plain, return_passes=True)


@pytest.mark.parametrize("wrong", looped_decoder.WRONG)
def test_the_comparison_fails_each_wrong_reference(params, forwarded, wrong):
    tokens, _, (want, _) = forwarded
    assert np.abs(_ref(params, tokens, wrong)
                  - np.asarray(want)).max() > 1000 * TOL


def test_flops_count_every_pass(cfg):
    once = dataclasses.replace(cfg, num_passes=1)
    head = 6.0 * cfg.hidden_size * cfg.vocab_size
    assert gpt.flops_per_token(cfg, 64) - head == pytest.approx(
        2 * (gpt.flops_per_token(once, 64) - head))


# -- prefill, then decode, chunks, a riding piece: the pools' planes ---------

def _pools(cfg, n_pages=40):
    k = kv_cache.PagedKVCache(cfg, SLOTS, 80, page_size=PS, num_pages=n_pages,
                              dtype=jnp.float32)
    return k.k_pages, k.v_pages


def _cold_program(params, padded, *, cfg):
    return gpt.forward(
        params, padded, cfg,
        kv_cache=gpt.init_kv_cache(cfg, 1, padded.shape[1],
                                   dtype=jnp.float32),
        cache_offset=jnp.zeros((1,), jnp.int32))


def _chunk_program(params, rows, start, kp, vp, table, ok, *, cfg):
    return extend_step_forward(params, rows, start, kp, vp, table, cfg,
                               write_ok=ok)


def _decode_program(params, toks, pos, kp, vp, table, active, ride, *, cfg):
    return decode_step_forward(params, toks, pos, kp, vp, table, cfg,
                               active=active, ride=ride)


def _cold_prefill(cfg, params, tokens, bucket, kp, vp, pages):
    """What the engine's prefill program does: the dense forward over a
    padded bucket, every (pass, layer)'s K/V laid out as pages."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        write_prompt_to_pages)
    n = len(tokens)
    padded = np.full((1, bucket), 7, np.int32)      # garbage padding
    padded[0, :n] = tokens
    logits, cache = support.program(_cold_program, cfg)(
        params, jnp.asarray(padded))
    kp, vp = write_prompt_to_pages((kp, vp), cache,
                                   jnp.asarray(pages[:bucket // PS]))
    return np.asarray(logits)[0, :n], kp, vp


def _decode(cfg, params, toks, pos, kp, vp, active, ride=None):
    return support.program(_decode_program, cfg)(
        params, jnp.asarray(toks, jnp.int32), jnp.asarray(pos, jnp.int32),
        kp, vp, jnp.asarray(TABLE), jnp.asarray(active), ride)


def _decode_one(cfg, params, tok, pos, kp, vp, ride=None):
    """One decode step of four slots of which slot 1 is live."""
    toks = np.full(SLOTS, 11, np.int32)                 # idle slots' garbage
    toks[1] = tok
    return _decode(cfg, params, toks, np.full(SLOTS, pos), kp, vp,
                   [False, True, False, False], ride)


@pytest.fixture(scope="module")
def served_sequence(cfg, params):
    """A sequence through cold prefill (padded bucket, garbage padding) and
    eight decode steps: (tokens, prompt length, every position's logits, the
    pools after)."""
    seq, n = support.tokens(37 + 8, seed=2), 37
    kp, vp = _pools(cfg)
    got = np.zeros((len(seq), cfg.vocab_size), np.float32)
    with jax.default_matmul_precision("highest"):
        got[:n], kp, vp = _cold_prefill(cfg, params, seq[:n], 48, kp, vp,
                                        list(TABLE[1, :6]))
        for pos in range(n, len(seq)):
            step = _decode_one(cfg, params, seq[pos], pos, kp, vp)
            kp, vp = step.k_pages, step.v_pages
            got[pos] = np.asarray(step.logits)[1]
    return seq, n, got, kp, vp


def test_prefill_then_decode_matches_the_reference(params, served_sequence):
    seq, _, got, kp, _ = served_sequence
    assert kp.shape == (6, 40, 1, PS, 128)      # 2 heads of 64: ONE pair
    assert np.abs(got - _ref(params, seq)).max() < TOL


def _plane_rows(pool, plane, n):
    """Slot 1's first ``n`` rows of one plane: [n, Nkv, D] (the pool lays a
    PAIR of heads of 64 side by side on 128 lanes)."""
    pages = np.asarray(pool)[plane, TABLE[1, :-(-n // PS)], 0]   # [P, PS, 128]
    return pages.reshape(-1, 2, 64)[:n]


def test_plane_t_l_holds_pass_t_layer_l(params, served_sequence):
    """After serving one sequence, plane ``t * L + l`` of its pages is the
    reference's K and V of pass t, layer l (prompt rows through cold
    prefill, the rest through decode steps), and is NOT another pass's."""
    seq, _, _, kp, vp = served_sequence
    with jax.default_matmul_precision("highest"):
        *_, kvs = looped_decoder.passes(params, seq, PUBLISHED, with_kv=True)
    n = len(seq)
    for t in range(2):
        for layer in range(3):
            k, v = (np.asarray(a) for a in kvs[t, layer])
            plane = t * 3 + layer
            assert np.abs(_plane_rows(kp, plane, n) - k).max() < TOL
            assert np.abs(_plane_rows(vp, plane, n) - v).max() < TOL
            other = (1 - t) * 3 + layer
            assert np.abs(_plane_rows(kp, other, n) - k).max() > 0.1
            assert np.abs(_plane_rows(vp, other, n) - v).max() > 0.01


def test_a_prompt_split_across_chunks_matches_the_reference(cfg, params):
    """A prompt of 37 tokens as chunks of 16, 16 and 5 rows through the
    chunk program's forward (suffix and chunked prefill run it): each chunk
    attends, in every pass, over that pass's own planes of the chunks
    before it."""
    seq, n = support.tokens(37 + 3, seed=7), 37
    kp, vp = _pools(cfg)
    want = _ref(params, seq)
    with jax.default_matmul_precision("highest"):
        for start in range(0, n, 16):
            live = min(16, n - start)
            rows = np.full((1, 16), 9, np.int32)
            rows[0, :live] = seq[start:start + live]
            step = support.program(_chunk_program, cfg)(
                params, jnp.asarray(rows), jnp.asarray([start], jnp.int32),
                kp, vp, jnp.asarray(TABLE[1:2]),
                (jnp.arange(16) < live)[None])
            kp, vp = step.k_pages, step.v_pages
            got = np.asarray(step.logits)[0, :live]
            assert np.abs(got - want[start:start + live]).max() < TOL
        for pos in range(n, len(seq)):
            step = _decode_one(cfg, params, seq[pos], pos, kp, vp)
            kp, vp = step.k_pages, step.v_pages
            assert np.abs(np.asarray(step.logits)[1] - want[pos]).max() < TOL


@pytest.mark.parametrize("n", [16 + 5, 2 * 16, 1],
                         ids=["two pieces", "whole pieces", "one token"])
def test_a_riding_piece_matches_the_reference(cfg, params, n):
    """A prompt of ``n`` tokens rides slot 1's decode steps in pieces of 16
    rows into slot 2: in every pass the piece's rows join the step's, write
    that pass's planes of their own slot's pages and attend over them."""
    assert can_carry(cfg)
    seq, prompt, C = (support.tokens(30 + 6, seed=5),
                      support.tokens(n + 3, seed=6), 16)
    kp, vp = _pools(cfg)
    want_seq, want_prompt = _ref(params, seq), _ref(params, prompt)
    with jax.default_matmul_precision("highest"):
        _, kp, vp = _cold_prefill(cfg, params, seq[:30], 32, kp, vp,
                                  list(TABLE[1, :4]))
        pos = 30
        for start in range(0, n, C):
            live = min(C, n - start)
            rows = np.full(C, 9, np.int32)          # garbage past the live
            rows[:live] = prompt[start:start + live]
            piece = Piece(jnp.int32(2), jnp.int32(start), jnp.int32(live),
                          jnp.int32(0), jnp.asarray(rows))
            step = _decode_one(cfg, params, seq[pos], pos, kp, vp, piece)
            kp, vp = step.k_pages, step.v_pages
            lg = np.asarray(step.logits)
            assert lg.shape == (SLOTS + 1, cfg.vocab_size)
            assert np.abs(lg[1] - want_seq[pos]).max() < TOL
            pos += 1
        assert np.abs(lg[SLOTS] - want_prompt[n - 1]).max() < TOL
        # slot 2 decodes behind its pieces, slot 1 beside it
        for j in range(n, n + 3):
            toks = np.full(SLOTS, 11, np.int32)
            toks[1], toks[2] = seq[pos], prompt[j]
            step = _decode(cfg, params, toks, [0, pos, j, 0], kp, vp,
                           [False, True, True, False])
            kp, vp = step.k_pages, step.v_pages
            lg = np.asarray(step.logits)
            assert np.abs(lg[1] - want_seq[pos]).max() < TOL
            assert np.abs(lg[2] - want_prompt[j]).max() < TOL
            pos += 1


def test_the_kernels_layer_operand_is_the_pools_plane(cfg):
    """``ops/paged_attention.py`` (and the Pallas kernel behind it) take
    the POOL's index as ``layer``: plane 4 of a 6-plane pool (pass 1, layer
    1) read through ``layer=4`` is that plane read alone. Nothing in them
    knows a layer of weights from a plane."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        paged_attention_multi)
    rng = np.random.default_rng(3)
    kp, vp = (jnp.asarray(rng.normal(size=(6, 40, 1, PS, 128)), jnp.float32)
              for _ in range(2))
    q = jnp.asarray(rng.normal(size=(SLOTS, 1, 2, 64)), jnp.float32)
    starts = jnp.asarray([0, 20, 33, 0], jnp.int32)
    whole = paged_attention_multi(q, kp, vp, jnp.asarray(TABLE), starts,
                                  layer=jnp.int32(4))
    alone = paged_attention_multi(q, kp[4:5], vp[4:5], jnp.asarray(TABLE),
                                  starts, layer=jnp.int32(0))
    assert np.array_equal(np.asarray(whole), np.asarray(alone))
    other = paged_attention_multi(q, kp, vp, jnp.asarray(TABLE), starts,
                                  layer=jnp.int32(1))
    assert np.abs(np.asarray(whole) - np.asarray(other))[1:3].max() > 0.01


# -- the engine ----------------------------------------------------------------

@pytest.fixture(scope="module")
def engine(cfg, params):
    return support.engine(cfg, params)


def _served(engine, prompts, sp=SP, **kw):
    with jax.default_matmul_precision("highest"):
        return engine.generate(prompts, sp, **kw)


def test_engine_serves_the_references_tokens(cfg, params, engine):
    """Eight prompts over four slots: slots are reused and the later
    prompts RIDE the residents' decode steps. Every served token is the
    reference's argmax (or within float32 noise of it)."""
    assert engine.kv.k_pages.shape[0] == 6
    prompts = [support.tokens(n, seed=s) for s, n in enumerate(
        (36, 20, 36, 20, 3, 1, 36, 2))]
    for p, r in zip(prompts, _served(engine, prompts)):
        assert len(r.generated_tokens) == 10
        assert support.gaps(_ref, params, p, r.generated_tokens).max() < TOL


def test_the_loop_group_counts_every_pass(engine):
    _served(engine, [support.fresh_tokens(12)])
    loop = engine.stats()["loop"]
    assert (loop["passes"], loop["pool_planes"]) == (2, 6)
    assert loop["decode_tokens"] > 0
    assert loop["decode_token_passes"] == 2 * loop["decode_tokens"]
    plain = support.engine("gpt-test")
    assert "loop" not in plain.stats()


def test_a_repeated_prompt_hits_the_prefix_cache(params, engine):
    """A page of a looped model is a function of its token prefix in every
    plane: the second request reuses the first's pages and the suffix
    program attends over them, pass by pass."""
    prompt = support.fresh_tokens(37)
    before = engine.stats()
    a, = _served(engine, [prompt])
    b, = _served(engine, [prompt])
    after = engine.stats()
    assert after["prefix_cached_tokens"] - before["prefix_cached_tokens"] \
        == 32
    assert after["compiled_programs"]["prefill_extend_buckets"] >= 1
    for r in (a, b):
        assert support.gaps(_ref, params, prompt,
                            r.generated_tokens).max() < TOL


def test_a_prompt_rides_a_busy_engine_to_the_same_tokens(cfg, params):
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
                        req.generated_tokens).max() < TOL


def test_chunked_prefill_through_the_engine(cfg, params):
    eng = support.engine(cfg, params, chunked_prefill_tokens=16)
    prompt = support.tokens(53, seed=31)
    req, = _served(eng, [prompt], SamplingParams(temperature=0.0,
                                                 max_tokens=6))
    assert eng.stats()["compiled_programs"]["prefill_chunk_buckets"] >= 1
    assert support.gaps(_ref, params, prompt,
                        req.generated_tokens).max() < TOL


# 10 usable pages of 8 tokens; two requests of 16 + 40 tokens need 7 pages
# each at the end: together 14 > 10, so on-demand admission MUST preempt
# (tests/test_admission.py's sizes)
_PRESSED = [support.tokens(16, seed=40 + i) for i in range(2)]


@pytest.mark.parametrize("mode", ["recompute", "swap"])
def test_a_preempted_request_comes_back_to_the_references_tokens(
        cfg, params, mode):
    """Recompute: the preempted request's planes are prefilled again, every
    pass. Swap: the payload's planes are the pool's (``kv_layers`` of them)
    and come back as they left."""
    eng = support.engine(cfg, params, admission="ondemand", preemption=mode,
                         kv_num_blocks=11, max_seq_len=128)
    reqs = _served(eng, _PRESSED, SamplingParams(temperature=0.0,
                                                 max_tokens=40))
    assert eng.total_preemptions > 0
    assert (eng.total_swap_ins > 0) == (mode == "swap")
    for p, r in zip(_PRESSED, reqs):
        assert len(r.generated_tokens) == 40
        assert support.gaps(_ref, params, p, r.generated_tokens).max() < TOL


def test_ngram_drafts_are_verified_over_every_pass(cfg, params):
    """A repetitive prompt drafts by n-gram; the verification window goes
    through ``extend_step_forward`` and so through every pass's planes."""
    eng = support.engine(cfg, params, speculative="ngram")
    prompt = (support.tokens(6, seed=50) * 6)[:33]
    req, = _served(eng, [prompt], SamplingParams(temperature=0.0,
                                                 max_tokens=12))
    assert eng.stats()["spec_dispatches"] > 0
    assert support.gaps(_ref, params, prompt,
                        req.generated_tokens).max() < TOL


def test_tensor_parallel_needs_no_new_rule(cfg, params):
    """The sandwich norms' scales fall under the norms' rule and the gate
    under the fallback (both replicated); two devices serve the
    reference's tokens."""
    from distributed_llm_training_and_inference_system_tpu.parallel.sharding import (
        spec_for_path)
    from jax.sharding import PartitionSpec as P
    for path in ("blocks.attn_out_norm.scale", "blocks.mlp_out_norm.scale",
                 "exit_gate.kernel", "exit_gate.bias"):
        assert spec_for_path(path) == P(None)
    eng = support.engine(cfg, params, tensor_parallel=2)
    prompt = support.tokens(21, seed=60)
    req, = _served(eng, [prompt], SamplingParams(temperature=0.0,
                                                 max_tokens=6))
    assert support.gaps(_ref, params, prompt,
                        req.generated_tokens).max() < TOL


# -- what the model is refused, by what it IS ---------------------------------

@pytest.mark.parametrize("feature", [
    "fleet serving", "fleet prefix fetch", "kv_quantization",
    "measure_device_times"])
def test_refused_is_asked_feature_by_feature(cfg, feature):
    what, why = kv_cache.refused(cfg, feature)
    assert what == "walks its stack 2 times" and why
    with pytest.raises(ValueError, match="is refused"):
        kv_cache.refuse(cfg, feature)


@pytest.mark.parametrize("feature", [
    "chunked_prefill_tokens", "riding", "prefix_caching", "speculative",
    "preemption: swap", "page payload", "tensor_parallel"])
def test_what_the_planes_allow_stays_allowed(cfg, feature):
    assert kv_cache.refused(cfg, feature) is None


def test_a_quantised_pool_is_refused_by_name(cfg, params):
    with pytest.raises(ValueError, match="kv_quantization int8 is refused"):
        support.engine(cfg, params, kv_quantization="int8")


def test_llmctl_train_refuses_a_looped_stack(cfg):
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        RunConfig)
    from distributed_llm_training_and_inference_system_tpu.runtime.engine import (
        TrainingEngine)
    run = RunConfig(model=cfg)
    with pytest.raises(ValueError) as e:
        TrainingEngine(run)
    assert "ouro-test" in str(e.value) and "llmctl train is refused" in str(
        e.value) and "exit distribution" in str(e.value)


@pytest.mark.parametrize("make", ["make_pipeline_loss_fn",
                                  "make_pipeline_grad_fn"])
def test_pipeline_stages_refuse_a_looped_stack(cfg, make):
    from distributed_llm_training_and_inference_system_tpu.parallel import (
        pipeline)
    par = ParallelConfig(pipeline_parallel=3, num_microbatches=3)
    with pytest.raises(ValueError) as e:
        getattr(pipeline, make)(cfg, par)
    assert "ouro-test" in str(e.value) and "pipeline stages are refused" \
        in str(e.value)


def test_the_planner_reads_the_layers_once_a_pass():
    from distributed_llm_training_and_inference_system_tpu.config import (
        get_hardware_preset)
    from distributed_llm_training_and_inference_system_tpu.parallel.planner import (
        ServePlanner)
    big = get_model_config("ouro-2.6b")
    once = dataclasses.replace(big, num_passes=1)
    hw = get_hardware_preset("v5e-1")
    looped, plain = ServePlanner(big, hw), ServePlanner(once, hw)
    assert plain.pass_multiple() == 1.0
    # 4 x the layers' 2,466.6 M + 201.3 M of embedding and head, over 2,668 M
    assert looped.pass_multiple() == pytest.approx(
        (4 * 48 * 51_388_416 + 201_326_592 + 4097) / 2_667_974_657)
    # a page is 192 planes' rows: 64 tokens x 1,572,864 B
    assert looped.page_bytes(64) == 64 * 1_572_864 == 4 * plain.page_bytes(64)


# -- the checkpoint's key map ---------------------------------------------------

def test_the_checkpoint_key_map_reads_ouros_names(cfg, params):
    """A fabricated state dict of ``ouro-test``'s shapes under the HF names
    (``input_layernorm_2``, ``post_attention_layernorm_2``,
    ``model.early_exit_gate.{weight,bias}``; a norm's weight is 1 + scale)
    comes back as the tree it was written from. No weights are fetched."""
    from test_io import params_to_hf_dict
    from distributed_llm_training_and_inference_system_tpu.io.hf_import import (
        hf_llama_to_params)
    hf = params_to_hf_dict(params, cfg)
    assert hf["model.layers.2.input_layernorm_2.weight"].shape == (128,)
    assert hf["model.early_exit_gate.weight"].shape == (1, 128)
    assert hf["model.early_exit_gate.bias"].shape == (1,)
    back = hf_llama_to_params(hf, cfg)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    flat_back, tree_back = jax.tree_util.tree_flatten_with_path(back)
    assert tree == tree_back
    for (path, a), (_, b) in zip(flat, flat_back):
        assert np.abs(np.asarray(a) - b).max() < 1e-6, path
    del hf["model.early_exit_gate.bias"]
    with pytest.raises(KeyError, match="early_exit_gate.bias"):
        hf_llama_to_params(hf, cfg)
