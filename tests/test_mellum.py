"""Mellum2's architecture in small (``mellum-test``): two periods of three
WINDOW layers (16 keys, plain rope) and a full one (YaRN with an attention
factor), GQA with per-head q/k norms, renormalised top-2 of 8 experts; the
window layers' K/V in a RING of pages a slot beside the full layers' growing
chain. Against the plain reference (benchmark/reference/windowed_decoder.py)
on seeded NON-trivial weights (every norm's scale), on the CPU.

Covers (ISSUE 63): the schema's reading of the catalog row; ``gpt.forward``
(logits; over the uniform dense cache, every layer full-length and masked);
cold prefill, then paged decode across several ring wraps, against the
reference's full forward, LOGITS; the same through chunks, a suffix window
and a riding piece; the engine (cold, chunked, riding, preemption and
recompute, its ``window`` group); the allocator's properties; every refusal
by name; training under the XLA mask against the reference's loss and
gradient; every wrong variant of the reference.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serving_support as support
from benchmark.reference import windowed_decoder
from distributed_llm_training_and_inference_system_tpu.config import get_model_config
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ConfigError,
    ModelConfig,
    ParallelConfig,
    RunConfig,
)
from distributed_llm_training_and_inference_system_tpu.models import gpt
from distributed_llm_training_and_inference_system_tpu.models.layers import (
    attend_fresh,
)
from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
    SplitPages,
)
from distributed_llm_training_and_inference_system_tpu.serve import (
    Request,
    SamplingParams,
    kv_cache,
)
from distributed_llm_training_and_inference_system_tpu.serve.decode import (
    PIECE_META,
    Piece,
    decode_step_forward,
    extend_step_forward,
)
from distributed_llm_training_and_inference_system_tpu.serve.kv_cache import (
    PagedKVCache,
    ring_pages,
)

# Float32 on the CPU with exact float32 matmuls: the program and the
# reference differ in the ORDER of additions alone (a fused reduction against
# ``jnp.mean``, the experts' grouped matmul against every expert masked, an
# online softmax over pages against one over the row). Over 8 layers of width
# 128 with logits of size ~0.25 that is measured 3e-7 to 6e-7. 1e-4 is far
# above it, far under what bfloat16 anywhere moves the logits by (the stream
# rounded once: 1e-2), and under the least of the wrong references (0.04,
# asserted below).
TOL = 1e-4
PS, SLOTS, WINDOW = support.PS, support.SLOTS, 16
SP = SamplingParams(temperature=0.0, max_tokens=40)


@pytest.fixture(scope="module")
def cfg():
    return get_model_config("mellum-test")


def published(cfg) -> dict:
    """``cfg`` as a published ``config.json`` holds it (what the reference
    reads)."""
    r = cfg.rope
    return {
        "name": cfg.name, "model_type": "mellum", "head_dim": cfg.head_dim,
        "hidden_size": cfg.hidden_size, "hidden_act": "silu",
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "num_hidden_layers": cfg.num_layers,
        "num_experts": cfg.moe.num_experts,
        "num_experts_per_tok": cfg.moe.experts_per_token,
        "norm_topk_prob": True, "moe_intermediate_size": cfg.ffn_size,
        "rms_norm_eps": cfg.norm_eps, "sliding_window": cfg.sliding_window,
        "use_sliding_window": True, "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.max_position_embeddings,
        "tie_word_embeddings": False, "attention_bias": False,
        "layer_types": [t + "_attention" for t in cfg.layer_types],
        "mlp_layer_types": ["sparse"] * cfg.num_layers,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": r.base,
                "factor": r.scaling_factor,
                "original_max_position_embeddings": r.original_max_position,
                "beta_fast": r.beta_fast, "beta_slow": r.beta_slow,
                "attention_factor": r.attention_factor},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": cfg.window_rope.base}}}


def seeded(cfg, seed=0):
    """``gpt.init`` with what it leaves trivial made visible: every norm's
    scale (two a layer, the q/k head norms', the final one; a unit scale
    hides a missing norm) in U(-0.3, 0.3)."""
    params = support.params_of(cfg, seed)
    key = jax.random.PRNGKey(seed + 100)
    count = iter(range(1000))

    def visible(path, leaf):
        if path[-1].key != "scale":
            return leaf
        return jax.random.uniform(jax.random.fold_in(key, next(count)),
                                  leaf.shape, leaf.dtype, -0.3, 0.3)
    return jax.tree_util.tree_map_with_path(visible, params)


@pytest.fixture(scope="module")
def params(cfg):
    return seeded(cfg)


def _ref(params, tokens, wrong=None):
    with jax.default_matmul_precision("highest"):
        return np.asarray(windowed_decoder.logits(
            params, list(tokens), published(get_model_config("mellum-test")),
            wrong=wrong))


# -- the schema ---------------------------------------------------------------

def test_the_catalog_row_builds_the_windowed_stack():
    row = support.catalog_row("Mellum2-12B-A2.5B-Instruct")
    cfg = ModelConfig.from_published(dict(row, name="mellum"))
    assert cfg.layer_types == ("sliding", "sliding", "sliding", "full") * 7
    assert cfg.window_period == ("sliding", "sliding", "sliding", "full")
    assert (cfg.sliding_window, cfg.window_layers, cfg.qk_norm) == (
        1024, 21, "head")
    assert (cfg.rope.scaling, cfg.rope.scaling_factor, cfg.rope.base,
            cfg.rope.original_max_position) == ("yarn", 16.0, 500000.0, 8192)
    assert cfg.rope.attention_factor == 1.2772588722239782
    assert (cfg.window_rope.scaling, cfg.window_rope.base,
            cfg.window_rope.attention_factor) == ("none", 500000.0, 1.0)
    assert (cfg.moe.num_experts, cfg.moe.experts_per_token,
            cfg.moe.norm_topk_prob, cfg.ffn_size) == (64, 8, True, 896)
    # the preset is the row, and the row survives its own dict
    preset = get_model_config("mellum2-12b-a2.5b")
    assert dataclasses.replace(cfg, name=preset.name) == preset
    assert ModelConfig.from_dict(preset.to_dict()) == preset
    # K/V by kind: 2,048 B a token a layer; 7 full layers, 21 window layers
    assert preset.kv_bytes_per_token(2, "full") == 7 * 2048
    assert preset.kv_bytes_per_token(2, "sliding") == 21 * 2048
    assert preset.kv_bytes_per_token(2) == 28 * 2048
    # ISSUE 63's arithmetic: 417.75 M a layer, 12.15 B in all
    layer = 2304 * (4096 + 512 + 512) + 4096 * 2304 + 2304 * 64 \
        + 64 * 3 * 2304 * 896 + 2 * 2304 + 2 * 128
    assert layer == 417_747_712
    assert preset.param_count == 28 * layer + 2 * 98304 * 2304 + 2304


def test_param_count_is_the_tree(cfg, params):
    assert cfg.param_count == sum(
        leaf.size for leaf in jax.tree_util.tree_leaves(params))


@pytest.mark.parametrize("change,match", [
    ({"layer_pattern": "*E" * 4, "num_layers": 8}, "a layer table"),
    ({"num_passes": 2}, "a looped stack"),
    ({"sliding_window": 0}, "sees at least itself"),
    ({"layer_types": ("sliding", "full")}, "must name each of the 8"),
    ({"layer_types": ("local",) * 8}, "unknown"),
])
def test_a_window_is_carried_by_the_uniform_stack_alone(cfg, change, match):
    with pytest.raises(ConfigError, match=match):
        dataclasses.replace(cfg, **change).validate()


def test_a_window_on_a_diffusion_or_latent_model_is_refused():
    for name in ("sdar-test", "xing-test"):
        try:
            other = get_model_config(name)
        except KeyError:
            continue
        windowed = dataclasses.replace(
            other, sliding_window=16,
            layer_types=("sliding",) * other.num_layers)
        with pytest.raises(ConfigError, match="window layers beside"):
            windowed.validate()


def test_a_stack_of_full_layers_is_the_stack_as_it_was(cfg):
    """``use_sliding_window: false`` turns every layer full; a model without
    window layers hands its scan no per-layer operand."""
    from distributed_llm_training_and_inference_system_tpu.models.layers import (
        layer_kinds)
    off = ModelConfig.from_published(dict(published(cfg),
                                          use_sliding_window=False))
    assert not off.has_window and layer_kinds(off) is None
    assert layer_kinds(get_model_config("olmoe-test")) is None
    kinds = layer_kinds(cfg)
    assert kinds.window.tolist() == [16, 16, 16, 0] * 2
    assert kinds.rope_scale.tolist() == pytest.approx(
        [1, 1, 1, cfg.rope.attention_factor] * 2)


# -- the forward against the reference -----------------------------------------

@pytest.fixture(scope="module")
def sequence():
    return support.tokens(90, seed=7)


@pytest.fixture(scope="module")
def forwarded(cfg, params, sequence):
    with jax.default_matmul_precision("highest"):
        return np.asarray(support.forward(params, [sequence], cfg)[0])


def test_forward_logits_match_the_reference(params, sequence, forwarded):
    ref = _ref(params, sequence)
    assert np.abs(ref).max() > 0.5
    assert np.abs(forwarded - ref).max() < TOL


@pytest.mark.parametrize("wrong", windowed_decoder.WRONG)
def test_the_comparison_fails_each_wrong_reference(params, sequence,
                                                   forwarded, wrong):
    """Every wrong variant of the reference differs from the program by
    hundreds of tolerances: all full, a window of 15 and of 17, YaRN on
    every layer, no attention factor, raw top-2 weights, the full layer
    first, float8 operands."""
    assert np.abs(forwarded - _ref(params, sequence, wrong)).max() > 0.04


def _over_dense_cache(params, tokens, *, cfg):
    cache = gpt.init_kv_cache(cfg, 1, 96, dtype=jnp.float32)
    return gpt.forward(params, tokens, cfg, kv_cache=cache,
                       cache_offset=jnp.zeros((1,), jnp.int32))


def test_the_uniform_cache_gives_the_rings_logits(cfg, params, sequence,
                                                  forwarded):
    """Every layer full-length and masked (the dense cache a cold prefill
    runs over: 8 planes of 96 rows) gives the forward's logits, which the
    ring's are held to below."""
    with jax.default_matmul_precision("highest"):
        logits, cache = support.program(_over_dense_cache, cfg)(
            params, jnp.asarray([sequence]))
    assert cache[0].shape[:3] == (8, 1, 96)
    assert np.abs(np.asarray(logits[0]) - forwarded).max() < TOL


# -- the pages: a chain and a ring ----------------------------------------------

def _pools(cfg, slots=SLOTS, rows=16, pages=40):
    return PagedKVCache(cfg, num_slots=slots, max_seq_len=support.SPAN,
                        page_size=PS, num_pages=pages, dtype=jnp.float32,
                        window_rows=rows)


def _window_program(params, rows, start, kp, vp, table, ok, *, cfg):
    step = extend_step_forward(params, rows, start, kp, vp, table, cfg,
                               write_ok=ok)
    return step.logits, step.k_pages, step.v_pages


def _decode_program(params, toks, pos, kp, vp, tables, active, ride, *, cfg):
    step = decode_step_forward(params, toks, pos, kp, vp, tables, cfg,
                               active=active, ride=ride)
    return step.logits, step.k_pages, step.v_pages


def _window(cfg, params, kv, kp, vp, slot, tokens, start):
    """One slot's window of ``tokens`` from ``start`` through the pages."""
    n = len(tokens)
    with jax.default_matmul_precision("highest"):
        return support.program(_window_program, cfg)(
            params, jnp.asarray([tokens]), jnp.asarray([start], jnp.int32),
            kp, vp, jnp.asarray(kv.block_tables[slot][None]),
            jnp.ones((1, n), bool))


def _decode(cfg, params, kv, kp, vp, slot, token, position, ride=None):
    toks = jnp.zeros((SLOTS,), jnp.int32).at[slot].set(token)
    pos = jnp.zeros((SLOTS,), jnp.int32).at[slot].set(position)
    with jax.default_matmul_precision("highest"):
        return support.program(_decode_program, cfg)(
            params, toks, pos, kp, vp, jnp.asarray(kv.block_tables),
            jnp.arange(SLOTS) == slot, ride)


def test_the_pools_are_a_chain_and_a_ring(cfg):
    kv = _pools(cfg)
    assert isinstance(kv.k_pages, SplitPages)
    assert kv.ring_entries == ring_pages(16, PS, 16) == 4
    # 2 full layers over 40 pages; 6 window layers over 4 slots x 4 + scratch
    assert kv.k_pages.full.shape == (2, 40, 2, PS, 16)
    assert kv.k_pages.window.shape == (6, SLOTS * 4 + 1, 2, PS, 16)
    assert kv.k_pages.is_window == (True, True, True, False) * 2
    assert kv.block_tables.shape == (SLOTS, support.SPAN // PS + 4)
    assert kv.bytes_per_token == 2 * 2 * 2 * 16 * 4      # the full layers'
    assert kv.pool_bytes("window") == 2 * 6 * 17 * 2 * PS * 16 * 4
    assert kv.hbm_bytes() == kv.pool_bytes("window") + kv.pool_bytes("full")
    chain, rings = kv.k_pages.tables_of(jnp.asarray(kv.block_tables))
    assert chain.shape == rings.shape == (SLOTS, support.SPAN // PS)
    # a model without window layers keeps its one pool and its table
    plain = _pools(get_model_config("olmoe-test"))
    assert not isinstance(plain.k_pages, SplitPages)
    assert plain.block_tables.shape == (SLOTS, support.SPAN // PS)
    assert plain.pool_bytes("window") == 0 == plain.ring_entries


def test_prefill_then_decode_across_ring_wraps(cfg, params, sequence,
                                               forwarded):
    """A prompt in chunks of 16 rows (two pages: the longest window a ring
    of 4 pages takes), then 42 decode steps: the ring of 32 rows wraps
    twice under the prompt and once more under the decode steps, and every
    row's logits are the reference's."""
    kv = _pools(cfg)
    slot = 2
    kv.allocate(slot, len(sequence))
    kp, vp = kv.k_pages, kv.v_pages
    for start in (0, 16, 32):
        logits, kp, vp = _window(cfg, params, kv, kp, vp, slot,
                                 sequence[start:start + 16], start)
        assert np.abs(np.asarray(logits[0])
                      - forwarded[start:start + 16]).max() < TOL
    for t in range(48, len(sequence)):
        logits, kp, vp = _decode(cfg, params, kv, kp, vp, slot, sequence[t],
                                 t)
        assert np.abs(np.asarray(logits[slot]) - forwarded[t]).max() < TOL
    # the ring holds the last 32 positions' rows and nothing else: plane 0
    # of the window pool, entry (t // 8) % 4
    ring = kv.block_tables[slot, -4:]
    held = np.asarray(kp.window[0, ring])         # [4, Nkv, PS, D]
    assert np.abs(held).min(axis=(1, 3)).all()
    # ... and the full pool every position's
    chain = kv.block_tables[slot, :12]
    assert np.abs(np.asarray(kp.full[0, chain])[:11]).min(axis=(1, 3)).all()


def test_a_suffix_window_behind_decoded_rows(cfg, params, sequence,
                                             forwarded):
    """A window that starts on a page behind rows other programs wrote (a
    preempted request's context computed again behind what it kept would be
    one): 24 rows by chunks, then a window of 16 from 24."""
    kv = _pools(cfg)
    kv.allocate(0, 64)
    kp, vp = kv.k_pages, kv.v_pages
    _, kp, vp = _window(cfg, params, kv, kp, vp, 0, sequence[:16], 0)
    _, kp, vp = _window(cfg, params, kv, kp, vp, 0, sequence[16:24], 16)
    logits, kp, vp = _window(cfg, params, kv, kp, vp, 0, sequence[24:40], 24)
    assert np.abs(np.asarray(logits[0]) - forwarded[24:40]).max() < TOL


@pytest.mark.parametrize("n", [16 + 5, 2 * 16 + 16], ids=["21", "48"])
def test_a_riding_piece_matches_the_reference(cfg, params, sequence,
                                              forwarded, n):
    """A prompt of ``n`` tokens rides another slot's decode steps in pieces
    of 16 rows; its last piece's last live row and the decoding slot's row
    both read the reference's logits, the ring wrapping under the pieces."""
    kv = _pools(cfg)
    rider, resident = 1, 3
    kv.allocate(rider, n)
    kv.allocate(resident, 64)
    other = support.tokens(40, seed=9)
    with jax.default_matmul_precision("highest"):
        other_logits = np.asarray(support.forward(params, [other], cfg)[0])
    kp, vp = kv.k_pages, kv.v_pages
    for start in (0, 16):
        _, kp, vp = _window(cfg, params, kv, kp, vp, resident,
                            other[start:start + 16], start)
    t = 32
    for start in range(0, n, 16):
        live = min(16, n - start)
        tokens = np.zeros(16, np.int32)
        tokens[:live] = sequence[start:start + live]
        ride = Piece(jnp.int32(rider), jnp.int32(start), jnp.int32(live),
                     jnp.int32(0), jnp.asarray(tokens))
        logits, kp, vp = _decode(cfg, params, kv, kp, vp, resident,
                                 other[t], t, ride)
        assert np.abs(np.asarray(logits[resident]) - other_logits[t]
                      ).max() < TOL
        assert np.abs(np.asarray(logits[SLOTS]) - forwarded[start + live - 1]
                      ).max() < TOL
        t += 1
    assert PIECE_META == 4


# -- the allocator ----------------------------------------------------------------

def test_a_slot_holds_its_ring_for_life_and_gives_everything_back(cfg):
    """Random admissions, growth and releases: a resident slot holds
    ``ring_entries`` window pages from admission to release, the same ones;
    no two slots share one; both pools' pages are all free at the end."""
    kv = _pools(cfg, pages=60)
    rng = np.random.default_rng(63)
    held: dict = {}
    for _ in range(300):
        slot = int(rng.integers(SLOTS))
        if slot not in held:
            n = int(rng.integers(1, 80))
            if kv.can_allocate(n):
                kv.allocate(slot, n)
                held[slot] = (n, kv.block_tables[slot, -4:].copy())
        elif rng.random() < 0.5:
            n = held[slot][0] + int(rng.integers(1, 40))
            if n <= support.SPAN and kv.extend_slot(slot, n):
                held[slot] = (n, held[slot][1])
        else:
            kv.release(slot)
            del held[slot]
            assert not kv.block_tables[slot].any()
        rings = [tuple(kv.block_tables[s, -4:]) for s in held]
        for s, (_, ring) in held.items():
            assert (kv.block_tables[s, -4:] == ring).all() and ring.all()
        flat = [p for ring in rings for p in ring]
        assert len(set(flat)) == len(flat)
        assert kv.free_ring_pages == (SLOTS - len(held)) * 4
    for slot in list(held):
        kv.release(slot)
    assert kv.free_ring_pages == SLOTS * 4 and kv.free_pages == 59
    assert not kv.block_tables.any()


@pytest.mark.parametrize("window,page,rows", [
    (16, 8, 16), (16, 8, 8), (16, 8, 1), (1024, 128, 128), (1024, 128, 256),
    (1024, 128, 1), (1024, 64, 128), (100, 16, 48)])
def test_no_write_lands_on_a_row_a_query_of_the_call_sees(window, page, rows):
    """``ring_pages`` is the least count for which the pages a call writes
    and the pages its first query still sees are distinct ring entries, at
    every start a program can have (a window of one row anywhere, a longer
    one on a page); one page fewer fails at some start."""
    def collides(ring):
        starts = (range(0, 40 * page, page) if rows > 1
                  else range(0, 6 * ring * page))
        for start in starts:
            first = max(start - (window - 1), 0) // page
            last = (start + rows - 1) // page
            entries = [p % ring for p in range(first, last + 1)]
            if len(set(entries)) != len(entries):
                return True
        return False
    ring = ring_pages(window, page, rows)
    assert not collides(ring) and collides(ring - 1)
    if (window, page) == (1024, 128):
        assert ring == {1: 9, 128: 9, 256: 10}[rows]


def test_a_cold_prompts_ring_entries_are_its_last_pages(cfg):
    kv = _pools(cfg)
    kv.allocate(1, 70)                  # 9 pages
    entries = kv.prompt_entries(1, 70, 12)
    assert entries.shape == (2, 12)
    assert (entries[0, :9] == kv.block_tables[1, :9]).all()
    assert not entries[0, 9:].any()
    ring = kv.block_tables[1, -4:]
    # pages 5..8 are kept, at entries 5 % 4 .. 8 % 4; pages 0..4 go to the
    # scratch page (overwritten before any query could read them)
    assert not entries[1, :5].any() and not entries[1, 9:].any()
    assert entries[1, 5:9].tolist() == [ring[1], ring[2], ring[3], ring[0]]
    plain = _pools(get_model_config("olmoe-test"))
    plain.allocate(1, 70)
    assert plain.prompt_entries(1, 70, 12).shape == (12,)


# -- the engine -------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine(cfg, params):
    return support.engine(cfg, params)


def _served(engine, prompts, sp=SP, **kw):
    with jax.default_matmul_precision("highest"):
        return engine.generate(prompts, sp, **kw)


def _gaps(params, prompt, served):
    return support.gaps(_ref, params, prompt, served)


def test_engine_serves_the_references_tokens(cfg, params, engine):
    """Eight prompts over four slots: the first are prefilled in chunks of
    16 over the ring (the short ones cold), the later ones RIDE the
    residents' decode steps, slots are reused, and 40 tokens a reply take
    every ring round more than once. Every served token is the reference's
    argmax (or within float32 noise of it)."""
    assert (engine.kv.ring_entries, engine._chunk_tokens,
            engine._ride_rows) == (4, 16, 16)
    prompts = [support.tokens(n, seed=s) for s, n in enumerate(
        (36, 20, 70, 9, 3, 1, 36, 100))]
    for p, r in zip(prompts, _served(engine, prompts)):
        assert len(r.generated_tokens) == 40
        assert _gaps(params, p, r.generated_tokens).max() < TOL
    stats = engine.stats()
    assert stats["prefill_ride_tokens"] > 0
    programs = stats["compiled_programs"]
    assert programs["prefill_chunk_buckets"] >= 1
    support.idle(engine)
    assert engine.kv.free_ring_pages == SLOTS * 4


def test_a_short_prompt_on_an_idle_engine_takes_the_cold_program(
        cfg, params, engine):
    """16 tokens or fewer: the cold program over the uniform dense cache,
    its rows written to the chain and to the ring by ``prompt_entries``."""
    before = engine.stats()["compiled_programs"]["prefill_dense_buckets"]
    prompt = support.fresh_tokens(13)
    req, = _served(engine, [prompt])
    assert engine.stats()["compiled_programs"]["prefill_dense_buckets"] \
        >= max(before, 1)
    assert _gaps(params, prompt, req.generated_tokens).max() < TOL


def test_the_window_group_counts_rows_by_kind(engine, params):
    _served(engine, [support.fresh_tokens(30)])
    w = engine.stats()["window"]
    assert (w["window"], w["window_layers"], w["ring_pages"]) == (16, 6, 4)
    assert w["window_pool_bytes"] == engine.kv.pool_bytes("window")
    assert w["full_pool_bytes"] == engine.kv.pool_bytes("full")
    # min(length, 16) x 6 against length x 6; the full layers' length x 2
    assert 0 < w["window_rows"] < w["window_rows_unwindowed"]
    assert w["full_rows"] * 3 == w["window_rows_unwindowed"]
    assert w["ring_wraps"] > 0
    assert w["refused"]["prefix_caching"] > 0       # OFF and counted
    assert "window" not in support.engine("gpt-test").stats()
    # (what a traced benchmark run reads the scopes from: lowered from the
    # live arguments' shapes, two tables a slot and two entry rows a prompt)
    texts = engine.program_texts(chunks=True)
    assert {name.split(" ")[0] for name in texts} >= {
        "_decode_impl_n", "prefill", "suffix"}


def test_a_repeated_prompt_is_prefilled_again(params, engine):
    """Prefix reuse is OFF for a ring: the second request reuses nothing
    and serves the same tokens."""
    prompt = support.fresh_tokens(37)
    before = engine.stats()
    a, = _served(engine, [prompt])
    b, = _served(engine, [prompt])
    after = engine.stats()
    assert after["prefix_cached_tokens"] == before["prefix_cached_tokens"]
    assert after["kv"]["prefix_cached_pages"] == 0
    assert a.generated_tokens == b.generated_tokens
    assert _gaps(params, prompt, a.generated_tokens).max() < TOL


def test_a_prompt_rides_a_busy_engine_to_the_same_tokens(cfg, params):
    eng = support.engine(cfg, params)
    with jax.default_matmul_precision("highest"):
        for i, n in enumerate((9, 13)):
            assert eng.scheduler.add_request(Request(
                f"resident-{i}", support.tokens(n, seed=20 + i), SP))
        while eng.active.sum() < 2:
            eng.step()
        prompt = support.tokens(45, seed=30)
        req = Request("rider", prompt, SamplingParams(temperature=0.0,
                                                      max_tokens=24))
        assert eng.scheduler.add_request(req)
        eng.run_until_idle()
    assert eng.stats()["prefill_ride_tokens"] == 45
    assert _gaps(params, prompt, req.generated_tokens).max() < TOL


# 10 usable full-pool pages of 8 tokens; two requests of 16 + 40 tokens need
# 7 pages each at the end: together 14 > 10, so on-demand admission MUST
# preempt (tests/test_admission.py's sizes); the rings are never short
_PRESSED = [support.tokens(16, seed=40 + i) for i in range(2)]


def test_a_preempted_request_is_recomputed_through_the_ring(cfg, params):
    """The full pool cannot extend a slot: the victim gives back its chain
    AND its ring, and comes back by recompute (its prompt and what it had
    generated, in chunks over a ring taken anew) to the reference's
    tokens."""
    eng = support.engine(cfg, params, admission="ondemand",
                         kv_num_blocks=11, max_seq_len=128)
    reqs = _served(eng, _PRESSED, SP)
    assert eng.total_preemptions > 0 and eng.total_swap_ins == 0
    for p, r in zip(_PRESSED, reqs):
        assert len(r.generated_tokens) == 40
        assert _gaps(params, p, r.generated_tokens).max() < TOL
    support.idle(eng)
    assert eng.kv.free_ring_pages == SLOTS * 4


# -- refused by name, never silently wrong -------------------------------------------

@pytest.mark.parametrize("feature", sorted(kv_cache.REFUSED["windowed"]))
def test_refused_is_asked_feature_by_feature(cfg, feature):
    what, why = kv_cache.refused(cfg, feature)
    assert what == "keeps its window layers' K/V in a ring of pages" and why
    with pytest.raises(ValueError, match="is refused"):
        kv_cache.refuse(cfg, feature)
    assert kv_cache.refused(get_model_config("olmoe-test"), feature) is None


@pytest.mark.parametrize("feature", ["chunked_prefill_tokens", "riding"])
def test_what_the_ring_allows_stays_allowed(cfg, feature):
    assert kv_cache.refused(cfg, feature) is None


@pytest.mark.parametrize("over,match", [
    ({"speculative": "ngram"}, "speculative is refused"),
    ({"preemption": "swap"}, "preemption: swap is refused"),
    ({"kv_quantization": "int8"}, "kv_quantization int8 is refused"),
    ({"tensor_parallel": 2}, "refused"),
])
def test_an_engine_that_asks_for_it_is_refused_by_name(cfg, params, over,
                                                       match):
    with pytest.raises(ValueError, match=match):
        support.engine(cfg, params, **over)


def test_the_page_payload_and_the_probes_are_refused(cfg, params, engine):
    with pytest.raises(ValueError, match="measure_device_times is refused"):
        engine.measure_device_times()
    engine.kv.allocate(0, 20)
    try:
        with pytest.raises(ValueError, match="is refused"):
            engine.kv.extract_slot(0)
        with pytest.raises(ValueError, match="is refused"):
            engine.kv.extract_pages([1])
    finally:
        engine.kv.release(0)
    with pytest.raises(ValueError, match="fleet prefix fetch is refused"):
        engine.prefix_fetch_hook = lambda *a: None


@pytest.mark.parametrize("impl", ["flash", "ring", "ulysses"])
def test_the_routes_without_a_window_term_refuse(impl):
    positions = jnp.arange(8)[None]
    with pytest.raises(ValueError, match="has no window term"):
        attend_fresh(positions, None, impl, window=4)
    attend_fresh(positions, None, impl)         # as ever without one


def test_pipeline_stages_refuse_window_layers(cfg):
    from distributed_llm_training_and_inference_system_tpu.parallel.pipeline import (
        make_pipeline_loss_fn)
    with pytest.raises(ValueError, match="pipeline stages are refused"):
        make_pipeline_loss_fn(cfg, ParallelConfig(pipeline_parallel=2))


def test_a_window_longer_than_the_ring_allows_is_refused(cfg, params):
    kv = _pools(cfg, rows=16)
    kv.allocate(0, 64)
    with pytest.raises(ValueError, match="would overwrite rows"):
        extend_step_forward(params, jnp.zeros((1, 24), jnp.int32),
                            jnp.zeros((1,), jnp.int32), kv.k_pages,
                            kv.v_pages, jnp.asarray(kv.block_tables[:1]), cfg)


# -- training under the XLA mask -----------------------------------------------------

def test_loss_and_gradient_match_the_reference(cfg, params):
    """``llmctl train`` on the test preset trains WITH the window (the XLA
    mask; the dropless route, whose every expert the reference applies):
    the next-token loss and its gradient by the q kernels are the
    reference's."""
    tokens = support.tokens(40, seed=11)
    pub = published(cfg)

    def program_loss(params):
        logits = gpt.forward(params, jnp.asarray([tokens]), cfg)[0]
        logp = jax.nn.log_softmax(logits[:-1])
        return -jnp.mean(logp[jnp.arange(39), jnp.asarray(tokens[1:])])

    def reference_loss(params):
        logits = windowed_decoder.logits(params, tokens, pub)
        logp = jax.nn.log_softmax(logits[:-1])
        return -jnp.mean(logp[jnp.arange(39), jnp.asarray(tokens[1:])])
    with jax.default_matmul_precision("highest"):
        a, ga = jax.value_and_grad(program_loss)(params)
        b, gb = jax.value_and_grad(reference_loss)(params)
    assert abs(float(a) - float(b)) < 1e-5
    for name in ("q", "k", "o"):
        x, y = (np.asarray(g["blocks"][name]["kernel"]) for g in (ga, gb))
        assert np.abs(y).max() > 1e-4
        assert np.abs(x - y).max() < 1e-5 * max(np.abs(y).max(), 1.0)


def test_llmctl_train_takes_the_xla_mask_or_refuses_by_name(cfg):
    from distributed_llm_training_and_inference_system_tpu.runtime.engine import (
        TrainingEngine)
    run = RunConfig(model=cfg)
    assert TrainingEngine(run).attn_impl == "xla"
    run.training.attn_impl = "flash"
    with pytest.raises(ValueError, match="under the XLA mask alone"):
        TrainingEngine(run)
