"""Latent attention (MLA) over one paged latent pool, the four-stream
hyper-connection, YaRN and the leading dense layer, on the CPU at
``xing-test`` widths with seeded random weights, held to the plain float32
reference (``benchmark/reference/latent_decoder.py``) on LOGITS.

Tolerance: both sides compute in float32 with full-precision matmuls and
differ in the ORDER of their sums alone (absorbed against expanded
attention, online against plain softmax, a one-hot page merge): logits of
size ~0.6 agree to ~3e-7, and TOL = 2e-5 leaves that two orders of room.
Every departure the chip's check is asked to refuse moves the reference's
logits by more than 20 x TOL (``test_each_departure_moves_the_logits``).
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serving_support as support
from benchmark.reference import latent_decoder as ref
from distributed_llm_training_and_inference_system_tpu.config.presets import (
    XING_TEST_PUBLISHED,
    get_model_config,
)
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ConfigError,
    ModelConfig,
)
from distributed_llm_training_and_inference_system_tpu.models import gpt, layers
from distributed_llm_training_and_inference_system_tpu.ops import (
    mla_paged_attention as mla,
)
from distributed_llm_training_and_inference_system_tpu.serve import (
    decode,
    kv_cache,
)
from distributed_llm_training_and_inference_system_tpu.serve.engine import (
    InferenceEngine,
)
from distributed_llm_training_and_inference_system_tpu.serve.kv_cache import (
    PagedKVCache,
)
from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
    SamplingParams,
)

TOL = 2e-5
C = XING_TEST_PUBLISHED
PS = 8


@pytest.fixture(scope="module")
def cfg():
    return get_model_config("xing-test")


@pytest.fixture(scope="module")
def params(cfg):
    """Seeded weights with every norm's scale, the selection bias and the
    hyper-connections' biases made non-trivial (at ``gpt.init``'s zeros a
    missing norm weight or bias would not show)."""
    tree = support.params_of(cfg)
    key = jax.random.PRNGKey(5)

    def seeded(path, x):
        names = [k.key for k in path]
        if "scale" in names or names[-1] in ("b_pre", "b_post", "b_res",
                                             "bias"):
            spread = 0.02 if names[-1] == "bias" else 0.4
            return x + jax.random.uniform(
                jax.random.fold_in(key, hash(tuple(names)) % 9973), x.shape,
                x.dtype, -spread, spread)
        return x
    return jax.tree_util.tree_map_with_path(seeded, tree)


def _reference(params, tokens, positions=None, wrong=None):
    return np.asarray(ref.logits(params, tokens, C, positions=positions,
                                 wrong=wrong))


def _window_program(params, window, starts, pool, tables, ok, *, cfg):
    return decode.extend_step_forward(params, window, starts, pool, None,
                                      tables, cfg, write_ok=ok)


def _paged(cfg, params, tokens, windows):
    """Logits of ``tokens`` served through the latent pages in windows of
    the given lengths (1 = a decode step), one slot among three."""
    kv = PagedKVCache(cfg, num_slots=3, max_seq_len=128, page_size=PS,
                      num_pages=40, dtype=jnp.float32)
    kv.allocate(1, len(tokens))
    tables = jnp.asarray(kv.block_tables)
    pool, out, at = kv.k_pages, [], 0
    with jax.default_matmul_precision("highest"):
        for t in windows:
            window = np.zeros((3, t), np.int32)
            window[1] = tokens[at:at + t]
            ok = np.zeros((3, t), bool)
            ok[1] = True
            lg, pool, none, *_ = support.program(_window_program, cfg)(
                params, jnp.asarray(window),
                jnp.asarray([0, at, 0], jnp.int32), pool, tables,
                jnp.asarray(ok))
            assert none is None
            out.append(np.asarray(lg[1]))
            at += t
    return np.concatenate(out)


# -- the model against the reference ---------------------------------------------

def test_the_full_forward_is_the_reference(cfg, params):
    tokens = support.tokens(40)
    with jax.default_matmul_precision("highest"):
        lg = support.forward(params, [tokens], cfg)
    assert np.abs(np.asarray(lg[0]) - _reference(params, tokens)).max() < TOL


@pytest.mark.parametrize("n, blocks", [(40, (256, 1024)), (45, (16, 8))],
                         ids=["one-block", "blocks-with-a-remainder"])
def test_the_reference_padded_and_compiled_is_the_reference(
        params, monkeypatch, n, blocks):
    """The form the cell's check runs (one length for every request, a
    jitted program a kind of sub-layer, the padding choosing no expert) gives
    the plain form's logits and routing margins, also where the queries and
    an expert's rows take several blocks and the last is not full."""
    monkeypatch.setattr(ref, "QUERY_BLOCK", blocks[0])
    monkeypatch.setattr(ref, "EXPERT_ROWS", blocks[1])
    tokens = support.tokens(n, seed=3)
    plain, margin = ref.logits(params, tokens, C, with_margin=True)
    ref._compiled_sub_layers.cache_clear()
    got, got_margin = ref.logits(params, tokens, C, with_margin=True,
                                 round_to=64, compiled=True)
    ref._compiled_sub_layers.cache_clear()
    assert got.shape == plain.shape == (n, C["vocab_size"])
    assert np.abs(np.asarray(got) - np.asarray(plain)).max() < TOL
    assert np.abs(np.asarray(got_margin) - np.asarray(margin)).max() < 1e-6


@pytest.mark.parametrize("windows", [
    [24] + [1] * 8,            # a window, then decode steps
    [16, 16],                  # chunked prefill
    [13, 19],                  # a suffix after a prefix that ends mid-page
    [1] * 12,                  # decode from the first token
], ids=["prefill-then-decode", "chunked", "suffix-mid-page", "decode-only"])
def test_the_paged_programs_are_the_reference(cfg, params, windows):
    tokens = support.tokens(sum(windows), seed=1)
    got = _paged(cfg, params, tokens, windows)
    assert np.abs(got - _reference(params, tokens)).max() < TOL


def _cold_program(params, padded, live, *, cfg):
    return gpt.forward(params, padded, cfg, segment_ids=live,
                       return_latent=True, return_moe_stats=True)


def _decode_program(params, tokens, positions, pool, tables, *, cfg):
    return decode.decode_step_forward(params, tokens, positions, pool, None,
                                      tables, cfg)


def test_cold_prefill_rows_then_decode_through_the_pages(cfg, params):
    """``forward(return_latent=True)``'s rows written to pages, then decode
    steps over them: what the engine's cold prefill does."""
    tokens = support.tokens(30, seed=2)
    n = 20
    kv = PagedKVCache(cfg, num_slots=1, max_seq_len=64, page_size=PS,
                      num_pages=12, dtype=jnp.float32)
    kv.allocate(0, len(tokens))
    with jax.default_matmul_precision("highest"):
        padded = jnp.asarray([tokens[:n] + [0] * 4])
        live = (jnp.arange(24)[None] < n).astype(jnp.int32)
        lg, rows, _ = support.program(_cold_program, cfg)(params, padded,
                                                          live)
        assert rows.shape == (3, 1, 24, cfg.mla.latent_size)
        rows = jnp.pad(rows[:, 0], ((0, 0), (0, 0), (
            0, cfg.mla.page_width - cfg.mla.latent_size)))
        pool = kv.k_pages.at[:, kv.block_tables[0, :3]].set(
            rows.reshape(3, 3, 1, PS, -1))
        outs = [np.asarray(lg[0, :n])]
        for i in range(n, len(tokens)):
            step, pool, *_ = support.program(_decode_program, cfg)(
                params, jnp.asarray([tokens[i]]), jnp.asarray([i]), pool,
                jnp.asarray(kv.block_tables))
            outs.append(np.asarray(step))
    assert np.abs(np.concatenate(outs)
                  - _reference(params, tokens)).max() < TOL


def test_absorbed_attention_is_expanded_attention(cfg, params):
    """One layer's mixer, the same rows: ``attend_fresh`` (expanded) against
    a latent ``attend`` that scores the rows directly (absorbed)."""
    layer = jax.tree_util.tree_map(lambda a: a[1], params["blocks"]["attn"])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 11, cfg.hidden_size))
    pos = jnp.arange(11)[None].repeat(2, 0)
    inv = layers.model_rope_frequencies(cfg)
    r = cfg.mla.kv_lora_rank

    def attend(q_lat, rows, scale):
        s = jnp.einsum("bsnw,bkw->bnsk", q_lat, rows) * scale
        s = jnp.where(pos[:, None, :, None] >= pos[:, None, None, :], s,
                      -jnp.inf)
        return jnp.einsum("bnsk,bkr->bsnr", jax.nn.softmax(s, -1),
                          rows[..., :r]), "state"
    attend.latent = True
    with jax.default_matmul_precision("highest"):
        a, rows = layers.latent_attention_mixer(
            h, layer, cfg, pos, inv, layers.attend_fresh(pos, None))
        b, state = layers.latent_attention_mixer(h, layer, cfg, pos, inv,
                                                 attend)
    assert state == "state" and rows.shape == (2, 11, cfg.mla.latent_size)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-6


# -- the kernel against its XLA twin ---------------------------------------------

# pages of PS = 8 rows: slots that see 3, 4, 1, 1 (the first token: length 0
# before it), 3 (whole pages exactly at T = 1), 19, 38 and 10 pages: every
# remainder of a group of 2 and of 4, and the ring (4 groups of buffers)
# walked round many times across the slots
_GROUPED = [17, 30, 3, 0, 23, 150, 301, 77]


@pytest.mark.parametrize("T,starts,group", [
    (1, [0, 17, 63], 4), (5, [0, 9, 40], 4), (300, [5], 1),
    (1, _GROUPED, 2), (2, _GROUPED, 2), (1, _GROUPED, 4), (2, _GROUPED, 4),
    (100, [17, 30], 1),
], ids=["decode", "window", "tiled-window", "decode-by-2", "window-of-2-by-2",
        "decode-by-4", "window-of-2-by-4", "rows-over-the-group"])
def test_the_kernel_in_interpret_mode_is_its_twin(monkeypatch, T, starts,
                                                  group):
    """``group``: the pages a loop step scores, READ OFF THE CALL: these
    pages are 4 KB, so 4 where the tile's rows allow a group, and 2 with the
    bytes a page's copy is worth (``PAGE_COPY_BYTES``) brought down to
    where two of them reach it."""
    L, NP, W, R, N = 2, 48, 128, 64, 4
    pool = jax.random.normal(jax.random.PRNGKey(0), (L, NP, 1, PS, W))
    if group == 2:
        monkeypatch.setattr(kv_cache, "PAGE_COPY_BYTES", 2 * PS * W * 4)
    rng = np.random.default_rng(0)
    tables = jnp.asarray(rng.permutation(np.arange(1, 1 + 40 * len(starts))
                                         % (NP - 1) + 1)
                         .reshape(len(starts), 40).astype(np.int32))
    q = jax.random.normal(jax.random.PRNGKey(T), (len(starts), T, N, W))
    args = (q, pool, tables, jnp.asarray(starts, jnp.int32))
    assert mla._tiling(q, pool)[1] == group
    twin, kernel = (mla.mla_paged_attention(
        *args, scale=0.1, value_width=R, impl=impl, layer=1)
        for impl in ("gather", "pallas"))
    assert twin.shape == (len(starts), T, N, R)
    assert np.abs(np.asarray(twin) - np.asarray(kernel)).max() < 2e-6
    if T == 100:       # over the row threshold: the walk a page a step
        def text():
            return jax.jit(functools.partial(
                mla.mla_paged_attention_pallas, scale=0.1, value_width=R,
                layer=1, interpret=True)).lower(*args).as_text()
        as_called = text()
        monkeypatch.setattr(mla, "_pages_a_step", lambda *_: 1)
        assert text() == as_called
        monkeypatch.setattr(mla, "_pages_a_step", lambda *_: 2)
        assert text() != as_called


@pytest.mark.parametrize("page_rows,dtype,rows,group", [
    (256, "bfloat16", 32, 2), (256, "bfloat16", 64, 2),
    (256, "bfloat16", 512, 2), (256, "bfloat16", 1024, 1),
    (128, "bfloat16", 32, 4), (64, "bfloat16", 64, 4),
    (512, "bfloat16", 32, 1), (256, "float32", 32, 1),
    (128, "float32", 256, 2), (128, "float32", 512, 1),
])
def test_pages_a_step_at_the_published_row(page_rows, dtype, rows, group):
    """640-wide rows: a decode step (32 heads), the self-drafting window of
    two, a riding piece's tile of 1,024 rows, smaller and larger pages."""
    q = jax.ShapeDtypeStruct((1, rows // 32, 32, 640), jnp.dtype(dtype))
    pool = jax.ShapeDtypeStruct((7, 9, 1, page_rows, 640), jnp.dtype(dtype))
    assert mla._tiling(q, pool) == (rows // 32, group)


# -- YaRN, the scale, the maps, by hand ------------------------------------------

def test_yarn_frequencies_and_m_by_hand():
    """At the published sizes: 64 rope values, base 10,000, factor 64 over
    4,096. The correction dims are 64 ln(4096 / (2 pi b)) / (2 ln 10000):
    10.4 for beta_fast 32 (floor 10) and 22.5 for beta_slow 1 (ceil 23)."""
    rope = ModelConfig.from_published(dict(
        C, qk_rope_head_dim=64, qk_nope_head_dim=128, rope_scaling=dict(
            C["rope_scaling"], factor=64,
            original_max_position_embeddings=4096))).rope
    f = np.asarray(layers.rope_frequencies(64, 10000.0, "yarn", 64.0,
                                           yarn=rope))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert np.allclose(f[:11], plain[:11])                 # dims 0..10 kept
    assert np.allclose(f[23:], plain[23:] / 64, rtol=1e-6)   # 23.. / factor
    assert np.isclose(f[16], plain[16] * ((1 - 6 / 13) + 6 / 13 / 64))
    m = 0.1 * math.log(64) + 1
    assert abs(m - 1.41589) < 1e-5 and np.isclose(rope.softmax_mscale, m)
    assert np.isclose(m * m, 2.0047, atol=1e-4)
    assert np.allclose(np.asarray(ref.yarn_inv_freq(dict(
        C, qk_rope_head_dim=64, rope_scaling=dict(
            C["rope_scaling"], factor=64,
            original_max_position_embeddings=4096)))), f, rtol=1e-6)


def test_the_residual_map_is_doubly_stochastic_and_clamped(cfg, params):
    hc = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["attn"]["hc"])
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(1),
                                (2, 5, cfg.hc_mult, cfg.hidden_size))
    pre, post, res = layers.hc_maps(x, hc, cfg)
    assert np.abs(np.asarray(res.sum(-1)) - 1).max() < 1e-4
    assert np.abs(np.asarray(res.sum(-2)) - 1).max() < 1e-4
    assert not np.allclose(np.asarray(res[0, 0]), np.eye(4), atol=0.05)
    assert (np.asarray(pre) > 0).all() and (np.asarray(pre) < 1).all()
    assert (np.asarray(post) > 0).all() and (np.asarray(post) < 2).all()
    # the clamp holds at +-30: a bias of +-1000 gives exp(+-30), finite, and
    # Sinkhorn still ends doubly stochastic where the matrix allows it
    wild = dict(hc, b_res=jnp.asarray(
        1000.0 * (2 * np.eye(4, dtype=np.float32) - 1)))
    res = np.asarray(layers.hc_maps(x, wild, cfg)[2])
    assert np.isfinite(res).all()
    assert np.allclose(res[0, 0], np.eye(4), atol=1e-6)


# -- what the chip's check must refuse: each moves the logits --------------------

@pytest.mark.parametrize("wrong", [
    "float8", "float8_latent", "ckv_unnormed", "rope_wrong_dims",
    "scale_without_mscale", "yarn_interpolation", "no_sinkhorn",
    "one_stream", "softmax_scores"])
def test_each_departure_moves_the_logits(params, wrong):
    tokens = support.tokens(48, seed=3)
    moved = np.abs(_reference(params, tokens, wrong=wrong)
                   - _reference(params, tokens)).max()
    assert moved > 20 * TOL, (wrong, moved)


def test_a_hit_on_another_documents_pages_moves_the_logits(params):
    doc, other, q = support.tokens(32, 4), support.tokens(32, 5), support.tokens(8, 6)
    at = range(32, 40)
    moved = np.abs(_reference(params, doc + q, at)
                   - _reference(params, other + q, at)).max()
    assert moved > 20 * TOL


# -- the engine: one latent pool, prefix reuse, chunking -------------------------

# a prompt over 32 tokens goes chunk by chunk (the shared shapes chunk none):
# the cases below serve the cold, the chunk and the suffix program
CHUNKS_OF_32 = dict(chunked_prefill_tokens=32)


def _last_logits(params):
    # (one compiled length for every step of every prompt)
    return lambda context: ref.logits(
        params, context, C, round_to=128, compiled=True,
        positions=[len(context) - 1])[0]


def test_the_engine_serves_from_one_latent_pool(cfg, params):
    eng = support.engine(cfg, params, **CHUNKS_OF_32)
    kv = eng.stats()["kv"]
    assert kv["kind"] == "latent" and eng.kv.v_pages is None
    assert kv["bytes_per_token"] == 3 * cfg.mla.page_width * 4
    assert eng.kv.k_pages.shape == (3, eng.kv.num_pages, 1, PS, 128)
    greedy = SamplingParams(temperature=0.0, max_tokens=6)
    doc = support.tokens(100, seed=7)
    cold_short = support.tokens(20, seed=8)             # cold: under a chunk
    first = doc + [5, 6, 7]                      # chunked: over a chunk
    second = doc + [9, 10, 11, 12]               # a hit on the document
    for prompt in (cold_short, first, second, first):
        got = eng.generate([prompt], greedy)[0].generated_tokens
        assert got == support.greedy(_last_logits(params), prompt, 6)
    st = eng.stats()
    programs = st["compiled_programs"]
    assert programs["prefill_dense_buckets"] == 1       # the cold rung
    assert programs["prefill_chunk_buckets"] == 1
    assert programs["prefill_extend_buckets"] >= 1
    # the second and third document requests took the document's 12 whole
    # pages from the cache
    assert st["prefix_cached_tokens"] >= 2 * 96
    assert st["kv"]["live_pages"] > 0


def test_a_long_prompt_is_chunked_even_where_no_chunk_is_configured(cfg,
                                                                    params):
    eng = support.engine(cfg, params, max_seq_len=2048,
                         kv_hbm_budget_gb=0.01)
    assert eng._chunk_tokens == InferenceEngine.LATENT_COLD_TOKENS == 1024
    prompt = support.tokens(1100, seed=9)
    got = eng.generate([prompt], SamplingParams(temperature=0.0,
                                                max_tokens=2))
    assert len(got[0].generated_tokens) == 2
    programs = eng.stats()["compiled_programs"]
    assert programs["prefill_dense_buckets"] == 0
    assert programs["prefill_chunk_buckets"] == 1


def test_concurrent_document_requests_share_pages_and_all_are_admitted(
        cfg, params):
    """The admission budget charges a request its UNCACHED tokens: eight
    requests on one resident document are admitted four a step (the slots),
    not one, and the pages promised to a request the budget stopped are not
    promised twice."""
    eng = support.engine(cfg, params, prefill_budget_tokens=64,
                         **CHUNKS_OF_32)
    greedy = SamplingParams(temperature=0.0, max_tokens=4)
    doc = support.tokens(100, seed=10)
    eng.generate([doc + [4]], greedy)
    prompts = [doc + support.tokens(5, seed=20 + i) for i in range(8)]
    got = eng.generate(prompts, greedy)
    assert [r.generated_tokens for r in got] == [
        support.greedy(_last_logits(params), p, 4) for p in prompts]
    assert eng._reserved_pages == 0 and not eng._prefix_pins
    assert eng.kv.free_pages == eng.kv.num_pages - 1


# -- refusals, by name -----------------------------------------------------------

@pytest.mark.parametrize("serve,match", [
    (dict(kv_quantization="int8"), "kv_quantization int8 is refused"),
    (dict(kv_quantization="int4"), "kv_quantization int4 is refused"),
    (dict(speculative="ngram"), "speculative is refused"),
    (dict(preemption="swap", swap_space_gb=0.1), "preemption: swap is refused"),
    (dict(tensor_parallel=2), "a model with a layer table serves plain"),
    (dict(quantization="int8"), "a model with a layer table serves plain"),
])
def test_what_the_latent_pool_does_not_carry_is_refused_by_name(
        cfg, params, serve, match):
    with pytest.raises(ValueError, match=match):
        support.engine(cfg, params, **CHUNKS_OF_32, **serve)


def test_page_transfer_and_the_drafter_and_training_are_refused(cfg, params):
    eng = support.engine(cfg, params, **CHUNKS_OF_32)
    eng.generate([support.tokens(20)], SamplingParams(temperature=0.0, max_tokens=2))
    with pytest.raises(ValueError, match="keeps latent pages: fleet prefix "
                                         "export"):
        eng.kv.extract_pages([1])
    with pytest.raises(ValueError, match="fleet prefix fetch is refused"):
        eng.prefix_fetch_hook = lambda req, hashes: None
    with pytest.raises(ConfigError, match="num_nextn_predict_layers = 1"):
        ModelConfig.from_published(dict(C, num_nextn_predict_layers=1))
    with pytest.raises(ConfigError, match="n_group = 2"):
        ModelConfig.from_published(dict(C, n_group=2))
    with pytest.raises(ValueError, match="dropless inference forward only"):
        gpt.forward(params, jnp.asarray([support.tokens(8)]), cfg,
                    moe_impl="capacity")
    with pytest.raises(ValueError, match="keeps no dense K/V cache"):
        gpt.forward(params, jnp.asarray([support.tokens(8)]), cfg,
                    kv_cache=gpt.init_kv_cache(cfg, 1, 8))


# -- counts by hand --------------------------------------------------------------

def test_parameter_and_cache_bytes_by_hand_at_the_published_sizes():
    with open("benchmark/configs/xing4.0-29b-a4b-7l.json") as f:
        config = json.load(f)
    full = ModelConfig.from_published(dict(
        config, num_hidden_layers=40, first_k_dense_replace=2))
    cut = ModelConfig.from_published(config)
    # MLA 2,752,512 + 4,718,592 + 2,064,384 + 4,194,304 + 14,680,064
    mla_ = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 \
        + 4096 * 3584
    assert mla_ == 28_409_856
    hc = 14336 + 14336 * 24 + 3 + 8 + 16
    expert, router = 3 * 3584 * 1024, 3584 * 64 + 64
    attn = mla_ + 768 + 512 + 3584 + hc
    expert_layer = attn + 3584 + hc + router + 65 * expert
    dense_layer = attn + 3584 + hc + 3 * 3584 * 9216
    ends = 2 * 131072 * 3584 + 3584
    assert full.param_count == ends + 2 * dense_layer + 38 * expert_layer
    assert round(full.param_count / 1e9, 1) == 29.5
    assert cut.param_count == ends + dense_layer + 6 * expert_layer
    assert round(cut.param_count * 2 / 1e9, 2) == 11.08
    assert cut.layer_pattern == "*D" + "*E" * 6 and cut.kv_layers == 7
    assert cut.mla.latent_size == 576 and cut.mla.page_width == 640
    assert cut.kv_bytes_per_token() == 7 * 640 * 2 == 8960
    assert np.isclose(cut.softmax_scale, 192 ** -0.5 * 2.00474, rtol=1e-5)
    kv = PagedKVCache(cut, num_slots=1, max_seq_len=512, page_size=256,
                      num_pages=4)
    assert kv.k_pages.shape == (7, 3, 1, 256, 640) and kv.v_pages is None
    # the planner counts the same bytes: 11.08 GB of weights, a page of 256
    # tokens 2.29 MB; 64 requests of 4k tokens of their OWN fit one v5e
    # chip (it does not know that requests share a document's pages)
    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_hardware_preset)
    from distributed_llm_training_and_inference_system_tpu.parallel.planner import (
        ServePlanner)
    planner = ServePlanner(cut, get_hardware_preset("v5e-1"), calibration={})
    assert planner.page_bytes(256) == 256 * 8960
    plan = planner.estimate(batch=64, context_len=4096, prompt_len=512,
                            page_size=256)
    assert plan.fits and round(plan.weight_gb, 2) == 11.08
    assert plan.kv_pages == int(plan.kv_pool_gb * 1e9 // (256 * 8960))
    with pytest.raises(ValueError, match="kv_quantization int8 is refused"):
        planner.estimate(batch=64, page_size=256, kv_quant="int8")
