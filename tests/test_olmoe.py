"""OLMoE-shaped models through the program, against the plain MoE reference
(``benchmark/reference/moe_decoder.py``): dropless top-k routing with the
weights not renormalised, RMSNorm over the whole q / k projection, on the
training-side forward, through the paged serving path, and in the engine.
Float32 on the CPU at a tiny size, seeded random weights; logits are
compared, not sampled tokens.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serving_support as support
from benchmark.reference import moe_decoder
from distributed_llm_training_and_inference_system_tpu.config import get_model_config
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ConfigError,
    ModelConfig,
)
from distributed_llm_training_and_inference_system_tpu.models import gpt
from distributed_llm_training_and_inference_system_tpu.models.layers import (
    moe_block,
    moe_block_capacity,
)
from distributed_llm_training_and_inference_system_tpu.ops import moe_gmm
from distributed_llm_training_and_inference_system_tpu.serve import (
    SamplingParams,
)
from distributed_llm_training_and_inference_system_tpu.serve.decode import (
    decode_step_forward,
    extend_step_forward,
)

# Both sides compute in float32 (the CPU's matmuls are full precision) and
# differ only in the ORDER of their sums: the program adds a token's k
# expert outputs in one reduction and the reference adds all E experts one
# after another, the attention kernels tile differently. Over two layers of
# width 64 that is a few float32 ulps of logits of size ~0.5: measured
# 1.5e-7 to 6e-7. 1e-4 is far above that and far below what any missing
# piece moves the logits by: dropping one (token, expert) pair at capacity
# moves them 3e-3, renormalised top-k weights 2e-2, no q/k norm 5e-2
# (asserted below).
TOL = 1e-4

PUBLISHED = {   # the keys of a published olmoe config.json, tiny values
    "name": "olmoe-test", "model_type": "olmoe", "num_hidden_layers": 2,
    "hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 256,
    "max_position_embeddings": 128, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": False, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "hidden_act": "silu", "tie_word_embeddings": False,
    "clip_qkv": None, "qk_norm": "projection", "dtype": "float32"}
PS = 8      # page size of the paged tests


@pytest.fixture(scope="module")
def cfg():
    return get_model_config("olmoe-test")


@pytest.fixture(scope="module")
def params(cfg):
    """Seeded weights with NON-trivial norm scales (init leaves them 0)."""
    p = support.params_of(cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        s = p["blocks"][name]["scale"]
        p["blocks"][name]["scale"] = 0.3 * jax.random.normal(
            next(keys), s.shape, s.dtype)
    # a router sharp enough that top-2 of 8 is not a coin toss
    p["blocks"]["moe"]["router"]["kernel"] = \
        p["blocks"]["moe"]["router"]["kernel"] * 20.0
    return p


def _ref(params, tokens, **over):
    return np.asarray(moe_decoder.logits(params, tokens,
                                         dict(PUBLISHED, **over)))


def _pages(cfg, n_pages=24):
    shape = (cfg.num_layers, n_pages, cfg.num_kv_heads, PS, cfg.head_dim)
    return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)


# -- (4) the published keys ----------------------------------------------------

def test_published_keys_build_the_preset(cfg):
    got = ModelConfig.from_dict(PUBLISHED)
    assert got.is_moe and got.moe.num_experts == 8
    assert got.moe.experts_per_token == 2 and not got.moe.norm_topk_prob
    assert got.ffn_size == 32 and got.qk_norm == "projection"
    assert got == dataclasses.replace(cfg, rope=got.rope)
    # the nested table still parses, and renormalises unless told not to
    nested = ModelConfig.from_dict({"hidden": 64, "heads": 4, "moe": {
        "num_experts": 4, "top_k": 2}})
    assert nested.moe.num_experts == 4 and nested.moe.norm_topk_prob


def test_num_experts_is_never_silently_dense():
    dense = ModelConfig.from_dict({k: v for k, v in PUBLISHED.items()
                                   if not k.startswith("num_experts")})
    assert not dense.is_moe
    assert ModelConfig.from_dict(PUBLISHED).param_count > 2 * dense.param_count
    with pytest.raises(ConfigError):
        ModelConfig.from_dict(dict(PUBLISHED, num_experts_per_tok=9))
    with pytest.raises(ConfigError):
        ModelConfig.from_dict(dict(PUBLISHED, qk_norm="per-head"))


@pytest.mark.parametrize("name", ["olmoe-test", "olmoe-1b-7b"])
def test_param_count_is_the_tree(name):
    cfg = get_model_config(name)
    tree = jax.eval_shape(lambda k: gpt.init(cfg, k), jax.random.PRNGKey(0))
    assert cfg.param_count == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    if name == "olmoe-1b-7b":       # the model card's 6.9 B total, 1.3 B active
        assert 6.9e9 < cfg.param_count < 6.93e9
        assert tree["blocks"]["q_norm"]["scale"].shape == (16, 2048)
        assert 1.27e9 < gpt.flops_per_token(cfg, 0) / 6 + 50304 * 2048 < 1.30e9


# -- (1) the training-side forward against the reference --------------------

def test_forward_matches_the_reference(cfg, params):
    tokens = support.tokens(40)
    got = np.asarray(gpt.forward(params, jnp.asarray([tokens]), cfg))[0]
    want = _ref(params, tokens)
    assert np.abs(got - want).max() < TOL
    assert want.std() > 0.1         # logits worth comparing


@pytest.mark.parametrize("wrong,least", [
    ({"norm_topk_prob": True}, 1e-3), ({"qk_norm": "none"}, 1e-3)])
def test_the_tolerance_sees_what_is_left_out(cfg, params, wrong, least):
    tokens = support.tokens(40)
    got = np.asarray(gpt.forward(params, jnp.asarray([tokens]), cfg))[0]
    assert np.abs(got - _ref(params, tokens, **wrong)).max() > max(
        least, 10 * TOL)


def test_capacity_route_drops_and_the_serving_route_does_not(cfg, params):
    """Training's capacity dispatch loses (token, expert) pairs when an
    expert overflows; the reference never does, so only the dropless route
    agrees with it."""
    tokens = support.tokens(40)
    want = _ref(params, tokens)
    capacity = np.asarray(gpt.forward(params, jnp.asarray([tokens]), cfg,
                                      moe_impl="capacity"))[0]
    assert np.abs(capacity - want).max() > 10 * TOL
    with pytest.raises(ValueError, match="capacity"):
        gpt.forward(params, jnp.asarray([tokens]), cfg, return_aux=True)


# -- (2) prefill, then decode, through the paged cache -----------------------

def _cold_prefill(cfg, params, tokens, bucket, kp, vp, pages):
    """What the engine's prefill program does: the dense forward over a
    padded bucket, its K/V scattered into ``pages``; logits of the real
    positions."""
    n = len(tokens)
    padded = np.full((1, bucket), 7, np.int32)      # garbage padding
    padded[0, :n] = tokens
    live = (jnp.arange(bucket)[None] < n).astype(jnp.int32)
    logits, (kd, vd), stats = gpt.forward(
        params, jnp.asarray(padded), cfg,
        kv_cache=gpt.init_kv_cache(cfg, 1, bucket, dtype=jnp.float32),
        cache_offset=jnp.zeros((1,), jnp.int32), segment_ids=live,
        return_moe_stats=True)

    def paged(d):
        return d[:, 0].reshape(cfg.num_layers, bucket // PS, PS,
                               cfg.num_kv_heads, cfg.head_dim
                               ).transpose(0, 1, 3, 2, 4)
    entries = jnp.asarray(pages[:bucket // PS])
    return (np.asarray(logits)[0, :n], kp.at[:, entries].set(paged(kd)),
            vp.at[:, entries].set(paged(vd)), stats)


@pytest.mark.parametrize("route", ["cold", "suffix", "chunked"])
def test_prefill_then_decode_matches_the_reference(cfg, params, route):
    """The whole served sequence, position by position, against the
    reference's full forward: the prompt through cold prefill (padded
    bucket), suffix prefill over cached pages, or chunked prefill; then
    eight decode steps in a batch of four slots of which one is live."""
    seq = support.tokens(37 + 8, seed=2)
    n = 37
    want = _ref(params, seq)
    kp, vp = _pages(cfg)
    table = np.zeros((4, 8), np.int32)
    table[1, :6] = [3, 4, 5, 6, 7, 8]               # slot 1 is the request
    got = np.zeros((len(seq), cfg.vocab_size), np.float32)
    if route == "cold":
        got[:n], kp, vp, _ = _cold_prefill(cfg, params, seq[:n], 48, kp, vp,
                                           list(table[1, :6]))
    else:
        cuts = [0, 16, n] if route == "suffix" else [0, 8, 16, 24, 32, n]
        for a, b in zip(cuts, cuts[1:]):
            T = 24 if route == "suffix" else 8      # the window is padded
            window = np.full((1, T), 9, np.int32)
            window[0, :b - a] = seq[a:b]
            lg, kp, vp, *_ = extend_step_forward(
                params, jnp.asarray(window), jnp.asarray([a], jnp.int32),
                kp, vp, jnp.asarray(table[1:2]), cfg,
                write_ok=jnp.arange(T)[None] < (b - a))
            got[a:b] = np.asarray(lg)[0, :b - a]
    for pos in range(n, len(seq)):
        toks = np.full(4, 11, np.int32)             # idle slots' garbage
        toks[1] = seq[pos]
        lg, kp, vp, stats, _ = decode_step_forward(
            params, jnp.asarray(toks), jnp.full((4,), pos, jnp.int32), kp,
            vp, jnp.asarray(table), cfg,
            active=jnp.asarray([False, True, False, False]),
            return_moe_stats=True)
        got[pos] = np.asarray(lg)[1]
        stats = np.asarray(stats)
        # one live token: k choices a layer, k experts hit a layer
        assert stats[:-1].sum() == cfg.num_layers * 2
        assert stats[-1] == cfg.num_layers * 2
    assert np.abs(got - want).max() < TOL


# -- (3) batch independence ------------------------------------------------------

def test_a_request_does_not_depend_on_its_companions(cfg, params):
    """One request's logits alone, in a full batch of other requests, and
    behind a longer padded prefill bucket are equal: no row displaces
    another, and padding and idle slots are never routed."""
    seq = support.tokens(21, seed=3)
    table = np.zeros((4, 8), np.int32)
    for slot in range(4):
        table[slot, :4] = 1 + 4 * slot + np.arange(4)

    def extend(batch_rows, slot):
        kp, vp = _pages(cfg)
        window = np.stack(batch_rows).astype(np.int32)
        lg, *_ = extend_step_forward(
            params, jnp.asarray(window),
            jnp.zeros((len(batch_rows),), jnp.int32), kp, vp,
            jnp.asarray(table[:len(batch_rows)]), cfg)
        return np.asarray(lg)[slot]

    alone = extend([seq], 0)
    others = [support.tokens(21, seed=10 + i) for i in range(3)]
    crowded = extend([others[0], others[1], seq, others[2]], 2)
    # same rows at another place in another batch: the same logits to the
    # last float32 ulps of a differently tiled matmul
    assert np.abs(alone - crowded).max() < 1e-5
    kp, vp = _pages(cfg)
    short, *_ = _cold_prefill(cfg, params, seq, 24, kp, vp, [1, 2, 3])
    long_, _, _, stats = _cold_prefill(cfg, params, seq, 64, kp, vp,
                                       list(range(1, 9)))
    assert np.abs(short - long_).max() < 1e-5
    assert np.abs(short - alone).max() < 1e-5
    # 43 rows of padding chose nothing
    assert int(np.asarray(stats)[:-1].sum()) == cfg.num_layers * 2 * len(seq)


def test_engine_serves_the_same_tokens_alone_and_in_a_full_batch(cfg, params):
    def engine():
        return support.engine(cfg, params)
    prompts = [support.tokens(n, seed=20 + n) for n in (30, 9, 17, 25)]
    greedy = SamplingParams(temperature=0.0, max_tokens=10)
    together = [r.generated_tokens
                for r in engine().generate(prompts, greedy)]
    eng = engine()
    [alone] = eng.generate(prompts[:1], greedy)
    assert alone.generated_tokens == together[0]
    # and they are the reference's argmax, teacher-forced
    seq = prompts[0] + alone.generated_tokens
    want = _ref(params, seq[:-1])[len(prompts[0]) - 1:]
    assert np.argmax(want, -1).tolist() == alone.generated_tokens
    # counters: one prefill of 30 live tokens and decode steps of ONE live
    # slot; idle slots and the prefill bucket's 2 rows of padding excluded
    moe = eng.stats()["moe"]
    L, K = cfg.num_layers, cfg.moe.experts_per_token
    steps = moe["decode_layer_steps"] // L
    assert moe["layer_steps"] == L * (steps + 1)
    # (a slot is live while its position is under its stop position: the
    # 9 steps that made tokens 2..10, and at most the dispatch's rest)
    live_steps, rest = divmod(sum(moe["choices"]) - L * K * 30, L * K)
    assert rest == 0 and 9 <= live_steps <= steps
    assert moe["decode_experts_hit"] == L * K * live_steps
    assert moe["experts_hit"] <= cfg.moe.num_experts * moe["layer_steps"]


def test_qk_norm_under_tensor_parallel_reduces_across_the_shards():
    """The projection norm spans the axis tp shards; GSPMD does the
    reduction (the scales follow the projection's sharding rule). tp=2
    serves the same greedy tokens as tp=1. (A dense model with the norm:
    an MoE model is refused under tp, below.)"""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    from distributed_llm_training_and_inference_system_tpu.parallel.sharding import (
        spec_for_path)
    assert tuple(spec_for_path("blocks.q_norm.scale", stacked=True)) == (
        "pp", "tp")
    dense = ModelConfig.from_dict(dict(
        {k: v for k, v in PUBLISHED.items() if "expert" not in k
         and k != "norm_topk_prob"}, intermediate_size=128))
    assert not dense.is_moe and dense.qk_norm == "projection"
    params = gpt.init(dense, jax.random.PRNGKey(0))
    for i, name in enumerate(("q_norm", "k_norm")):
        s = params["blocks"][name]["scale"]
        params["blocks"][name]["scale"] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(7 + i), s.shape, s.dtype)
    prompts = [support.tokens(19, seed=5), support.tokens(11, seed=6)]
    greedy = SamplingParams(temperature=0.0, max_tokens=8)
    out = []
    for tp in (1, 2):
        eng = support.engine(dense, params, max_batch_size=2,
                             tensor_parallel=tp)
        out.append([r.generated_tokens for r in eng.generate(prompts, greedy)])
    assert out[0] == out[1]


def test_an_moe_model_under_tensor_parallel_is_refused(cfg, params):
    """The grouped-matmul kernel cannot be partitioned and ragged_dot under
    a tp mesh has never run on the chip: a clear error, not an unmeasured
    route."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    with pytest.raises(ValueError, match="MoE model is refused"):
        support.engine(cfg, params, tensor_parallel=2)


# -- the dropless block and its kernel ------------------------------------------

@pytest.mark.parametrize("n_tokens,live_tokens", [(5, 5), (32, 20), (64, 64)])
def test_moe_block_kernel_route_is_the_xla_route(cfg, params, n_tokens,
                                                 live_tokens, monkeypatch):
    """The Pallas kernel (interpreted here) and ragged_dot on one layout;
    whole stack with a layer index against one layer's slice; dead rows
    return zeros and are not counted."""
    moe = params["blocks"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(n_tokens),
                          (1, n_tokens, cfg.hidden_size), jnp.float32)
    live = (jnp.arange(n_tokens) < live_tokens)[None]
    stack = dict(moe, router={"kernel": moe["router"]["kernel"][1]})
    layer1 = jax.tree.map(lambda a: a[1], moe)
    want, want_counts = moe_block(x, layer1, cfg, live=live)   # ragged_dot

    def kernel(lhs, rhs, tile_group, tiles_used, layer=None, *, tm, name):
        if layer is None:
            rhs, layer = rhs[None], 0
        return moe_gmm.moe_gmm(lhs, rhs, tile_group, tiles_used, layer,
                               tm=tm, name=name, interpret=True)
    monkeypatch.setattr(moe_gmm, "grouped_matmul", kernel)
    for layer, index in ((stack, jnp.int32(1)), (layer1, None)):
        got, counts = moe_block(x, layer, cfg, live=live, layer_index=index)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        assert np.asarray(counts).tolist() == np.asarray(
            want_counts).tolist()
    assert int(want_counts.sum()) == 2 * live_tokens
    assert not np.asarray(want)[0, live_tokens:].any()
    # every live token got all of its k experts: against the capacity
    # block given room for everything, which then drops nothing
    roomy = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
    full, _ = moe_block_capacity(x[:, :live_tokens], layer1, roomy)
    np.testing.assert_allclose(np.asarray(want)[:, :live_tokens],
                               np.asarray(full), atol=1e-5, rtol=1e-5)


def test_grouped_matmul_skips_unused_tiles():
    rng = np.random.default_rng(0)
    E, K, N, tm = 8, 64, 256, 8
    tiles = [2, 0, 1, 3, 0, 1, 1, 2]
    used = sum(tiles)
    group = np.repeat(np.arange(E), tiles).tolist()
    group += [group[-1]] * 3                        # the clamped tail
    lhs = jnp.asarray(rng.normal(size=(len(group) * tm, K)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(3, E, K, N)), jnp.float32)
    want = np.concatenate([
        np.asarray(lhs[i * tm:(i + 1) * tm]) @ np.asarray(rhs[1, group[i]])
        if i < used else np.zeros((tm, N), np.float32)
        for i in range(len(group))])
    args = (lhs, rhs, jnp.asarray(group), jnp.int32(used), jnp.int32(1))
    # the kernel writes zeros past the used tiles
    got = moe_gmm.moe_gmm(*args, tm=tm, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)
    # ragged_dot (the route off the TPU) leaves those rows undefined
    got = moe_gmm.grouped_matmul(*args, tm=tm)
    np.testing.assert_allclose(np.asarray(got)[:used * tm],
                               want[:used * tm], atol=1e-4)


def test_norm_topk_prob_is_a_configuration_not_a_constant(cfg, params):
    """gpt-moe-* renormalise (the default); OLMoE does not; both through
    one router."""
    layer1 = jax.tree.map(lambda a: a[0], params["blocks"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 6, cfg.hidden_size))
    renorm = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, norm_topk_prob=True))
    a, _ = moe_block(x, layer1, cfg)
    b, _ = moe_block(x, layer1, renorm)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() > 1e-4
    assert get_model_config("gpt-moe-1b").moe.norm_topk_prob


def test_dense_model_programs_carry_nothing_of_this():
    """A dense model's decode step returns three values and its parameter
    tree has no norm on q and k."""
    cfg = get_model_config("gpt-test")
    tree = jax.eval_shape(lambda k: gpt.init(cfg, k), jax.random.PRNGKey(0))
    assert "q_norm" not in tree["blocks"] and "moe" not in tree["blocks"]
    eng = support.engine(cfg)
    eng.generate([[5, 6, 7]], SamplingParams(temperature=0.0, max_tokens=3))
    assert "moe" not in eng.stats()


def test_serve_planner_prices_a_dropless_moe():
    """Prefill by the ACTIVE parameters, decode by the experts a batch
    hits, and the dispatch buffers without a capacity factor."""
    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_hardware_preset)
    from distributed_llm_training_and_inference_system_tpu.parallel.planner import (
        ServePlanner)
    cfg = get_model_config("olmoe-1b-7b")
    plan = ServePlanner(cfg, get_hardware_preset("v5e-1"), calibration={})
    active = plan.active_param_count()
    assert 1.27e9 < active < 1.30e9          # the model card's 1.3 B active
    one, full = (plan.moe_decode_weight_fraction(b) for b in (1, 32))
    # one token reads 8 of 64 experts a layer; 32 tokens hit ~98 % of them
    assert 0.17 < one < 0.19 and 0.97 < full < 0.99
    rows = 1024 * 8 + 64 * 127
    assert plan.moe_dispatch_bytes(1024) == rows * (2 * 2048 + 2 * 1024) * 2
    dense = ServePlanner(get_model_config("mistral-7b"),
                         get_hardware_preset("v5e-1"), calibration={})
    assert dense.moe_dispatch_bytes(1024) == 0.0
    assert dense.moe_decode_weight_fraction(8) == 1.0
    assert dense.active_param_count() == get_model_config(
        "mistral-7b").param_count
