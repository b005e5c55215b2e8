"""Model unit tests: shapes, causality, cache equivalence, MoE.

The reference has zero model-level tests (SURVEY §4: 4 CLI assertions
total); these are the unit layer of the rebuild's test pyramid.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_training_and_inference_system_tpu.config import get_model_config
from distributed_llm_training_and_inference_system_tpu.models import (
    forward, init, init_kv_cache, next_token_loss)
from distributed_llm_training_and_inference_system_tpu.models.gpt import flops_per_token


@pytest.fixture(scope="module")
def cfg():
    return get_model_config("gpt-test")


@pytest.fixture(scope="module")
def params(cfg):
    return init(cfg, jax.random.PRNGKey(0))


def test_forward_shapes_and_dtype(cfg, params):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits = forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_determinism(cfg, params):
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0, cfg.vocab_size)
    a = forward(params, tokens, cfg)
    b = forward(params, tokens, cfg)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_causality(cfg, params):
    """Changing a future token must not affect past logits."""
    key = jax.random.PRNGKey(3)
    tokens = jax.random.randint(key, (1, 12), 0, cfg.vocab_size)
    logits_a = forward(params, tokens, cfg)
    tokens_b = tokens.at[0, 8].set((tokens[0, 8] + 1) % cfg.vocab_size)
    logits_b = forward(params, tokens_b, cfg)
    np.testing.assert_allclose(np.asarray(logits_a[0, :8]),
                               np.asarray(logits_b[0, :8]), atol=1e-5)
    assert not np.allclose(np.asarray(logits_a[0, 8:]), np.asarray(logits_b[0, 8:]))


def test_packed_segments_isolation(cfg, params):
    """Tokens in segment 2 must be unaffected by segment 1's content."""
    key = jax.random.PRNGKey(4)
    seq_a = jax.random.randint(key, (1, 6), 0, cfg.vocab_size)
    seq_b = jax.random.randint(jax.random.PRNGKey(5), (1, 6), 0, cfg.vocab_size)
    seq_c = jax.random.randint(jax.random.PRNGKey(6), (1, 6), 0, cfg.vocab_size)

    packed_1 = jnp.concatenate([seq_a, seq_b], axis=1)
    packed_2 = jnp.concatenate([seq_c, seq_b], axis=1)
    segs = jnp.concatenate([jnp.full((1, 6), 1), jnp.full((1, 6), 2)], axis=1)
    pos = jnp.concatenate([jnp.arange(6), jnp.arange(6)])[None, :]

    l1 = forward(params, packed_1, cfg, segment_ids=segs, positions=pos)
    l2 = forward(params, packed_2, cfg, segment_ids=segs, positions=pos)
    np.testing.assert_allclose(np.asarray(l1[0, 6:]), np.asarray(l2[0, 6:]),
                               atol=1e-5)


def test_kv_cache_decode_matches_full_forward(cfg, params):
    """Prefill + step-by-step decode must reproduce the full forward logits.

    This is the correctness property the reference's serve loop violates by
    recomputing the full prefix and discarding the cache (SURVEY §2.4.2)."""
    B, S = 2, 10
    tokens = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0, cfg.vocab_size)
    full_logits = forward(params, tokens, cfg)

    k_cache, v_cache = init_kv_cache(cfg, B, 16, dtype=jnp.float32)
    prefill_len = 6
    offset = jnp.zeros((B,), jnp.int32)
    logits_p, cache = forward(params, tokens[:, :prefill_len], cfg,
                              kv_cache=(k_cache, v_cache), cache_offset=offset)
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(full_logits[:, :prefill_len]),
                               rtol=2e-4, atol=2e-4)
    # decode one token at a time
    for t in range(prefill_len, S):
        offset = jnp.full((B,), t, jnp.int32)
        logits_t, cache = forward(params, tokens[:, t:t + 1], cfg,
                                  kv_cache=cache, cache_offset=offset)
        np.testing.assert_allclose(np.asarray(logits_t[:, 0]),
                                   np.asarray(full_logits[:, t]),
                                   rtol=2e-4, atol=2e-4)


def test_loss_decreases_on_repeated_batch(cfg, params):
    """One SGD step on a fixed batch must reduce its loss (learnability)."""
    tokens = jax.random.randint(jax.random.PRNGKey(8), (4, 16), 0, cfg.vocab_size)

    def loss_fn(p):
        return next_token_loss(forward(p, tokens, cfg), tokens)[0]

    l0, grads = jax.value_and_grad(loss_fn)(params)
    p2 = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads)
    l1 = loss_fn(p2)
    assert float(l1) < float(l0)


def test_moe_forward_and_grads():
    cfg = get_model_config("gpt-test-moe")
    params = init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits, aux = forward(params, tokens, cfg, return_aux=True,
                          moe_impl="capacity")
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert float(aux) > 0.0  # router aux loss is live

    def loss_fn(p):
        lg, aux = forward(p, tokens, cfg, return_aux=True,
                              moe_impl="capacity")
        return next_token_loss(lg, tokens)[0] + aux

    grads = jax.grad(loss_fn)(params)
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0
    # router must receive gradient (MoE is differentiable end-to-end)
    r = grads["blocks"]["moe"]["router"]["kernel"]
    assert float(jnp.sum(jnp.abs(r))) > 0


def _moe_block_onehot_reference(x, layer, cfg):
    """GShard one-hot einsum dispatch — the round-1..4 formulation, kept
    as the numerical reference for the sort-based dispatch that replaced
    it (the [N, E, C] one-hot tensors were the measured 20.8 GB MoE
    training OOM; see models/layers.py moe_block docstring)."""
    from distributed_llm_training_and_inference_system_tpu.models.layers import (
        _activate)
    B, S, H = x.shape
    E = cfg.moe.num_experts
    K = cfg.moe.experts_per_token
    N = B * S
    C = max(int(cfg.moe.capacity_factor * K * N / E), 1)

    xt = x.reshape(N, H)
    logits = jnp.einsum("nh,he->ne", xt.astype(jnp.float32),
                        layer["router"]["kernel"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(top_e, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot.reshape(N * K, E), axis=0) - onehot.reshape(N * K, E)
    pos = jnp.sum(pos.reshape(N, K, E) * onehot, axis=-1)
    fits = pos < C
    disp = (jax.nn.one_hot(top_e, E, dtype=x.dtype)[..., None]
            * jax.nn.one_hot(jnp.where(fits, pos, C), C + 1,
                             dtype=x.dtype)[..., None, :-1])
    combine = disp * top_p[..., None, None].astype(x.dtype)
    disp = jnp.sum(disp, axis=1)
    combine = jnp.sum(combine, axis=1)
    xe = jnp.einsum("nec,nh->ech", disp, xt)

    def expert_ffn(w, xe_):
        g = jnp.einsum("ch,hf->cf", xe_, w["gate"])
        u = jnp.einsum("ch,hf->cf", xe_, w["up"])
        return jnp.einsum("cf,fh->ch", _activate(g, cfg.activation) * u,
                          w["down"])

    he = jax.vmap(expert_ffn)(
        {"gate": layer["gate"]["kernel"], "up": layer["up"]["kernel"],
         "down": layer["down"]["kernel"]}, xe)
    return jnp.einsum("nec,ech->nh", combine, he).reshape(B, S, H)


@pytest.mark.parametrize("capacity_factor", [1.25, 0.35])
def test_moe_sort_dispatch_matches_onehot(capacity_factor):
    """The sort-based dispatch must be numerically identical to the
    one-hot einsum formulation — INCLUDING which overflow tokens drop at
    tight capacity (stable sort preserves the token-major choice order
    the cumsum-based position assignment used)."""
    import dataclasses

    from distributed_llm_training_and_inference_system_tpu.models.layers import (
        moe_block_capacity)
    cfg = get_model_config("gpt-test-moe")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe,
                                     capacity_factor=capacity_factor))
    params = init(cfg, jax.random.PRNGKey(0))
    layer = jax.tree_util.tree_map(lambda p: p[0],
                                   params["blocks"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.hidden_size),
                          jnp.float32)
    got, _ = moe_block_capacity(x, layer, cfg)
    want = _moe_block_onehot_reference(x, layer, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_moe_learns_under_tight_capacity():
    """Token dropping at capacity_factor=1.0 must not break learning —
    the dropped-token residual fallback is the GShard/Switch semantics,
    and a dispatch bug that misroutes (rather than drops) tokens shows
    up here as a flat loss."""
    import dataclasses

    cfg = get_model_config("gpt-test-moe")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=1.0))
    params = init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0,
                                cfg.vocab_size)

    @jax.jit
    def step(p):
        def loss_fn(p):
            lg, aux = forward(p, tokens, cfg, return_aux=True,
                              moe_impl="capacity")
            return next_token_loss(lg, tokens)[0] + aux
        l, g = jax.value_and_grad(loss_fn)(p)
        return l, jax.tree_util.tree_map(lambda w, gr: w - 0.05 * gr, p, g)

    l0, params = step(params)
    for _ in range(60):
        loss, params = step(params)
    # measured: 5.60 -> 3.94 over 60 steps on CPU; a misrouting bug
    # leaves the loss near the 5.5 unigram floor
    assert float(loss) < 0.8 * float(l0), (float(l0), float(loss))


def test_remat_matches_baseline(cfg, params):
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 16), 0, cfg.vocab_size)
    base = forward(params, tokens, cfg, remat="none")
    sel = forward(params, tokens, cfg, remat="selective")
    full = forward(params, tokens, cfg, remat="full")
    np.testing.assert_allclose(np.asarray(base), np.asarray(sel), atol=1e-5)
    np.testing.assert_allclose(np.asarray(base), np.asarray(full), atol=1e-5)


def test_flops_per_token_sane():
    cfg7 = get_model_config("gpt-7b")
    f = flops_per_token(cfg7, 2048)
    # ~6 * 7e9 ≈ 4.2e10 dense + attention term
    assert 3e10 < f < 9e10


def test_chunked_loss_matches_dense(cfg, params):
    """chunked_next_token_loss (scan + per-chunk remat, no [B,S,V] resident)
    must match the dense next_token_loss in value AND gradient, including
    packed-segment masking."""
    from distributed_llm_training_and_inference_system_tpu.exec.train_step import (
        _loss_fn)

    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 1,
                                cfg.vocab_size)
    segs = jnp.concatenate([jnp.ones((2, 40), jnp.int32),
                            2 * jnp.ones((2, 20), jnp.int32),
                            jnp.zeros((2, 4), jnp.int32)], axis=1)
    batch = {"tokens": tokens, "segment_ids": segs}

    def dense(p):
        total, (loss, count) = _loss_fn(p, batch, cfg, "xla", "none",
                                        loss_chunk=0)
        return total

    def chunked(p):
        total, (loss, count) = _loss_fn(p, batch, cfg, "xla", "none",
                                        loss_chunk=24)   # non-divisor: pads
        return total

    l_ref, g_ref = jax.value_and_grad(dense)(params)
    l_chk, g_chk = jax.value_and_grad(chunked)(params)
    np.testing.assert_allclose(float(l_chk), float(l_ref), rtol=1e-5)
    flat_r = jax.tree_util.tree_leaves(g_ref)
    flat_c = jax.tree_util.tree_leaves(g_chk)
    for r, c in zip(flat_r, flat_c):
        np.testing.assert_allclose(np.asarray(c), np.asarray(r),
                                   rtol=2e-4, atol=1e-5)


_LOSS_B, _LOSS_S, _LOSS_H = 2, 64, 32
# 384 = 3 lane-wide slices of 128 under the limit below; 389 is prime: no cut
_LOSS_SLICE_LIMIT = _LOSS_B * 72 * 128 * 4


@pytest.mark.parametrize("vocab, walk", [(384, "vocabulary"), (389, "rows")],
                         ids=["lane-wide-slices", "no-cut"])
@pytest.mark.parametrize("segments", [True, False],
                         ids=["packed-and-padded", "no-segment-ids"])
@pytest.mark.parametrize("z_loss", [0.0, 1e-3], ids=["no-z-loss", "z-loss"])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_chunked_loss_backward_matches_dense(monkeypatch, tied, z_loss,
                                             segments, vocab, walk):
    """The hand-written backward, walking the vocabulary in three slices or
    (a vocabulary with no cut) the rows in three chunks: loss and BOTH
    gradients are the dense ``next_token_loss``'s, for a head ``[H, V]`` and
    a tied embedding ``[V, H]``, with z-loss, segment masks and padding, and
    a ``loss_chunk`` (24) that does not divide the 63 targets."""
    from distributed_llm_training_and_inference_system_tpu.models import loss
    monkeypatch.setattr(loss, "SLICE_BYTES_LIMIT", _LOSS_SLICE_LIMIT)
    B, S, H = _LOSS_B, _LOSS_S, _LOSS_H
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    hidden = jax.random.normal(keys[0], (B, S, H))
    w = 0.2 * jax.random.normal(keys[1], (vocab, H) if tied else (H, vocab))
    tokens = jax.random.randint(keys[2], (B, S), 1, vocab)
    segs = jnp.concatenate([jnp.ones((B, 40), jnp.int32),
                            2 * jnp.ones((B, 20), jnp.int32),
                            jnp.zeros((B, 4), jnp.int32)], axis=1
                           ) if segments else None
    plan = loss.plan_loss_backward(rows=B * 72, chunks=3, hidden=H,
                                   vocab=vocab)
    assert (plan.axis, plan.slices) == (walk, 3)

    def dense(h, w):
        logits = jnp.einsum("bsh,vh->bsv" if tied else "bsh,hv->bsv", h, w)
        return next_token_loss(logits, tokens, segs, z_loss)[0]

    def chunked(h, w):
        return loss.chunked_next_token_loss(h, w, tokens, segs, z_loss,
                                            chunk=24, tied=tied)[0]

    l_ref, g_ref = jax.value_and_grad(dense, argnums=(0, 1))(hidden, w)
    l_chk, g_chk = jax.jit(jax.value_and_grad(chunked, argnums=(0, 1)))(
        hidden, w)
    np.testing.assert_allclose(float(l_chk), float(l_ref), rtol=1e-5)
    for r, c in zip(g_ref, g_chk):
        np.testing.assert_allclose(np.asarray(c), np.asarray(r),
                                   rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("shapes, want", [
    # internlm2-1.8b-6l.pretrain-4k: [2, 4096] rows, the whole head
    (dict(rows=8192, chunks=8, hidden=2048, vocab=92544),
     ("vocabulary", 3, 30848, 67108864, 1010827264)),
    # internlm2-1.8b.pretrain-4k-fsdp4: [4, 4096] rows, a quarter of the
    # head a chip: a vocabulary spread over devices walks rows (two slices
    # of 11,568 a shard measured 40.9 ms against 39.4, PR 48: the rows'
    # gather and their gradient's reduce stood alone around the loop)
    (dict(rows=16384, chunks=8, hidden=2048, vocab=92544, shards=4),
     ("rows", 8, 2048, 189530112, 189530112)),
    # and so do few rows against a wide shard
    (dict(rows=2048, chunks=2, hidden=64, vocab=65536, shards=4),
     ("rows", 2, 1024, 4194304, 67108864)),
    # the gpt-* templates' 50,304 = 3 x 131 x 128
    (dict(rows=8192, chunks=8, hidden=2048, vocab=50304),
     ("vocabulary", 3, 16768, 67108864, 549453824)),
    # twice a prime: no slice of a lane or more fits, the rows are walked
    (dict(rows=8192, chunks=8, hidden=2048, vocab=2 * 50021),
     ("rows", 8, 1024, 819544064, 409772032)),
    # a test model's whole vocabulary is one slice of any width
    (dict(rows=30, chunks=1, hidden=64, vocab=250),
     ("vocabulary", 1, 250, 7680, 30000)),
    # few rows against a small head: the head's gradient is the smaller carry
    (dict(rows=65536, chunks=2, hidden=4096, vocab=1024),
     ("rows", 2, 32768, 16777216, 134217728)),
], ids=["pretrain-4k", "pretrain-4k-fsdp4", "wide-shard", "gpt-vocabulary",
        "twice-a-prime", "one-slice", "rows-rewrite-less"])
def test_loss_backward_plan_from_shapes(shapes, want):
    """Which axis the loss's backward walks is decided by the bytes each
    loop would rewrite, from shapes alone: (axis, slices, width, the carry's
    bytes, one iteration's float32 logits)."""
    from distributed_llm_training_and_inference_system_tpu.models.loss import (
        plan_loss_backward)
    assert plan_loss_backward(**shapes) == want


def test_three_steps_agree_between_the_walks(cfg, monkeypatch):
    """Three optimizer steps of ``gpt-test``: the losses with the backward
    walking the vocabulary are those with it walking the rows."""
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        OptimizerConfig)
    from distributed_llm_training_and_inference_system_tpu.exec.train_step import (
        TrainState, make_train_step)
    from distributed_llm_training_and_inference_system_tpu.models import loss
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(5), (4, 48), 1,
                                          cfg.vocab_size)}

    def losses(walk):
        # no slice fits under 0 bytes: the rows are walked
        monkeypatch.setattr(loss, "SLICE_BYTES_LIMIT",
                            0 if walk == "rows" else 1 << 30)
        step_fn, tx, _ = make_train_step(cfg, OptimizerConfig(lr=1e-2),
                                         loss_chunk=16)
        state = TrainState.create(init(cfg, jax.random.PRNGKey(0)), tx)
        step = jax.jit(step_fn)
        out = []
        for _ in range(3):
            state, metrics = step(state, batch)
            out.append(float(metrics["loss"]))
        assert loss.chunked_loss_backward_plan(
            4, 48, cfg.hidden_size, cfg.vocab_size, 16).axis == walk
        return out

    by_vocabulary, by_rows = losses("vocabulary"), losses("rows")
    assert by_vocabulary[2] < by_vocabulary[0]
    np.testing.assert_allclose(by_vocabulary, by_rows, rtol=1e-5)


# ---------------------------------------------------------------------------
# One block: every program that runs a layer runs models.layers.decoder_block,
# so every route gives gpt.forward's numbers for every feature of the block.
# A route that spells the equations out again fails here the day the block
# learns something the copy did not.
# ---------------------------------------------------------------------------

_FEATURES = {
    "attention_bias": ("gpt-test", {"attention_bias": True}),
    "qk_norm": ("gpt-test", {"qk_norm": "projection"}),
    "tied_embeddings": ("gpt-test", {"tie_word_embeddings": True}),
    "dropless_moe": ("gpt-test-moe", {}),
}
_B, _S, _PS = 2, 16, 8


def _feature_model(feature):
    import dataclasses
    name, changes = _FEATURES[feature]
    cfg = dataclasses.replace(get_model_config(name), **changes)
    params = init(cfg, jax.random.PRNGKey(7))

    # init leaves biases and norm scales at zero, where a route that forgot
    # one of them would still agree
    def visible(path, p):
        if path[-1].key in ("bias", "scale"):
            key = jax.random.fold_in(jax.random.PRNGKey(8), p.size)
            return p + 0.3 * jax.random.normal(key, p.shape, p.dtype)
        return p
    params = jax.tree_util.tree_map_with_path(visible, params)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (_B, _S), 0,
                                cfg.vocab_size)
    return cfg, params, tokens


def _route_training(cfg, params, tokens):
    want = forward(params, tokens, cfg)
    got = forward(params, tokens, cfg, remat="selective",
                  segment_ids=jnp.ones_like(tokens))
    return got, want


def _route_cold_prefill(cfg, params, tokens):
    cache = init_kv_cache(cfg, _B, 2 * _S, dtype=jnp.float32)
    got, _ = forward(params, tokens, cfg, kv_cache=cache,
                     cache_offset=jnp.zeros((_B,), jnp.int32))
    return got, forward(params, tokens, cfg)


def _paged_extend(cfg, params, tokens, window):
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        extend_step_forward)
    pages_a_slot = _S // _PS
    shape = (cfg.num_layers, 1 + _B * pages_a_slot, cfg.num_kv_heads, _PS,
             cfg.head_dim)
    kp, vp = jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
    tables = 1 + jnp.arange(_B * pages_a_slot, dtype=jnp.int32).reshape(
        _B, pages_a_slot)                       # page 0 is the scratch page
    step = jax.jit(lambda t, start, kp, vp: extend_step_forward(
        params, t, start, kp, vp, tables, cfg))
    out = []
    for start in range(0, _S, window):
        logits, kp, vp, *_ = step(tokens[:, start:start + window],
                                  jnp.full((_B,), start, jnp.int32), kp, vp)
        out.append(logits)
    return jnp.concatenate(out, axis=1), forward(params, tokens, cfg)


def _route_pipeline_stage(cfg, params, tokens):
    """Two stages of one layer each over two microbatches: the schedule's
    loss against the loss of gpt.forward's logits, microbatch by microbatch
    (training's capacity dispatch counts a microbatch's tokens)."""
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ParallelConfig)
    from distributed_llm_training_and_inference_system_tpu.parallel.pipeline import (
        make_pipeline_loss_fn)
    par = ParallelConfig(pipeline_parallel=2, num_microbatches=2,
                         activation_checkpoint="none")
    micro = tokens.reshape(2, _B // 2, _S)
    _, (got, _) = jax.jit(make_pipeline_loss_fn(cfg, par))(
        params, {"tokens": micro})
    losses = [next_token_loss(forward(params, m, cfg, moe_impl="capacity"),
                              m) for m in micro]
    total = sum(n for _, n in losses)
    return got, sum(loss * n for loss, n in losses) / total


def _route_calibration(cfg, params, tokens):
    """The AWQ pass records the RMS of what layer i's q/k/v matmul is
    given: the residual stream after i layers under layer i's attn_norm,
    which gpt.forward returns for the model cut to i layers with that norm
    as its final norm."""
    import dataclasses
    from distributed_llm_training_and_inference_system_tpu.ops.quantization import (
        activation_channel_scales)
    got = activation_channel_scales(params, cfg, tokens)["blocks.q.kernel"]
    want = []
    for i in range(1, cfg.num_layers):
        cut = dict(params, blocks=jax.tree_util.tree_map(
            lambda p: p[:i], params["blocks"]),
            final_norm={"scale": params["blocks"]["attn_norm"]["scale"][i]})
        h = forward(cut, tokens, dataclasses.replace(cfg, num_layers=i),
                    return_hidden=True)
        want.append(jnp.sqrt(jnp.mean(h ** 2, axis=(0, 1))) + 1e-6)
    return got[1:], jnp.stack(want)


_ROUTES = {
    "training": _route_training,
    "cold_prefill": _route_cold_prefill,
    "paged_extend_t1": lambda *a: _paged_extend(*a, window=1),
    "paged_extend_t8": lambda *a: _paged_extend(*a, window=8),
    "pipeline_stage": _route_pipeline_stage,
    "calibration": _route_calibration,
}


@pytest.mark.parametrize("feature", list(_FEATURES))
@pytest.mark.parametrize("route", list(_ROUTES))
def test_every_route_runs_the_one_block(route, feature):
    cfg, params, tokens = _feature_model(feature)
    got, want = _ROUTES[route](cfg, params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
