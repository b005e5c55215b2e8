"""Int4 KV pages + courier-aware speculation tests.

Two bars, both absolute:

- **Layout**: packed nibbles must be BIT-exact through every path that
  touches them — pack/unpack round trips (odd counts included), the
  whole-page merge vs the single-token scatter, extract -> courier ->
  restore. A nibble off by one is wrong KV served silently.
- **Fleet invariance**: an int4-KV engine disturbed by migration,
  prefill->decode handoff, or prefix fetch must emit exactly the tokens
  the UNDISTURBED int4 engine emits (greedy and seeded) — the PR-2..7
  token-identity contract extended to the new page type. (int4 vs fp is
  a QUALITY trade, not an identity: the nibble rounding legitimately
  flips greedy argmaxes at depth — see USER_GUIDE "KV quantization:
  int8 vs int4".)

Plus the courier-aware-speculation half: SpecState units (EWMA window
adaptation, clamped deserialization) and the engine-backed assertion
that a sequence re-placed mid-speculation resumes at its migrated
window instead of a cold proposer.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serving_support as support
from distributed_llm_training_and_inference_system_tpu.config import get_model_config
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ConfigError,
    FleetConfig,
    ServeConfig,
)
from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
    Int4Pages,
    QuantPages,
    paged_attention,
    paged_attention_multi,
    quantize_kv_token_int4,
    write_token_to_pages,
    write_window_to_pages,
)
from distributed_llm_training_and_inference_system_tpu.ops.quantization import (
    dequantize_int4_rows,
    pack_int4_rows,
    quantize_int4_rows,
    unpack_int4_rows,
)
from distributed_llm_training_and_inference_system_tpu.serve import (
    InferenceEngine,
    SamplingParams,
)
from distributed_llm_training_and_inference_system_tpu.serve.fleet import (
    FaultPlan,
    ServeFleet,
)
from distributed_llm_training_and_inference_system_tpu.serve.kv_cache import (
    PagedKVCache,
)
from distributed_llm_training_and_inference_system_tpu.serve.speculative import (
    SPEC_MIN_WINDOW,
    SPEC_WARMUP_DISPATCHES,
    SpecState,
)


@pytest.fixture(scope="module")
def model_cfg():
    return get_model_config("gpt-test")


@pytest.fixture(scope="module")
def params(model_cfg):
    return support.params_of(model_cfg)


# -- pack/unpack bitwise units ------------------------------------------------


class TestPackUnpack:
    def test_round_trip_even(self):
        rng = np.random.default_rng(0)
        q = rng.integers(-8, 8, (3, 4, 8, 16)).astype(np.int8)
        packed = pack_int4_rows(jnp.asarray(q), axis=-2)
        assert packed.shape == (3, 4, 4, 16) and packed.dtype == jnp.uint8
        back = unpack_int4_rows(packed, axis=-2)
        np.testing.assert_array_equal(np.asarray(back), q)

    def test_round_trip_odd_count_pads_then_trims(self):
        """An odd page-slot count pads one zero row; unpack with n trims
        it so callers never see the pad."""
        rng = np.random.default_rng(1)
        q = rng.integers(-8, 8, (2, 7, 5)).astype(np.int8)
        packed = pack_int4_rows(jnp.asarray(q), axis=1)
        assert packed.shape == (2, 4, 5)
        back = unpack_int4_rows(packed, axis=1, n=7)
        np.testing.assert_array_equal(np.asarray(back), q)
        # untrimmed unpack exposes the zero pad row
        full = np.asarray(unpack_int4_rows(packed, axis=1))
        assert full.shape == (2, 8, 5)
        np.testing.assert_array_equal(full[:, 7], 0)

    def test_nibble_layout_low_is_even_slot(self):
        """Byte layout is load-bearing (the Pallas body and the write
        path must agree): element 2i -> low nibble, 2i+1 -> high."""
        q = jnp.asarray([[3], [-2]], jnp.int8)          # slots 0, 1
        packed = np.asarray(pack_int4_rows(q, axis=0))
        assert packed.shape == (1, 1)
        assert packed[0, 0] == (3 | ((-2 & 0xF) << 4))

    def test_quantize_int4_rows_range_and_scale(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 16))
        q, scale = quantize_int4_rows(x)
        assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
        assert int(jnp.max(q)) <= 7 and int(jnp.min(q)) >= -7
        np.testing.assert_allclose(
            np.asarray(q * scale[..., None]), np.asarray(x),
            atol=np.abs(np.asarray(x)).max() / 7)

    def test_dequantize_matches_manual(self):
        rng = np.random.default_rng(2)
        q = rng.integers(-7, 8, (2, 8, 16)).astype(np.int8)
        scale = rng.random((2, 8)).astype(np.float32) + 0.1
        packed = pack_int4_rows(jnp.asarray(q), axis=-2)
        out = dequantize_int4_rows(packed, jnp.asarray(scale))
        np.testing.assert_allclose(np.asarray(out),
                                   q * scale[..., None], rtol=1e-6)


# -- Int4Pages ops ------------------------------------------------------------


def _zero_pages(NP, Nkv, PS, D):
    return Int4Pages(jnp.zeros((NP, Nkv, PS // 2, D), jnp.uint8),
                     jnp.zeros((NP, Nkv, PS), jnp.float32))


class TestInt4PagesOps:
    def test_logical_shape_reported(self):
        pages = _zero_pages(6, 4, 8, 32)
        assert pages.shape == (6, 4, 8, 32)
        assert pages.values.shape == (6, 4, 4, 32)
        assert isinstance(pages, QuantPages)   # dispatch subtype contract

    def test_write_then_read_roundtrip(self):
        NP, Nkv, PS, D = 6, 4, 8, 32
        pages = _zero_pages(NP, Nkv, PS, D)
        kv = jax.random.normal(jax.random.PRNGKey(0), (2, Nkv, D))
        tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        positions = jnp.asarray([3, 9], jnp.int32)
        pages = write_token_to_pages(pages, kv, tables, positions)
        deq = pages.dequant()
        np.testing.assert_allclose(np.asarray(deq[1, :, 3]),
                                   np.asarray(kv[0]), rtol=0.2, atol=0.2)
        np.testing.assert_allclose(np.asarray(deq[4, :, 1]),
                                   np.asarray(kv[1]), rtol=0.2, atol=0.2)

    def test_single_token_write_preserves_sibling_nibble(self):
        """Two page slots share a byte: writing slot 3 must not disturb
        slot 2's nibble (bit-compared, not dequant-compared)."""
        NP, Nkv, PS, D = 4, 2, 8, 16
        pages = _zero_pages(NP, Nkv, PS, D)
        tables = jnp.asarray([[1]], jnp.int32)
        kv0 = jax.random.normal(jax.random.PRNGKey(1), (1, Nkv, D))
        pages = write_token_to_pages(pages, kv0, tables,
                                     jnp.asarray([2], jnp.int32))
        before = np.asarray(pages.values).copy()
        kv1 = jax.random.normal(jax.random.PRNGKey(2), (1, Nkv, D))
        pages = write_token_to_pages(pages, kv1, tables,
                                     jnp.asarray([3], jnp.int32))
        after = np.asarray(pages.values)
        # slots 2 and 3 share byte column 1: low nibble (slot 2) kept
        np.testing.assert_array_equal(after[1, :, 1] & 0x0F,
                                      before[1, :, 1] & 0x0F)

    def test_window_merge_bit_identical_to_scatter(self):
        """The whole-page merge and the per-token scatter must produce
        BIT-identical packed bytes and scales — the same invariant the
        int8 path holds (tests/test_kv_quant.py), now through the
        unpack->merge->repack cycle."""
        NP, Nkv, PS, D = 8, 2, 8, 16
        B, T = 2, 4
        base = _zero_pages(NP, Nkv, PS, D)
        # pre-fill some staging content so the merge must preserve rows
        pre = jax.random.normal(jax.random.PRNGKey(3), (B, Nkv, D))
        tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        base = write_token_to_pages(base, pre, tables,
                                    jnp.asarray([5, 13], jnp.int32))
        new_kv = jax.random.normal(jax.random.PRNGKey(4), (B, T, Nkv, D))
        # slot 0 crosses its page edge (6..9 spans pages 0->1); slot 1
        # stays inside page 1; one token masked off in both paths
        starts = jnp.asarray([6, 10], jnp.int32)
        ok = jnp.asarray([[True, True, True, True],
                          [True, True, False, True]])
        merged = write_window_to_pages(base, new_kv, tables, starts, ok)
        scattered = base
        for j in range(T):
            scattered = write_token_to_pages(
                scattered, new_kv[:, j], tables, starts + j,
                active=ok[:, j])
        # page 0 is reserved scratch — masked writes land there and its
        # content is documented garbage; every REAL page must match bit
        # for bit
        np.testing.assert_array_equal(np.asarray(merged.values)[1:],
                                      np.asarray(scattered.values)[1:])
        np.testing.assert_array_equal(np.asarray(merged.scale)[1:],
                                      np.asarray(scattered.scale)[1:])

    @pytest.mark.parametrize("impl", ["gather", "pallas"])
    def test_attention_close_to_fp_cache(self, impl):
        """Paged attention over int4 pages vs the SAME values in fp
        pages: within the int4 round-trip tolerance (both impls)."""
        B, Nq, Nkv, D, PS, NP, maxP = 2, 8, 4, 128, 8, 10, 3
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (B, Nq, D), jnp.float32)
        kf = jax.random.normal(ks[1], (NP, Nkv, PS, D), jnp.float32)
        vf = jax.random.normal(ks[2], (NP, Nkv, PS, D), jnp.float32)
        qk, sk = quantize_int4_rows(kf)
        qv, sv = quantize_int4_rows(vf)
        kq = Int4Pages(pack_int4_rows(qk, axis=-2), sk)
        vq = Int4Pages(pack_int4_rows(qv, axis=-2), sv)
        tables = jnp.arange(1, 1 + B * maxP, dtype=jnp.int32).reshape(
            B, maxP)
        lengths = jnp.asarray([PS * maxP, PS * 2 - 3], jnp.int32)
        ref = paged_attention(q, kf, vf, tables, lengths, impl="gather")
        out = paged_attention(q, kq, vq, tables, lengths, impl=impl)
        # ~3 bits of mantissa: the nibble round-trip error is ~10x the
        # int8 case (values in [-7, 7] vs [-127, 127])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0.3, atol=0.3)

    def test_multi_query_pallas_matches_gather(self):
        """The head-folded Pallas extend kernel (interpret mode) over
        packed int4 tiles vs the gather fallback: same dequant math,
        near-identical output."""
        B, T, Nq, Nkv, D, PS, maxP = 2, 4, 4, 2, 128, 8, 3
        NP = B * maxP + 1
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (B, T, Nq, D), jnp.float32)
        kf = jax.random.normal(ks[1], (NP, Nkv, PS, D), jnp.float32)
        vf = jax.random.normal(ks[2], (NP, Nkv, PS, D), jnp.float32)
        qk, sk = quantize_int4_rows(kf)
        qv, sv = quantize_int4_rows(vf)
        kq = Int4Pages(pack_int4_rows(qk, axis=-2), sk)
        vq = Int4Pages(pack_int4_rows(qv, axis=-2), sv)
        tables = jnp.arange(1, NP, dtype=jnp.int32).reshape(B, maxP)
        starts = jnp.asarray([5, 11], jnp.int32)
        ref = paged_attention_multi(q, kq, vq, tables, starts,
                                    impl="gather")
        from distributed_llm_training_and_inference_system_tpu.ops.paged_attention_pallas import (  # noqa: E501
            paged_attention_pallas_multi)
        out = paged_attention_pallas_multi(q, kq, vq, tables, starts,
                                           interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_quantize_kv_token_int4_shared_math(self):
        x = jax.random.normal(jax.random.PRNGKey(5), (3, 4, 16))
        q1, s1 = quantize_kv_token_int4(x)
        q2, s2 = quantize_int4_rows(x)
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


# -- cache pool + payload validation -----------------------------------------


class TestInt4Cache:
    def test_pool_autosize_doubles_int8(self, model_cfg):
        def pool(kind):
            # budget small enough that the slots*pages cap never clips
            return PagedKVCache(model_cfg, num_slots=64, max_seq_len=4096,
                                page_size=16, hbm_budget_gb=0.01,
                                quantized=kind).num_pages
        n8, n4 = pool("int8"), pool("int4")
        # row bytes (D + 4 scale) vs (D/2 + 4): ~2x at D=128, less at
        # the test model's tiny head_dim — assert the exact layout ratio
        D = model_cfg.head_dim
        assert n4 / n8 == pytest.approx((D + 4) / (D // 2 + 4), rel=0.05)
        # and the production-relevant claim at D=128: >= 1.9x
        assert (128 + 4) / (128 // 2 + 4) >= 1.9

    def test_odd_page_size_rejected(self, model_cfg):
        with pytest.raises(ValueError, match="must be even"):
            PagedKVCache(model_cfg, num_slots=2, max_seq_len=64,
                         page_size=7, quantized="int4")
        with pytest.raises(ConfigError, match="must be even"):
            ServeConfig(model="gpt-test", kv_block_size=7,
                        kv_quantization="int4").validate()

    def test_unknown_kind_rejected(self, model_cfg):
        with pytest.raises(ValueError, match="unknown KV quantization"):
            PagedKVCache(model_cfg, num_slots=2, max_seq_len=64,
                         quantized="int2")

    def test_extract_restore_bit_exact(self, model_cfg):
        """write_slot_pages -> extract_slot_pages round-trips arbitrary
        packed bytes and scales exactly (the migration/restore path must
        never renormalize a nibble)."""
        kv = PagedKVCache(model_cfg, num_slots=2, max_seq_len=64,
                          page_size=8, num_pages=12, quantized="int4")
        kv.allocate(0, 24)
        L, Nkv, PS, D = (model_cfg.num_layers, model_cfg.num_kv_heads,
                         8, model_cfg.head_dim)
        rng = np.random.default_rng(7)

        def part():
            return {"values": rng.integers(0, 256, (L, 3, Nkv, PS // 2,
                                                    D)).astype(np.uint8),
                    "scale": rng.random((L, 3, Nkv, PS))
                    .astype(np.float32)}
        payload = {"k": part(), "v": part(), "num_pages": 3}
        kv.write_slot_pages(0, payload)
        back = kv.extract_slot_pages(0, 0, 3)
        for name in ("k", "v"):
            np.testing.assert_array_equal(payload[name]["values"],
                                          back[name]["values"])
            np.testing.assert_array_equal(payload[name]["scale"],
                                          back[name]["scale"])
        assert back["k"]["values"].dtype == np.uint8

    def test_wrong_width_payload_rejected(self, model_cfg):
        """An int8 payload must not scatter into an int4 pool (dtype
        guard): same logical shape family, very different bytes."""
        kv8 = PagedKVCache(model_cfg, num_slots=2, max_seq_len=64,
                           page_size=8, num_pages=12, quantized="int8")
        kv8.allocate(0, 16)
        payload = kv8.extract_slot_pages(0, 0, 2)
        kv4 = PagedKVCache(model_cfg, num_slots=2, max_seq_len=64,
                           page_size=8, num_pages=12, quantized="int4")
        kv4.allocate(0, 16)
        with pytest.raises(ValueError):
            kv4.write_slot_pages(0, payload)
        # and the mirror image: int4 payload into an int8 pool
        p4 = kv4.extract_slot_pages(0, 0, 2)
        with pytest.raises(ValueError):
            kv8.write_slot_pages(0, p4)


# -- engine-backed fleet invariance ------------------------------------------


def _fleet_cfg(**kw):
    base = dict(replicas=2, affinity_prefix_tokens=0,
                restart_backoff_s=0.05, probe_interval_s=0.05)
    base.update(kw)
    return FleetConfig(**base)


def _serve_cfg(**kw):
    # two slots a replica: the four prompts queue, so a drain or a handoff
    # finds requests waiting as well as running
    return support.serve_config("gpt-test", kv_quantization="int4",
                                max_batch_size=2, **kw)


PROMPTS = [[3, 1, 4, 1, 5, 9], [2, 7, 1, 8, 2], [6, 1, 8, 0],
           [35, 8, 9, 7, 9, 3]]

CHAOS = dict(courier_chunk_bytes=1024, courier_max_retries=12,
             courier_retry_backoff_ms=0.2,
             courier_retry_backoff_max_ms=2.0,
             courier_chunk_deadline_ms=20.0)

CHAOS_PLAN = dict(chunk_drop_rate=0.2, chunk_corrupt_rate=0.15,
                  chunk_delay_rate=0.1, chunk_delay_ms=30.0,
                  chunk_duplicate_rate=0.1)


def _ref_tokens(model_cfg, params, sampling, **serve_kw):
    eng = InferenceEngine(model_cfg, _serve_cfg(**serve_kw),
                          params=params, seed=0)
    out = [r.generated_tokens for r in eng.generate(PROMPTS, sampling)]
    eng.release()
    return out


def _warm(fleet):
    for rep in fleet.replicas:
        rep.engine.generate([[1, 2, 3]],
                            SamplingParams(temperature=0.0, max_tokens=4))
        rep.engine.total_prefill_tokens = 0
        rep.engine.total_unexpected_prefills = 0
    fleet.start()


class TestInt4FleetIdentity:
    @pytest.mark.parametrize(
        "sampling",
        [SamplingParams(temperature=0.0, max_tokens=112),
         SamplingParams(temperature=0.8, seed=123, max_tokens=112)],
        ids=["greedy", "seeded"])
    def test_drain_migration_chunk_chaos(self, model_cfg, params,
                                         sampling):
        """Mid-decode drain moves int4 payloads over the chaotic courier:
        zero re-prefill, token-identical to the undisturbed int4 engine,
        no aborts. (112 new tokens, 14 dispatches: at 40 the requests
        were done ~35 ms after their second token, and a main thread
        held up that long drained a replica with nothing to migrate.)"""
        ref = _ref_tokens(model_cfg, params, sampling)
        fleet = ServeFleet(
            model_cfg, _serve_cfg(),
            _fleet_cfg(migrate_on_drain=True, **CHAOS), params=params,
            supervise=False, seed=0,
            fault_plan=FaultPlan(seed=5, slow_replica=0, slow_ms=3.0,
                                 **CHAOS_PLAN))
        _warm(fleet)
        try:
            deadline = time.monotonic() + 300
            evs, reqs = [], []
            for p in PROMPTS:
                ev = threading.Event()
                reqs.append(fleet.submit(
                    p, sampling, on_complete=lambda _r, ev=ev: ev.set()))
                evs.append(ev)
            while not all(len(r.generated_tokens) >= 2 for r in reqs):
                time.sleep(0.002)
                assert time.monotonic() < deadline, "decode hung"
            pre = sum(rep.engine.total_prefill_tokens
                      for rep in fleet.replicas)
            assert fleet.drain(0)
            while not all(e.is_set() for e in evs):
                fleet.supervisor.poll_once()
                time.sleep(0.005)
                assert time.monotonic() < deadline, "drain hung"
            post = sum(rep.engine.total_prefill_tokens
                       for rep in fleet.replicas)
            snap = fleet.status()
        finally:
            fleet.shutdown()
        assert [r.generated_tokens for r in reqs] == ref, (
            "int4 drain migration diverged from undisturbed engine")
        assert post == pre, "migration re-prefilled"
        assert snap["migration"]["migrations"] >= 1
        assert snap["courier"]["aborts"] == 0

    @pytest.mark.parametrize(
        "sampling",
        [SamplingParams(temperature=0.0, max_tokens=24),
         SamplingParams(temperature=0.8, seed=7, max_tokens=24)],
        ids=["greedy", "seeded"])
    def test_disagg_handoff(self, model_cfg, params, sampling):
        """Every prompt prefills on the prefill replica and decodes on
        the decode replica (zero prefill there) after its packed-int4
        pages cross the handoff courier under chunk chaos."""
        ref = _ref_tokens(model_cfg, params, sampling)
        fleet = ServeFleet(
            model_cfg, _serve_cfg(),
            _fleet_cfg(roles="prefill,decode", **CHAOS), params=params,
            supervise=False, seed=0,
            fault_plan=FaultPlan(seed=6, **CHAOS_PLAN))
        _warm(fleet)
        try:
            reqs = fleet.generate(PROMPTS, sampling, timeout_s=300)
            snap = fleet.status()
            decode_eng = fleet.replicas[1].engine
            decode_prefill = decode_eng.total_prefill_tokens
        finally:
            fleet.shutdown()
        assert [r.generated_tokens for r in reqs] == ref, (
            "int4 handoff diverged from undisturbed engine")
        assert snap["handoff"]["handoffs"] == len(PROMPTS)
        assert decode_prefill == 0, "decode replica dispatched prefill"
        assert snap["courier"]["aborts"] == 0

    def test_prefix_fetch_int4_pages(self, model_cfg, params):
        """Off-affinity spill fetches the shared hot prefix as packed
        int4 pages: prefill shrinks by exactly the fetched coverage and
        output stays token-identical."""
        hot = [7, 3, 9, 1, 4, 8, 2, 6] * 4    # 4 full pages
        prompts = [hot + [50 + i, 60 + i, 70 + i] for i in range(4)]
        sampling = SamplingParams(temperature=0.0, max_tokens=16)
        ref_eng = InferenceEngine(
            model_cfg, _serve_cfg(), params=params, seed=0)
        ref = [r.generated_tokens
               for r in ref_eng.generate(prompts, sampling)]
        ref_eng.release()
        fleet = ServeFleet(
            model_cfg, _serve_cfg(),
            _fleet_cfg(prefix_fetch=True, courier_chunk_bytes=1024),
            params=params, supervise=False, seed=0)
        _warm(fleet)
        try:
            deadline = time.monotonic() + 300
            # warm replica 0 with the hot prefix while 1 is drained
            assert fleet.drain(1)
            while fleet.replicas[1].state != "drained":
                fleet.supervisor.poll_once()
                time.sleep(0.005)
                assert time.monotonic() < deadline
            warm = fleet.generate([prompts[0]], sampling, timeout_s=300)
            assert warm[0].generated_tokens == ref[0]
            fleet.undrain(1)
            assert fleet.drain(0)
            while fleet.replicas[0].state != "drained":
                fleet.supervisor.poll_once()
                time.sleep(0.005)
                assert time.monotonic() < deadline
            pre = fleet.replicas[1].engine.total_prefill_tokens
            got = fleet.generate(prompts[1:], sampling, timeout_s=300)
            eng1 = fleet.replicas[1].engine
            fetched = eng1.total_prefix_fetched_tokens
            spent = eng1.total_prefill_tokens - pre
            snap = fleet.status()
        finally:
            fleet.shutdown()
        assert [r.generated_tokens for r in got] == ref[1:], (
            "int4 prefix-fetch spill diverged")
        assert fetched == len(hot)
        assert spent == sum(len(p) for p in prompts[1:]) - 3 * len(hot)
        assert snap["prefix_fetch"]["aborts"] == 0
        assert snap["prefix_fetch"]["bytes"] > 0


# -- SpecState units ----------------------------------------------------------


class TestSpecState:
    def test_window_grows_on_high_acceptance(self):
        st = SpecState(window=4)
        for _ in range(SPEC_WARMUP_DISPATCHES + 2):
            st.observe(3, 3, max_window=8)
        assert st.window > 4
        assert st.ewma == pytest.approx(1.0)
        assert st.drafts == 3 * (SPEC_WARMUP_DISPATCHES + 2)
        assert st.accepted == st.drafts

    def test_window_shrinks_on_low_acceptance_after_warmup(self):
        st = SpecState(window=8)
        for i in range(SPEC_WARMUP_DISPATCHES - 1):
            st.observe(0, 7, max_window=8)
            assert st.window == 8, "window moved during warmup"
        for _ in range(8):
            st.observe(0, 7, max_window=8)
        assert st.window == SPEC_MIN_WINDOW

    def test_deterministic_across_replicas(self):
        """Same observation stream -> same window, whichever replica
        folds it (the migration invariant)."""
        a, b = SpecState(window=6), SpecState(window=6)
        seq = [(2, 5), (0, 5), (4, 5), (5, 5), (1, 5), (3, 5)]
        for acc, dr in seq:
            a.observe(acc, dr, max_window=8)
            b.observe(acc, dr, max_window=8)
        assert a == b

    def test_round_trip_dict(self):
        st = SpecState(window=5, ewma=0.375, warmup=9, drafts=63,
                       accepted=21)
        assert SpecState.from_dict(st.to_dict(), max_window=8) == st

    def test_from_dict_clamps_malformed(self):
        """A foreign/corrupt dict must clamp, not poison the dispatch
        shapes (the window bounds tokens[] writes)."""
        st = SpecState.from_dict(
            {"window": 99, "ewma": "NaN-ish", "warmup": -3,
             "drafts": None}, max_window=8)
        assert st.window == 8
        assert st.ewma == 0.0 and st.warmup == 0 and st.drafts == 0
        st = SpecState.from_dict({"window": -5, "ewma": 7.0},
                                 max_window=8)
        assert st.window == SPEC_MIN_WINDOW
        assert st.ewma == 1.0
        assert SpecState.from_dict({}, max_window=6).window == 6

    def test_observe_clamps_inputs(self):
        st = SpecState(window=4)
        st.observe(10, 3, max_window=8)      # accepted > drafted clamps
        assert st.accepted == 3 and st.drafts == 3
        st.observe(-2, 0, max_window=8)      # degenerate dispatch
        assert st.accepted == 3 and st.drafts == 4


# -- courier-aware speculation, engine-backed --------------------------------


class TestSpecResume:
    def test_handoff_resumes_spec_state(self, model_cfg, params):
        """Disaggregated serving with speculation: every sequence's
        SpecState crosses the handoff courier and the decode replica
        arms FROM it (total_spec_resumes), token-identical to the
        undisturbed speculative int4 engine."""
        sampling = SamplingParams(temperature=0.0, max_tokens=32)
        spec_kw = dict(speculative="ngram", speculative_tokens=4)
        ref = _ref_tokens(model_cfg, params, sampling, **spec_kw)
        fleet = ServeFleet(
            model_cfg, _serve_cfg(**spec_kw),
            _fleet_cfg(roles="prefill,decode"), params=params,
            supervise=False, seed=0)
        _warm(fleet)
        try:
            reqs = fleet.generate(PROMPTS, sampling, timeout_s=300)
            decode_eng = fleet.replicas[1].engine
            resumes = decode_eng.total_spec_resumes
            dispatches = decode_eng.total_spec_dispatches
            decode_prefill = decode_eng.total_prefill_tokens
            snap = fleet.status()
        finally:
            fleet.shutdown()
        assert [r.generated_tokens for r in reqs] == ref, (
            "speculative int4 handoff diverged")
        assert resumes == len(PROMPTS), (
            f"decode replica cold-started proposers: {resumes} resumes "
            f"for {len(PROMPTS)} handoffs")
        assert dispatches >= 1
        assert decode_prefill == 0
        # the supervisor aggregates the per-replica counters
        assert snap["spec"]["resumes"] == resumes
        assert snap["spec"]["dispatches"] >= dispatches
        rep1 = next(r for r in snap["replicas"] if r["replica"] == 1)
        assert rep1["spec_resumes"] == resumes
        assert 0.0 <= rep1["spec_acceptance"] <= 1.0

    def test_drain_migration_carries_tuned_window(self, model_cfg,
                                                  params, monkeypatch):
        """A sequence migrated MID-speculation arrives with its adapted
        (non-cold) window: the destination's SpecState.from_dict sees
        warmup > 0 and the exact window the source tuned — not the cold
        ServeConfig.speculative_tokens default."""
        sampling = SamplingParams(temperature=0.0, max_tokens=48)
        T = 6
        spec_kw = dict(speculative="ngram", speculative_tokens=T,
                       decode_steps_per_dispatch=2)
        ref = _ref_tokens(model_cfg, params, sampling, **spec_kw)
        seen: list = []
        orig = SpecState.from_dict.__func__

        def spy(cls, d, max_window):
            st = orig(cls, d, max_window)
            seen.append((dict(d), st.window))
            return st
        monkeypatch.setattr(SpecState, "from_dict", classmethod(spy))
        fleet = ServeFleet(
            model_cfg, _serve_cfg(**spec_kw),
            _fleet_cfg(migrate_on_drain=True), params=params,
            supervise=False, seed=0,
            fault_plan=FaultPlan(slow_replica=0, slow_ms=3.0))
        _warm(fleet)
        try:
            deadline = time.monotonic() + 300
            evs, reqs = [], []
            for p in PROMPTS:
                ev = threading.Event()
                reqs.append(fleet.submit(
                    p, sampling, on_complete=lambda _r, ev=ev: ev.set()))
                evs.append(ev)

            def warmed_up():
                eng = fleet.replicas[0].engine
                states = [eng.spec_state_of(s)
                          for s, r in enumerate(eng.scheduler.slots)
                          if r is not None]
                states = [s for s in states if s is not None]
                return states and all(
                    s["warmup"] >= SPEC_WARMUP_DISPATCHES
                    for s in states)
            while not warmed_up():
                time.sleep(0.002)
                assert time.monotonic() < deadline, (
                    "source never warmed its spec windows")
            assert fleet.drain(0)
            while not all(e.is_set() for e in evs):
                fleet.supervisor.poll_once()
                time.sleep(0.005)
                assert time.monotonic() < deadline, "drain hung"
            dest = fleet.replicas[1].engine
            resumes = dest.total_spec_resumes
        finally:
            fleet.shutdown()
        assert [r.generated_tokens for r in reqs] == ref, (
            "mid-speculation migration diverged")
        assert resumes >= 1
        migrated = [d for d, _w in seen if d.get("warmup", 0) > 0]
        assert migrated, f"every resume was a cold proposer: {seen}"
        for d, w in seen:
            want = max(SPEC_MIN_WINDOW, min(int(d.get("window", T)), T))
            assert w == want, (
                f"destination armed window {w}, migrated state said "
                f"{d}")
