"""Speculative decoding + multi-token paged forward tests.

The load-bearing property: speculation must be invisible in the output —
greedy generations are bit-identical with speculation on or off (the
acceptance rule is draft == argmax, so draft quality only affects speed).
The reference has no speculation (one token per forward per request,
reference serve/server.py:199-249).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_training_and_inference_system_tpu.config import get_model_config
from distributed_llm_training_and_inference_system_tpu.config.schema import ServeConfig
from distributed_llm_training_and_inference_system_tpu.models import gpt
from distributed_llm_training_and_inference_system_tpu.serve import (
    InferenceEngine,
    SamplingParams,
)
from distributed_llm_training_and_inference_system_tpu.serve.decode import (
    extend_step_forward,
)
from distributed_llm_training_and_inference_system_tpu.serve.speculative import (
    propose_ngram_draft,
)


@pytest.fixture(scope="module")
def model_cfg():
    return get_model_config("gpt-test")


def make_engine(model_cfg, **overrides) -> InferenceEngine:
    kw = dict(model="gpt-test", max_batch_size=4, max_seq_len=128,
              prefill_chunk=32, kv_block_size=8, dtype="float32")
    kw.update(overrides)
    return InferenceEngine(model_cfg, ServeConfig(**kw), seed=0)


def greedy_reference(params, cfg, prompt, n_new):
    tokens = list(prompt)
    for _ in range(n_new):
        logits = gpt.forward(params, jnp.asarray([tokens], jnp.int32), cfg)
        tokens.append(int(jnp.argmax(logits[0, -1])))
    return tokens[len(prompt):]


class TestNgramProposer:
    def test_finds_following_tokens(self):
        ctx = np.array([1, 2, 3, 9, 9, 1, 2, 3], np.int32)
        draft = propose_ngram_draft(ctx, 2, max_ngram=3)
        # trailing [1,2,3] matched at position 0 -> followed by [9, 9]
        assert draft is not None and list(draft) == [9, 9]

    def test_prefers_longest_ngram_and_latest_match(self):
        ctx = np.array([5, 1, 2, 7, 0, 1, 2, 8, 3, 1, 2], np.int32)
        draft = propose_ngram_draft(ctx, 1, max_ngram=3)
        # trailing 2-gram [1,2] latest earlier occurrence at 5..6 -> next 8
        assert draft is not None and list(draft) == [8]

    def test_no_match_returns_none(self):
        assert propose_ngram_draft(
            np.array([1, 2, 3, 4], np.int32), 3) is None
        assert propose_ngram_draft(np.array([7], np.int32), 3) is None

    def test_pads_short_draft(self):
        ctx = np.array([4, 5, 4, 5], np.int32)
        draft = propose_ngram_draft(ctx, 4, max_ngram=2)
        assert draft is not None and len(draft) == 4


class TestExtendForward:
    """extend_step_forward == the dense causal forward, via pages."""

    def _pages(self, cfg, n_pages=8, page_size=8, dtype=jnp.float32):
        shape = (cfg.num_layers, n_pages, cfg.num_kv_heads, page_size,
                 cfg.head_dim)
        return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)

    def test_from_scratch_matches_dense(self, model_cfg):
        cfg = model_cfg
        params = gpt.init(cfg, jax.random.PRNGKey(0))
        tokens = jnp.asarray([[5, 17, 99, 3, 42, 7, 23, 11]], jnp.int32)
        T = tokens.shape[1]
        kp, vp = self._pages(cfg)
        tables = jnp.asarray([[1, 2, 0, 0]], jnp.int32)  # page 0 = scratch
        logits, kp, vp, *_ = extend_step_forward(
            params, tokens, jnp.zeros((1,), jnp.int32), kp, vp, tables, cfg)
        dense = gpt.forward(params, tokens, cfg)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(dense),
                                   rtol=2e-4, atol=2e-4)

    def test_split_extend_matches_dense(self, model_cfg):
        """Suffix extend over a cached paged prefix == dense forward tail —
        the cached-prefix prefill path."""
        cfg = model_cfg
        params = gpt.init(cfg, jax.random.PRNGKey(1))
        full = jnp.asarray([[5, 17, 99, 3, 42, 7, 23, 11, 250, 9]], jnp.int32)
        n0 = 6
        kp, vp = self._pages(cfg)
        tables = jnp.asarray([[1, 2, 0, 0]], jnp.int32)
        _, kp, vp, *_ = extend_step_forward(
            params, full[:, :n0], jnp.zeros((1,), jnp.int32), kp, vp,
            tables, cfg)
        logits_tail, kp, vp, *_ = extend_step_forward(
            params, full[:, n0:], jnp.full((1,), n0, jnp.int32), kp, vp,
            tables, cfg)
        dense = gpt.forward(params, full, cfg)
        np.testing.assert_allclose(np.asarray(logits_tail),
                                   np.asarray(dense[:, n0:]),
                                   rtol=2e-4, atol=2e-4)

    def test_write_mask_protects_pages(self, model_cfg):
        """Tokens past write_ok must land in scratch page 0, not real pages."""
        cfg = model_cfg
        params = gpt.init(cfg, jax.random.PRNGKey(2))
        tokens = jnp.asarray([[5, 17, 99, 3]], jnp.int32)
        kp, vp = self._pages(cfg)
        tables = jnp.asarray([[1, 0, 0, 0]], jnp.int32)
        write_ok = jnp.asarray([[True, True, False, False]])
        _, kp2, *_ = extend_step_forward(
            params, tokens, jnp.zeros((1,), jnp.int32), kp, vp, tables, cfg,
            write_ok=write_ok)
        page1 = np.asarray(kp2[:, 1])          # [Nkv, PS, D]
        assert np.abs(page1[:, :, 2:4]).sum() == 0.0   # masked offsets empty
        assert np.abs(page1[:, :, :2]).sum() > 0.0     # allowed offsets wrote


class TestSpeculativeEngine:
    PROMPT_REPETITIVE = [7, 8, 9, 10, 7, 8, 9, 10, 7, 8, 9, 10, 7, 8]
    PROMPT_RANDOM = [5, 17, 99, 3, 42, 250, 23]

    def test_greedy_bit_identical_with_speculation(self, model_cfg):
        for prompt in (self.PROMPT_REPETITIVE, self.PROMPT_RANDOM):
            eng = make_engine(model_cfg, speculative="ngram",
                              speculative_tokens=4)
            [req] = eng.generate([prompt], SamplingParams(temperature=0.0,
                                                          max_tokens=10))
            assert req.generated_tokens == greedy_reference(
                eng.params, model_cfg, prompt, 10), f"prompt {prompt}"

    def test_perfect_drafts_fully_accepted(self, model_cfg):
        """Feed the true argmax chain as the draft: every draft must be
        accepted and the bonus token emitted — n_emit == T. This pins the
        speedup mechanism itself (not just output equivalence)."""
        from distributed_llm_training_and_inference_system_tpu.serve.speculative import (
            speculative_verify)
        cfg = model_cfg
        params = gpt.init(cfg, jax.random.PRNGKey(0))
        prompt = self.PROMPT_REPETITIVE
        chain = greedy_reference(params, cfg, prompt, 5)   # [g0..g4]

        n = len(prompt)
        T = 4
        shape = (cfg.num_layers, 8, cfg.num_kv_heads, 8, cfg.head_dim)
        kp, vp = jnp.zeros(shape), jnp.zeros(shape)
        tables = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        _, kp, vp, *_ = extend_step_forward(
            params, jnp.asarray([prompt], jnp.int32),
            jnp.zeros((1,), jnp.int32), kp, vp, tables, cfg)

        tokens = jnp.asarray([[chain[0], chain[1], chain[2], chain[3]]],
                             jnp.int32)
        emitted, n_emit = speculative_verify(
            params, tokens, jnp.asarray([n], jnp.int32), kp, vp, tables,
            jnp.asarray([n + 64], jnp.int32),
            jnp.asarray(np.asarray(jax.random.key_data(
                jax.random.PRNGKey(0)))[None], jnp.uint32),
            jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.float32), cfg).sampled
        assert int(n_emit[0]) == T
        assert [int(t) for t in np.asarray(emitted[0])] == chain[1:1 + T]

    def test_engine_spec_stats_consistent(self, model_cfg):
        eng = make_engine(model_cfg, speculative="ngram",
                          speculative_tokens=4)
        [req] = eng.generate([self.PROMPT_REPETITIVE],
                             SamplingParams(temperature=0.0, max_tokens=12))
        s = eng.stats()
        assert len(req.generated_tokens) == 12
        assert s["spec_dispatches"] > 0
        assert 0 <= s["spec_accepted"] <= s["spec_drafts"]
        # prefill emits 1 token; every dispatch emits at least 1 more
        assert s["spec_dispatches"] <= 11

    def test_sampled_requests_match_nonspec_engine(self, model_cfg):
        """temperature>0 rows use the plain sampling path inside the verify
        program — same key folding as decode, so outputs are bit-identical
        to a non-speculative engine with the same seed."""
        sp = SamplingParams(temperature=0.8, top_k=20, max_tokens=8, seed=123)
        out = []
        for spec in ("off", "ngram"):
            eng = make_engine(model_cfg, speculative=spec,
                              speculative_tokens=4)
            [req] = eng.generate([self.PROMPT_RANDOM], sp)
            out.append(req.generated_tokens)
        assert out[0] == out[1]

    def test_mixed_greedy_and_sampled_batch(self, model_cfg):
        """A greedy and a sampled request resident together: the greedy one
        must still match the dense reference; the sampled one must match
        its non-speculative twin (same seed)."""
        from distributed_llm_training_and_inference_system_tpu.serve import Request
        greedy_sp = SamplingParams(temperature=0.0, max_tokens=8)
        sampled_sp = SamplingParams(temperature=0.9, max_tokens=8, seed=7)

        def run(spec):
            eng = make_engine(model_cfg, speculative=spec,
                              speculative_tokens=4)
            reqs = [Request("g", list(self.PROMPT_REPETITIVE), greedy_sp),
                    Request("s", list(self.PROMPT_RANDOM), sampled_sp)]
            for r in reqs:
                assert eng.scheduler.add_request(r)
            eng.run_until_idle()
            return eng, reqs

        eng_on, (g_on, s_on) = run("ngram")
        _, (g_off, s_off) = run("off")
        assert g_on.generated_tokens == greedy_reference(
            eng_on.params, model_cfg, self.PROMPT_REPETITIVE, 8)
        assert g_on.generated_tokens == g_off.generated_tokens
        assert s_on.generated_tokens == s_off.generated_tokens

    def test_max_tokens_respected(self, model_cfg):
        eng = make_engine(model_cfg, speculative="ngram",
                          speculative_tokens=6)
        [req] = eng.generate([self.PROMPT_REPETITIVE],
                             SamplingParams(temperature=0.0, max_tokens=5))
        assert len(req.generated_tokens) == 5
        assert req.finish_reason == "length"
