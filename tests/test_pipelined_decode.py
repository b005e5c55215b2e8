"""Pipelined decode dispatch (ServeConfig.pipelined_decode).

One un-fetched K-step dispatch stays in flight; the next chains on its
device-resident scan carry, overlapping the per-dispatch host round trip
with device execution. The bars: BITWISE-identical output to the
unpipelined engine (same per-step program, same PRNG fold) across greedy
and seeded-sampled batches, correct behavior when requests finish
mid-chain (snapshot masking), when arrivals force a chain break
(admission + prefill), and under preemption pressure.
"""

import jax
import numpy as np
import pytest

from distributed_llm_training_and_inference_system_tpu.config import get_model_config
from distributed_llm_training_and_inference_system_tpu.config.schema import ServeConfig
from distributed_llm_training_and_inference_system_tpu.models import init
from distributed_llm_training_and_inference_system_tpu.serve import (
    InferenceEngine,
    SamplingParams,
)


@pytest.fixture(scope="module")
def model_cfg():
    return get_model_config("gpt-test")


@pytest.fixture(scope="module")
def params(model_cfg):
    return init(model_cfg, jax.random.PRNGKey(0))


def make_engine(model_cfg, params, pipelined, **overrides):
    kw = dict(model="gpt-test", max_batch_size=4, max_seq_len=128,
              prefill_chunk=32, kv_block_size=8, dtype="float32",
              pipelined_decode=pipelined)
    kw.update(overrides)
    return InferenceEngine(model_cfg, ServeConfig(**kw), params=params,
                           seed=0)


PROMPTS = [[5, 17, 99, 3, 42, 7, 23],
           [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11],
           [7, 8, 9, 10] * 4,
           [101, 55, 3]]


def _tokens(reqs):
    return [list(r.generated_tokens) for r in reqs]


class TestPipelinedEquivalence:
    def test_greedy_bitwise_identical(self, model_cfg, params):
        sp = SamplingParams(temperature=0.0, max_tokens=24)
        ref = _tokens(make_engine(model_cfg, params, False)
                      .generate(PROMPTS, sp))
        got = _tokens(make_engine(model_cfg, params, True)
                      .generate(PROMPTS, sp))
        assert got == ref

    def test_seeded_sampling_bitwise_identical(self, model_cfg, params):
        sp = SamplingParams(temperature=0.9, top_k=50, top_p=0.95,
                            max_tokens=16, seed=1234)
        ref = _tokens(make_engine(model_cfg, params, False)
                      .generate(PROMPTS, sp))
        got = _tokens(make_engine(model_cfg, params, True)
                      .generate(PROMPTS, sp))
        assert got == ref

    def test_staggered_finishes_mid_chain(self, model_cfg, params):
        """Different max_tokens per request: finishes land mid-chain and
        the snapshot masking must drop exactly the dead rows."""
        eng_p = make_engine(model_cfg, params, True)
        eng_r = make_engine(model_cfg, params, False)
        sps = [SamplingParams(temperature=0.0, max_tokens=5 + 7 * i)
               for i in range(len(PROMPTS))]
        from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
            Request)
        outs = []
        for eng in (eng_p, eng_r):
            reqs = [Request(request_id=f"r{i}", prompt_tokens=list(p),
                            sampling=sps[i])
                    for i, p in enumerate(PROMPTS)]
            for r in reqs:
                assert eng.scheduler.add_request(r)
            eng.run_until_idle()
            outs.append(_tokens(reqs))
            for i, r in enumerate(reqs):
                assert len(r.generated_tokens) == 5 + 7 * i
        assert outs[0] == outs[1]

    def test_arrivals_break_chain_and_match(self, model_cfg, params):
        """New requests admitted while a chain is in flight: prefill
        forces a drain; output still matches the unpipelined engine."""
        from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
            Request)
        outs = []
        for pipelined in (True, False):
            eng = make_engine(model_cfg, params, pipelined)
            sp = SamplingParams(temperature=0.0, max_tokens=12)
            first = [Request(request_id=f"a{i}", prompt_tokens=list(p),
                             sampling=sp)
                     for i, p in enumerate(PROMPTS[:2])]
            for r in first:
                assert eng.scheduler.add_request(r)
            # a few steps: chain forms (2 of 4 slots = gate threshold)
            for _ in range(3):
                eng.step()
            late = [Request(request_id=f"b{i}", prompt_tokens=list(p),
                            sampling=sp)
                    for i, p in enumerate(PROMPTS[2:])]
            for r in late:
                assert eng.scheduler.add_request(r)
            eng.run_until_idle()
            outs.append(_tokens(first + late))
        assert outs[0] == outs[1]

    def test_preemption_pressure_with_pipelining(self, model_cfg, params):
        """Tiny page pool: ensure-capacity preempts while dispatches are
        chained; streams still complete and match the roomy engine."""
        sp = SamplingParams(temperature=0.0, max_tokens=10)
        roomy = _tokens(make_engine(model_cfg, params, False)
                        .generate(PROMPTS, sp))
        tight = make_engine(model_cfg, params, True, kv_num_blocks=14,
                            admission="ondemand")
        got = _tokens(tight.generate(PROMPTS, sp))
        assert got == roomy
        assert all(len(t) == 10 for t in got)


class TestPipelinedWithSpeculation:
    def test_sampled_then_greedy_drains_before_spec(self, model_cfg,
                                                    params):
        """An all-sampled batch can set a pending pipelined dispatch; when
        a greedy arrival later engages the speculative path, the engine
        must drain first (spec builds drafts from HOST state, which is K
        tokens stale while a dispatch is pending). Output must match the
        unpipelined speculative engine."""
        from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
            Request)
        outs = []
        for pipelined in (True, False):
            eng = make_engine(model_cfg, params, pipelined,
                              speculative="ngram", speculative_tokens=4)
            sampled = [Request(request_id=f"s{i}", prompt_tokens=list(p),
                               sampling=SamplingParams(
                                   temperature=0.8, max_tokens=20, seed=7))
                       for i, p in enumerate(PROMPTS[:2])]
            for r in sampled:
                assert eng.scheduler.add_request(r)
            for _ in range(3):   # all-sampled: spec skipped, chain can form
                eng.step()
            greedy = Request(request_id="g", prompt_tokens=PROMPTS[2],
                             sampling=SamplingParams(temperature=0.0,
                                                     max_tokens=16))
            assert eng.scheduler.add_request(greedy)
            eng.run_until_idle()
            outs.append(_tokens(sampled + [greedy]))
            # the slot-step ledger across plain, chained and speculative
            # dispatches: every slot of every step in one class, and every
            # token of the replies credited (a verify window's step counts
            # once, however many tokens it emitted)
            eng._drain_pending()
            stats = eng.stats()
            ledger = stats["slot_steps"]
            assert stats["spec_dispatches"] > 0
            assert sum(ledger[k] for k in ("useful", "overrun", "prompt_wait",
                                           "empty")) == (
                stats["decode_steps"] * eng.serve_cfg.max_batch_size)
            assert ledger["tokens_credited"] == sum(map(len, outs[-1]))
            assert ledger["first_tokens"] == 3
            assert 0 < ledger["useful"] <= ledger["tokens_credited"] - 3
        assert outs[0] == outs[1]


class TestPipelinedComposition:
    def test_pipelined_int8_artifact_prefix_cache(self, model_cfg, params,
                                                  tmp_path):
        """The round-4 stack composed: pre-quantized int8 artifact +
        prefix caching + pipelined dispatch — tokens identical to the
        plain unpipelined in-memory engine with in-process quant."""
        from distributed_llm_training_and_inference_system_tpu.io.export import (
            export_params)
        art = export_params(params, tmp_path / "w8.safetensors",
                            quant="int8")
        sp = SamplingParams(temperature=0.0, max_tokens=16)
        shared = [9, 8, 7, 6, 5, 4, 3, 2]
        prompts = [shared + [i] for i in range(4)]   # shared prefix
        ref_eng = make_engine(model_cfg, params, False,
                              quantization="int8")
        ref = _tokens(ref_eng.generate(prompts, sp))
        eng = InferenceEngine(model_cfg, ServeConfig(
            model="gpt-test", max_batch_size=4, max_seq_len=128,
            prefill_chunk=32, kv_block_size=8, dtype="float32",
            artifact=str(tmp_path / "w8.safetensors"),
            prefix_caching=True, pipelined_decode=True), seed=0)
        got = _tokens(eng.generate(prompts, sp))
        assert got == ref
        assert eng.quantization == "int8"


class TestPipelinedMachinery:
    def test_chain_actually_forms(self, model_cfg, params):
        """At full occupancy the engine must hold a pending dispatch."""
        from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
            Request)
        eng = make_engine(model_cfg, params, True)
        sp = SamplingParams(temperature=0.0, max_tokens=40)
        reqs = [Request(request_id=f"r{i}", prompt_tokens=list(p),
                        sampling=sp) for i, p in enumerate(PROMPTS)]
        for r in reqs:
            assert eng.scheduler.add_request(r)
        eng.step()            # prefill (chain can't form yet)
        eng.step()
        eng.step()
        assert eng._pending is not None, "no chain under full occupancy"
        eng.run_until_idle()
        assert all(len(r.generated_tokens) == 40 for r in reqs)

    def test_unpipelined_never_pends(self, model_cfg, params):
        eng = make_engine(model_cfg, params, False)
        eng.generate(PROMPTS, SamplingParams(temperature=0.0,
                                             max_tokens=12))
        assert eng._pending is None

    def test_occupancy_gate_blocks_light_load(self, model_cfg, params):
        """One resident stream out of 4 slots: the gate must keep the
        engine on the unpipelined path (no pending dispatch)."""
        from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
            Request)
        eng = make_engine(model_cfg, params, True)
        r = Request(request_id="solo", prompt_tokens=PROMPTS[0],
                    sampling=SamplingParams(temperature=0.0,
                                            max_tokens=30))
        assert eng.scheduler.add_request(r)
        for _ in range(4):
            eng.step()
            assert eng._pending is None
        eng.run_until_idle()
        assert len(r.generated_tokens) == 30
