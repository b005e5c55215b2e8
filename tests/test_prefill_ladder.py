"""The cold-prefill bucket ladder (PR 30): ``InferenceEngine._bucket`` pads a
prompt to the next of c, 2c, 4c, then two rungs an octave, where c is
``prefill_chunk`` rounded up to a page. The ladder's shape over chunks and
``max_seq_len``s, that the benchmark's warm-up touches every rung the cells'
prompts reach, and that a prompt is served the same through either of two
rungs with ``prefill_padded_tokens`` counting the rows computed."""

import math
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

import serving_support as support
from benchmark.runners.serve import Served
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ServeConfig)
from distributed_llm_training_and_inference_system_tpu.models import gpt
from distributed_llm_training_and_inference_system_tpu.serve import (
    InferenceEngine, Request, SamplingParams)

PAGE = 64
CHUNKS = [64, 256, 512]
MAX_SEQ_LENS = [256, 2048, 8192, 32768]


class Ladder:
    """The engine's two bucket methods over a configuration alone: they read
    ``serve_cfg`` and the page size and nothing else of an engine."""
    _bucket = InferenceEngine._bucket
    _suffix_bucket = InferenceEngine._suffix_bucket

    def __init__(self, chunk: int, max_seq_len: int, page: int = PAGE):
        self.serve_cfg = ServeConfig(prefill_chunk=chunk,
                                     max_seq_len=max_seq_len,
                                     kv_block_size=page)
        self.kv = SimpleNamespace(page_size=page)
        self.c = math.ceil(max(chunk, page) / page) * page
        self.cap = math.ceil(max_seq_len / page) * page
        self.buckets = [self._bucket(n) for n in range(max_seq_len + 1)]
        self.rungs = sorted(set(self.buckets))


@pytest.fixture(scope="module", params=[(c, s) for c in CHUNKS
                                        for s in MAX_SEQ_LENS],
                ids=lambda p: f"chunk{p[0]}-seq{p[1]}")
def ladder(request):
    return Ladder(*request.param)


def test_rungs_are_page_and_chunk_multiples_that_cover_every_length(ladder):
    assert all(r % PAGE == 0 for r in ladder.rungs)
    # the cap alone may fall between two multiples of the chunk
    assert all(r % ladder.c == 0 for r in ladder.rungs if r != ladder.cap)
    assert ladder.rungs[-1] == ladder.cap
    assert all(b >= max(n, 1) for n, b in enumerate(ladder.buckets))
    assert ladder.buckets == sorted(ladder.buckets)
    # the smallest rung at or above n: no rung lies between n and its bucket
    for lo, hi in zip([0] + ladder.rungs, ladder.rungs):
        assert ladder.buckets[lo + 1] == hi == ladder.buckets[hi]


def test_rungs_are_c_2c_4c_then_two_an_octave(ladder):
    want, k = [], 1
    while not want or want[-1] < ladder.cap:
        want.append(min(k * ladder.c, ladder.cap))
        # 1, 2, 4, 6, 8, 12, 16, 24, ...
        k = k * 2 if k < 4 else (k * 3 // 2 if k & (k - 1) == 0 else k * 4 // 3)
    assert ladder.rungs == want


def test_padding_is_under_a_chunk_then_half_then_a_third_of_the_rung(ladder):
    for n, b in enumerate(ladder.buckets[1:], start=1):
        if n <= 2 * ladder.c:
            assert b - n < ladder.c, (n, b)
        elif n <= 4 * ladder.c:
            assert 2 * (b - n) < b, (n, b)
        else:
            assert 3 * (b - n) < b, (n, b)


def test_a_suffix_bucket_never_exceeds_the_cold_bucket(ladder):
    for m in range(1, len(ladder.buckets)):
        s = ladder._suffix_bucket(m)
        assert m <= s <= ladder.buckets[m] and s % PAGE == 0, (m, s)


@pytest.mark.parametrize("max_seq_len,rungs", [
    (2048, [256, 512, 1024, 1536, 2048]),
    (32768, [256, 512, 1024, 1536, 2048, 3072, 4096, 6144, 8192, 12288,
             16384, 24576, 32768]),
])
def test_program_count_at_the_default_chunk(max_seq_len, rungs):
    assert ServeConfig().prefill_chunk == 256
    assert Ladder(ServeConfig().prefill_chunk, max_seq_len).rungs == rungs
    assert len(rungs) == {2048: 5, 32768: 13}[max_seq_len]


@pytest.mark.parametrize("chunk,page,n,want", [
    (256, 64, 600, 1024), (256, 64, 1025, 1536), (256, 64, 5000, 6144),
    (100, 64, 1, 128),            # the chunk is rounded up to a page
    (8, 64, 65, 128),             # a chunk under the page steps by pages
    (256, 64, 0, 256),
])
def test_single_lengths(chunk, page, n, want):
    assert Ladder(chunk, 8192, page)._bucket(n) == want


@pytest.mark.parametrize("traffic", ["chat", "batch-64", "moe-batch-64"])
def test_the_benchmarks_warm_up_touches_every_rung_the_cells_reach(traffic):
    """``benchmark/runners/serve.py warm`` sends one prompt of every length
    ``prefill_buckets`` returns; under the default chunk those lengths must
    land on every rung a prompt of the traffic can reach, or a program
    compiles inside the window."""
    from benchmark import traffic as traffic_mod
    from benchmark.run import ROOT
    spec = traffic_mod.load(
        f"{ROOT}/benchmark/traffic/{traffic}.json")["prompt_tokens"]
    lo, hi = spec["min"], spec["max"]
    assert (lo, hi) == (32, 1024)
    serve_cfg = ServeConfig(max_seq_len=2048, kv_block_size=PAGE)
    sent = Served.prefill_buckets(SimpleNamespace(serve_cfg=serve_cfg), lo, hi)
    ladder = Ladder(serve_cfg.prefill_chunk, serve_cfg.max_seq_len)
    assert sent == [256, 512, 768, 1024]
    assert {ladder.buckets[n] for n in sent} == set(
        ladder.buckets[lo:hi + 1]) == {256, 512, 1024}


# -- one prompt through two rungs, float32 on the CPU ---------------------------
# at the harness's shapes (pages of 8, a chunk of 32): 25 tokens fill three
# pages and a row, the 32-row program pads inside the last page and the
# 64-row program (the one shape this property is about) by four pages more

PROMPT = support.tokens(25, seed=30)


def _last_logits(params, tokens, at, *, cfg):
    logits, _ = gpt.forward(
        params, tokens, cfg,
        kv_cache=gpt.init_kv_cache(cfg, 1, tokens.shape[1],
                                   dtype=jnp.float32),
        cache_offset=jnp.zeros((1,), jnp.int32), unembed_positions=at)
    return logits[0, 0]


def serve_through(model: str, chunk: int) -> dict:
    eng = support.engine(model, prefill_chunk=chunk)
    assert not np.asarray(eng.kv.k_pages).any()
    req = Request("r", PROMPT, SamplingParams(temperature=0.0, max_tokens=32))
    assert eng.scheduler.add_request(req)
    eng.step()                      # the prefill and one decode dispatch
    live = eng.kv.block_tables[req.slot][:eng.kv.pages_needed(len(PROMPT))]
    k, v = np.asarray(eng.kv.k_pages), np.asarray(eng.kv.v_pages)
    untouched = np.setdiff1d(np.arange(k.shape[1]), [0, *live])
    bucket = eng._bucket(len(PROMPT))
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :len(PROMPT)] = PROMPT
    logits = support.program(_last_logits, eng.cfg)(
        eng.params, jnp.asarray(tokens), jnp.asarray([len(PROMPT) - 1]))
    stats = eng.stats()
    return {"tokens": list(req.generated_tokens), "bucket": bucket,
            "live": (k[:, live, :, :], v[:, live, :, :]),
            "scratch_written": bool(k[:, 0].any()),
            "elsewhere_written": bool(k[:, untouched].any()
                                      or v[:, untouched].any()),
            "logits": np.asarray(logits),
            "rows": stats["prefill_padded_tokens"],
            "prefill_tokens": stats["prefill_tokens"]}


@pytest.mark.parametrize("model", ["gpt-test", "olmoe-test"])
def test_a_prompt_is_served_the_same_through_either_rung(model):
    fine, coarse = serve_through(model, 32), serve_through(model, 64)
    assert (fine["bucket"], coarse["bucket"]) == (32, 64)
    assert (fine["rows"], coarse["rows"]) == (32, 64)
    assert fine["prefill_tokens"] == coarse["prefill_tokens"] == len(PROMPT)
    assert len(fine["tokens"]) >= 1 and fine["tokens"] == coarse["tokens"]
    np.testing.assert_allclose(fine["logits"], coarse["logits"], atol=1e-5)
    assert int(fine["logits"].argmax()) == fine["tokens"][0]
    # the prompt's 25 rows (and the decode steps after them) in its pages
    prompt_rows = len(PROMPT) % support.PS
    for a, b in zip(fine["live"], coarse["live"]):
        np.testing.assert_allclose(a[:, :-1], b[:, :-1], atol=1e-5)
        np.testing.assert_allclose(a[:, -1, :, :prompt_rows],
                                   b[:, -1, :, :prompt_rows], atol=1e-5)
        assert a[:, :-1].any()
    # a 32-row program has no page of padding for 25 tokens in pages of 8;
    # the 64-row program's four land on scratch page 0, nowhere else
    assert not fine["elsewhere_written"] and not coarse["elsewhere_written"]
    assert coarse["scratch_written"]
