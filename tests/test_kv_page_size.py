"""The page where none is stated (``ServeConfig.kv_block_size`` 0): by the
stored row's bytes (``serve/kv_cache.py page_size_by_rows``), resolved once
by the engine and written back onto the caller's configuration."""

import dataclasses
import json
import math
import types
from pathlib import Path

import pytest

from serving_support import params_of, tokens

from distributed_llm_training_and_inference_system_tpu.config import (
    get_model_config)
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ConfigError, ModelConfig, ServeConfig)
from distributed_llm_training_and_inference_system_tpu.metrics.spans import (
    STARTUP)
from distributed_llm_training_and_inference_system_tpu.serve import (
    InferenceEngine, SamplingParams)
from distributed_llm_training_and_inference_system_tpu.serve.kv_cache import (
    MIN_PAGE_TOKENS, PAGE_COPY_BYTES, PagedKVCache, kv_row_bytes,
    page_size_by_rows, resolve_page_size)

CONFIGS = Path(__file__).resolve().parents[1] / "benchmark" / "configs"
RIDE_ROWS = InferenceEngine.RIDE_ROWS


def published(name):
    config = json.loads((CONFIGS / f"{name}.json").read_text())
    return ModelConfig.from_published(config), config["serve"]


def resolved(model, **serve):
    cfg = ServeConfig(model=model.name, **serve)
    stated = resolve_page_size(model, cfg, most=RIDE_ROWS)
    return cfg.kv_block_size, stated


# -- the rule -------------------------------------------------------------------

@pytest.mark.parametrize("name,page,page_bytes", [
    ("falcon-h1-34b-4l", 128, 256 << 10),                 # 4 K/V heads
    ("sdar-30b-a3b-7l", 128, 256 << 10),                  # 4
    ("nemotron-3-nano-30b-a3b-14l-ep2", 128, 128 << 10),  # 2
    ("mistral-7b-16l", 128, 512 << 10),                   # 8
    ("olmoe-1b-7b-10l", 64, 512 << 10),                   # 16: stays
])
def test_the_default_page_of_the_benchmarks_configurations(name, page,
                                                           page_bytes):
    model, serve = published(name)
    assert "kv_block_size" not in serve
    assert resolved(model, **serve) == (page, False)
    assert page * kv_row_bytes(model) == page_bytes


@pytest.mark.parametrize("name", [
    "xing4.0-29b-a4b-7l", "kimi-linear-48b-a3b-12l-ep8",
    "solar-open2-250b-4l-ep8", "joyai-llm-flash-8l-ep2", "lfm2-8b-a1b-16l"])
def test_a_stated_page_of_256_stays(name):
    model, serve = published(name)
    assert serve["kv_block_size"] == 256
    assert resolved(model, **serve) == (256, True)


@pytest.mark.parametrize("stated", [8, 16, 64, 96, 512])
def test_a_stated_page_is_used_as_it_is(stated):
    model, serve = published("falcon-h1-34b-4l")
    assert resolved(model, **{**serve, "kv_block_size": stated}) == (stated,
                                                                     True)


def test_a_negative_page_is_refused():
    with pytest.raises(ConfigError, match="kv_block_size"):
        ServeConfig(kv_block_size=-64).validate()


@pytest.mark.parametrize("kind,row", [("none", 2 * 16 * 128 * 2),
                                      ("int8", 2 * 16 * (128 + 4)),
                                      ("int4", 2 * 16 * (64 + 4))])
def test_quantised_pages_count_their_stored_bytes(kind, row):
    """OLMoE's 16 heads: 8,192 B a token in bfloat16 (64 tokens reach
    512 KB), 4,224 in int8 and 2,176 in int4 (128 do not: the cap)."""
    model, serve = published("olmoe-1b-7b-10l")
    assert kv_row_bytes(model, 2, kind) == row
    page, _ = resolved(model, **{**serve, "kv_quantization": kind})
    assert page == (64 if kind == "none" else 128)
    cache = PagedKVCache(dataclasses.replace(model, num_layers=1), 1, 256,
                         page_size=page, num_pages=2, quantized=kind)
    assert cache.bytes_per_token == row
    assert cache.stats()["page_bytes"] == page * row


@pytest.mark.parametrize("name", ["xing4.0-29b-a4b-7l", "lfm2-8b-a1b-16l"])
def test_a_latent_row_and_paired_heads_count_as_stored(name):
    """A latent pool's ONE padded row of 640 values; 8 heads of 64 stored as
    4 pairs on 128 lanes are the bytes of 8 x 64."""
    model, _ = published(name)
    row = kv_row_bytes(model)
    assert row == (640 * 2 if model.is_latent else 2 * 8 * 64 * 2)
    assert resolved(model)[0] == 128


@pytest.mark.parametrize("row,most,page", [
    (1, 128, 128), (1, 256, 256), (1, 64, 64), (1, 100, 64),
    (2048, 512, 256), (4096, 512, 128), (8192, 512, 64), (1 << 20, 512, 64)])
def test_the_rule_is_a_power_of_two_between_the_floor_and_the_cap(row, most,
                                                                  page):
    assert page_size_by_rows(row, most) == page
    assert page >= MIN_PAGE_TOKENS and page & (page - 1) == 0
    assert page == most or page * row >= PAGE_COPY_BYTES or 2 * page > most


def test_a_float32_row_is_twice_the_bytes():
    """chip_smoke's ``ride`` arm: mistral-7b's 8 heads in float32 reach
    512 KB at 64 tokens."""
    model, serve = published("mistral-7b-16l")
    assert resolved(model, **{**serve, "dtype": "float32"}) == (64, False)


# -- through an engine ----------------------------------------------------------

@pytest.mark.parametrize("page", [64, 128])
def test_a_step_carries_128_rows_at_either_page(page):
    cfg = get_model_config("gpt-test")
    eng = InferenceEngine(cfg, ServeConfig(
        model="gpt-test", max_batch_size=2, max_seq_len=256,
        kv_block_size=page, dtype="float32"), params=params_of(cfg))
    assert eng._ride_rows == InferenceEngine.piece_rows(page) == 128
    assert eng.stats()["kv"]["page_size_stated"] is True


@pytest.mark.parametrize("name", ["gpt-test", "olmoe-test", "falcon-h1-test",
                                  "xing-test"])
def test_the_callers_configuration_holds_the_resolved_page(name):
    """What ``benchmark/runners/serve.py`` does: build the server, THEN
    divide by ``kv_block_size`` of the object it handed over."""
    from benchmark.runners.serve import Served
    from distributed_llm_training_and_inference_system_tpu.serve.server import (
        InferenceServer)
    cfg = get_model_config(name)
    serve_cfg = ServeConfig(model=name, max_batch_size=2, max_seq_len=256,
                            dtype="float32", port=0)
    assert serve_cfg.kv_block_size == 0
    server = InferenceServer(cfg, serve_cfg, params=params_of(cfg))
    kv = server.engine.stats()["kv"]
    # a test model's row is a few hundred bytes: the cap
    assert serve_cfg.kv_block_size == kv["page_size"] == RIDE_ROWS
    assert kv["page_size_stated"] is False
    assert kv["page_bytes"] == RIDE_ROWS * kv_row_bytes(cfg, 4)
    assert kv["page_bytes"] * cfg.kv_layers == (RIDE_ROWS
                                                * kv["bytes_per_token"])
    # ... and the start-up log line says which rule sized the pool
    assert STARTUP.snapshot()["notes"]["kv_pool"] == {
        k: kv[k] for k in ("num_pages", "page_size", "page_bytes",
                           "page_size_stated")}
    assert "page_size_stated False" in STARTUP.summary()
    buckets = Served.prefill_buckets(
        types.SimpleNamespace(serve_cfg=serve_cfg), 32, 256)
    chunk = math.ceil(serve_cfg.prefill_chunk / RIDE_ROWS) * RIDE_ROWS
    assert buckets == [min(k * chunk, 256)
                       for k in range(1, 256 // chunk + 1)]
    # an object used again states the size: the second engine keeps it
    again = InferenceEngine(cfg, serve_cfg, params=params_of(cfg))
    assert again.kv.page_size == RIDE_ROWS and again.kv.page_size_stated


def test_an_engine_on_the_default_page_serves():
    cfg = get_model_config("gpt-test")
    stated, default = (InferenceEngine(cfg, ServeConfig(
        model="gpt-test", max_batch_size=2, max_seq_len=256, dtype="float32",
        kv_block_size=page), params=params_of(cfg)) for page in (64, 0))
    assert (stated.kv.page_size, default.kv.page_size) == (64, 128)
    prompts = [tokens(70, seed=1), tokens(9, seed=2)]
    greedy = SamplingParams(temperature=0.0, max_tokens=70)
    assert ([r.generated_tokens for r in default.generate(prompts, greedy)]
            == [r.generated_tokens for r in stated.generate(prompts, greedy)])


def test_a_diffusion_models_stated_page_must_hold_whole_blocks():
    cfg = get_model_config("sdar-test")
    Bd = cfg.diffusion.block_length
    with pytest.raises(ValueError, match=f"kv_block_size {Bd + 1} must be a "
                                         f"multiple of block_length {Bd}"):
        InferenceEngine(cfg, ServeConfig(
            model="sdar-test", max_batch_size=2, max_seq_len=64,
            kv_block_size=Bd + 1, dtype="float32"), params=params_of(cfg))
    # ... and none stated resolves to one that does
    eng = InferenceEngine(cfg, ServeConfig(
        model="sdar-test", max_batch_size=2, max_seq_len=256,
        dtype="float32"), params=params_of(cfg))
    assert eng.kv.page_size == RIDE_ROWS and not eng.kv.page_size % Bd
