"""A slot's sampling key is made on the host (serve/sampling.py
``seed_key_data``), and so is the first token's, the slot's key with the
prompt's length folded in (``fold_in_key_data``); the decode programs fold
each later position in themselves: (a) both host derivations are JAX's own
keys, bit for bit; (b) a seeded request through every prefill path is
served the tokens of the direct reference ``chip_smoke.seeded_reference``
(fold_in(PRNGKey(seed), context length) for every token), for a dense, a
sparse-expert and a hybrid test model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serving_support as support
from chip_smoke import seeded_reference
from distributed_llm_training_and_inference_system_tpu.config import (
    get_model_config)
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ServeConfig)
from distributed_llm_training_and_inference_system_tpu.models import gpt
from distributed_llm_training_and_inference_system_tpu.serve import (
    InferenceEngine, SamplingParams)
from distributed_llm_training_and_inference_system_tpu.serve.sampling import (
    fold_in_key_data, seed_key_data)

BASE_SEED, ADMITTED = 12345, 17        # an engine's _base_seed + its counter
SEEDS = (0, 1, 2**31 - 1, 2**31, 2**32 - 1, 2**32 + 5, -1,
         BASE_SEED + ADMITTED)


@pytest.mark.parametrize("x64", [False, True])
@pytest.mark.parametrize("seed", SEEDS)
def test_host_key_is_jaxs_own_bit_for_bit(seed, x64):
    with jax.enable_x64(x64):
        want = np.asarray(jax.random.key_data(jax.random.PRNGKey(seed)))
        got = seed_key_data(seed)
    assert got.dtype == np.uint32 and got.shape == (2,)
    assert got.tolist() == want.tolist()
    if not x64:                    # the seed is narrowed to 32 bits first
        assert got.tolist() == [0, seed % 2**32]


@pytest.mark.parametrize("x64", [False, True])
@pytest.mark.parametrize("n", [0, 1, 16, 333, 2047, 2**31 - 1])
def test_host_fold_in_is_jaxs_own_bit_for_bit(n, x64):
    with jax.enable_x64(x64):
        for seed in SEEDS:
            want = np.asarray(jax.random.key_data(
                jax.random.fold_in(jax.random.PRNGKey(seed), n)))
            got = fold_in_key_data(seed_key_data(seed), n)
            assert got.dtype == np.uint32 and got.shape == (2,)
            assert got.tolist() == want.tolist(), (seed, n)


def test_host_fold_in_is_the_decode_programs_derivation():
    """``decode_scan`` folds ``pos + 1`` into the wrapped slot key on the
    device; the first token's key is the same fold at the prompt's length,
    made on the host."""
    keys = np.stack([seed_key_data(s) for s in SEEDS])
    n = np.arange(len(SEEDS), dtype=np.int32) * 37 + 5
    on_device = jax.random.key_data(jax.vmap(jax.random.fold_in)(
        jax.vmap(jax.random.wrap_key_data)(jnp.asarray(keys)),
        jnp.asarray(n)))
    on_host = [fold_in_key_data(k, int(i)) for k, i in zip(keys, n)]
    assert np.asarray(on_device).tolist() == np.stack(on_host).tolist()


def test_another_prng_implementation_is_refused_by_name():
    with jax.default_prng_impl("rbg"):
        with pytest.raises(ValueError, match="threefry2x32"):
            InferenceEngine(get_model_config("gpt-test"), ServeConfig(
                model="gpt-test", max_batch_size=2, max_seq_len=64,
                dtype="float32"))


# -- (b) every prefill path against the direct reference ------------------------

SPAN, NEW = 128, 12       # the reference's one padded length; new tokens
SEEDED = dict(temperature=0.8, top_k=40, top_p=0.9)


@pytest.fixture(scope="module", params=["gpt-test", "olmoe-test",
                                        "nemotron-h-test"])
def model(request):
    cfg = get_model_config(request.param)
    params = support.params_of(cfg)
    forward = jax.jit(lambda p, t: gpt.forward(p, t, cfg))

    def next_logits(context):
        padded = np.zeros((1, SPAN), np.int32)
        padded[0, :len(context)] = context
        return forward(params, jnp.asarray(padded))[0, len(context) - 1]
    return cfg, params, next_logits


def _cold(model):
    eng = support.engine(model[0])
    prompts = [support.tokens(37, seed=1)]
    reqs = eng.generate(prompts, SamplingParams(max_tokens=NEW, seed=41,
                                                **SEEDED))
    assert eng.stats()["prefix_cached_tokens"] == 0
    assert eng.compiled_programs()["prefill_dense_buckets"] == 1
    return reqs


def _suffix(model):
    eng = support.engine(model[0])
    first = support.tokens(40, seed=2)
    eng.generate([first], SamplingParams(temperature=0.0, max_tokens=2))
    reqs = eng.generate([first[:32] + support.tokens(11, seed=3)],
                        SamplingParams(max_tokens=NEW, seed=42, **SEEDED))
    assert eng.stats()["prefix_cached_tokens"] == 32
    assert eng.compiled_programs()["prefill_extend_buckets"] == 1
    return reqs


def _chunked(model):
    eng = support.engine(model[0], chunked_prefill_tokens=16)
    reqs = eng.generate([support.tokens(43, seed=4)],
                        SamplingParams(max_tokens=NEW, seed=43, **SEEDED))
    programs = eng.compiled_programs()
    assert programs["prefill_chunk_buckets"] >= 1
    assert programs["prefill_extend_buckets"] == 1
    assert programs["prefill_dense_buckets"] == 0
    return reqs


def _swap_in(model):
    """Two requests that outgrow a pool of 10 pages: one is swapped out and
    comes back through ``_restore_swapped``, which seeds the slot anew."""
    eng = support.engine(model[0], admission="ondemand", preemption="swap",
                  kv_num_blocks=11)
    reqs = eng.generate([support.tokens(16, seed=5), support.tokens(16, seed=6)],
                        SamplingParams(max_tokens=40, seed=44, **SEEDED))
    assert eng.total_swap_ins > 0
    return reqs


@pytest.mark.parametrize("path", [_cold, _suffix, _chunked, _swap_in])
def test_a_seeded_request_is_served_the_direct_references_tokens(model, path):
    cfg, _, next_logits = model
    if cfg.is_recurrent and path is not _cold:
        pytest.skip("a recurrent model has the cold path alone: prefix "
                    "reuse is off and the others are refused by name")
    for req in path(model):
        want = seeded_reference(next_logits, req.prompt_tokens, req.sampling,
                                req.sampling.max_tokens)
        assert req.generated_tokens == want
