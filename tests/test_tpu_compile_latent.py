"""The latent cell's kernel and serve programs (Xing4.0-29B-A4B, 7 layers).

Compiled by the TPU v5e compiler for a chip that is DESCRIBED, not attached
(libtpu is installed here); nothing runs, so these tests say nothing about
results or times: `chip_smoke.py` checks each kernel's result against its
XLA reference on the real chip. Every other test runs the kernels in
interpret mode on the CPU, which cannot see what the chip's compiler
refuses: a slice not aligned to the tiling, a kernel that wants more than
its 16 MB of scoped VMEM.

Rules the ``tests/test_tpu_compile_*.py`` files keep (pytest-xdist imports
every test file in every worker; the driver's command allows several
processes to load libtpu, ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``, and without it
a second process's ``topo`` skips):

- the topology is described inside the module-scoped ``topo`` fixture
  (``tests/conftest.py``), never at import, never in a
  ``skipif``/``parametrize`` argument; shardings and shapes are built in
  fixtures/tests;
- a family of programs a file (PR 45 split the one file by family so that
  ``--dist loadfile`` spreads them over the workers), compiled in the test's
  own process;
- the kernels pick ``interpret`` from ``jax.default_backend()``, which
  still says ``cpu`` here: the ``as_tpu`` fixture steers that, and every
  test asserts ``tpu_custom_call`` is in the compiled text so an
  interpreted lowering cannot pass.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest

from tpu_compile_support import (
    LATENT_PS,
    LATENT_MAXP,
    LATENT_PAGES,
    LATENT_POOL_BYTES,
    PIECE_ROWS_BYTES,
    _compile,
    _sds,
    _no_copy_of,
)


def test_a_latent_engine_that_rides_holds_the_parents_programs():
    """``doc-qa-64``'s traffic at a sixteenth of its sizes (4 documents of
    512-1,024 tokens loaded one at a time, each once, by chunks of 64;
    then 16 questions of 2-16 tokens behind them, replies of 4-24, over 4
    slots and pages of 16): the latent engine ends with the programs the
    parent ended with on the same requests (the chunk program, three
    suffix rungs, ONE decode program: 5), though the tails admitted to a
    busy batch rode the decode dispatches."""
    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ServeConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    from distributed_llm_training_and_inference_system_tpu.serve import (
        InferenceEngine, Request, SamplingParams)
    import numpy as np
    cfg = get_model_config("xing-test")
    eng = InferenceEngine(
        cfg, ServeConfig(model="xing-test", max_batch_size=4,
                         max_seq_len=1088, kv_block_size=16, dtype="float32",
                         chunked_prefill_tokens=64,
                         decode_steps_per_dispatch=4),
        params=gpt.init(cfg, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)

    def draw(median, sigma, lo, hi, n):
        return np.clip(rng.lognormal(np.log(median), sigma, n),
                       lo, hi).astype(int)

    def greedy(n):
        return SamplingParams(temperature=0.0, max_tokens=int(n))
    docs = [rng.integers(1, 250, n).tolist()
            for n in draw(768, 0.25, 512, 1024, 4)]
    for doc in docs:     # set-up: each document once, one question, a token
        eng.generate([doc + rng.integers(1, 250, 2).tolist()], greedy(1))
    loaded = eng.stats()
    for i, (q, new) in enumerate(zip(draw(6, 0.5, 2, 16, 16),
                                     draw(12, 0.5, 4, 24, 16))):
        assert eng.scheduler.add_request(Request(
            f"r{i}", docs[i % 4] + rng.integers(1, 250, q).tolist(),
            greedy(new)))
    eng.run_until_idle()
    stats = eng.stats()
    assert stats["prefill_ride_tokens"] - loaded["prefill_ride_tokens"] > 0
    # every question found its document's whole pages
    assert (stats["prefix_cached_tokens"] - loaded["prefix_cached_tokens"]
            == sum(len(docs[i % 4]) // 16 * 16 for i in range(16)))
    assert stats["compiled_programs"] == loaded["compiled_programs"] == {
        "prefill_dense_buckets": 0, "prefill_extend_buckets": 3,
        "prefill_chunk_buckets": 1, "decode": 1, "decode_short": 0,
        "speculative": 0, "total": 5}


# -- the latent cell (Xing4.0-29B-A4B, 7 layers): the kernel and the decode
# program at the published widths and the configuration's page size ---------


@pytest.mark.parametrize("B,T", [(64, 1), (1, 512), (1, 1024), (64, 2),
                                 (64, 16)],
                         ids=["decode", "suffix-512", "chunk-1024",
                              "window-of-2", "window-of-16"])
def test_latent_paged_attention_kernel_compiles(one_chip, as_tpu, B, T):
    """32 heads over ONE pool of 640-wide rows (576 + padding), pages of
    256: one query a slot, and the windows of suffix and chunked prefill
    tiled 32 tokens a grid step; a decode step, the self-drafting window of
    two and the largest tile that scores two pages a loop step (512 rows)
    hold the ring of 8 pages."""
    from distributed_llm_training_and_inference_system_tpu.ops.mla_paged_attention import (
        mla_paged_attention)
    sds = _sds(one_chip)
    compiled = _compile(
        functools.partial(mla_paged_attention, scale=0.14, value_width=512,
                          layer=3),
        sds((B, T, 32, 640), jnp.bfloat16),
        sds((7, LATENT_PAGES, 1, LATENT_PS, 640), jnp.bfloat16),
        sds((B, LATENT_MAXP), jnp.int32), sds((B,), jnp.int32))
    assert "mla_paged_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_a_576_wide_latent_page_is_refused_by_mosaic(one_chip, as_tpu):
    """Why a latent row is stored 640 wide: the chip's layout pads a
    576-wide minor dimension to 640, and a page copy of 576 is refused."""
    from distributed_llm_training_and_inference_system_tpu.ops.mla_paged_attention import (
        mla_paged_attention)
    sds = _sds(one_chip)
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(functools.partial(mla_paged_attention, scale=0.14,
                                   value_width=512, layer=3),
                 sds((64, 1, 32, 576), jnp.bfloat16),
                 sds((7, LATENT_PAGES, 1, LATENT_PS, 576), jnp.bfloat16),
                 sds((64, LATENT_MAXP), jnp.int32), sds((64,), jnp.int32))


@functools.cache
def _latent_decode_program(one_chip, layers=7, dtype=jnp.bfloat16, B=64,
                           pages=LATENT_PAGES):
    """``decode_scan`` at the latent cell's shapes (the published widths, 7
    layers, 64 slots, a donated pool of 1,307 pages of 256): the compile of
    it with a piece of ``carry`` rows riding each of its 2 steps, or (0)
    the program without pieces."""
    import json
    from pathlib import Path

    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        PIECE_META, decode_scan)
    config = json.loads((Path(__file__).resolve().parents[1] / "benchmark"
                         / "configs" / "xing4.0-29b-a4b-7l.json").read_text())
    cfg = dataclasses.replace(
        ModelConfig.from_published(dict(config, num_hidden_layers=layers)),
        dtype=jnp.dtype(dtype).name)
    sds = _sds(one_chip)
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda k: gpt.init(cfg, k, dtype), jax.random.PRNGKey(0)))
    K = 2
    pool = sds((layers, pages, 1, LATENT_PS, 640), dtype)

    def program(params, pool, tokens, positions, tables, stops, keys, temp,
                top_k, top_p, ride=None):
        return decode_scan(params, tokens, positions, pool, None, tables,
                           stops, keys, temp, top_k, top_p, cfg, K,
                           return_moe_stats=True, ride=ride)

    i32 = lambda *shape: sds(shape, jnp.int32)

    @functools.cache     # (two tests read the plain program's analysis)
    def compile_(carry):
        ride = (i32(K, PIECE_META + carry),) if carry else ()
        compiled = jax.jit(program, donate_argnums=(1,)).lower(
            params, pool, i32(B), i32(B), i32(B, LATENT_MAXP), i32(B),
            sds((B, 2), jnp.uint32), sds((B,), jnp.float32), i32(B),
            sds((B,), jnp.float32), *ride).compile()
        text = compiled.as_text()
        assert "moe_gmm" in text and "mla_paged_attention" in text
        # the piece's window is the multi-query kernel, the slots' the T = 1
        assert ("mla_paged_attention_mq" in text) == bool(carry)
        kind = {"bfloat16": "bf16", "float32": "f32"}[cfg.dtype]
        _no_copy_of(text, [f"{kind}[{layers},{pages},1,256,640]",
                           f"{kind}[{pages},1,256,640]",
                           f"{kind}[{layers - 1},64,3584,1024]",
                           f"{kind}[{layers - 1},64,1024,3584]"])
        return compiled.memory_analysis()
    return compile_


# temporaries of the program WITHOUT pieces (the slow case below reads them
# again; 84 s of compile that the driver's run no longer pays: PR 53)
LATENT_PLAIN_TEMP_BYTES = 270_240_256


@pytest.mark.slow     # ~85 s: the carrying case below holds every property
def test_latent_decode_program_moves_no_pool_and_no_stack(one_chip, as_tpu):
    """The multi-step decode program at the latent cell's shapes: the ONE
    latent pool (3.0 GB) rides the carry and is aliased to the output, the
    expert stacks stay whole: no temporary the size of the pool, of a
    layer's slab of it (428 MB) or of an expert stack (2.8 GB). (The
    carrying program's case compiles the same step body with a window
    beside it and asserts the same: no copy, the pool aliased, temporaries
    under a layer's slab.)"""
    mem = _latent_decode_program(one_chip)(0)
    assert mem.temp_size_in_bytes < LATENT_POOL_BYTES // 7, (
        f"decode program holds {mem.temp_size_in_bytes / 1e6:.1f} MB of "
        "temporaries")
    assert mem.temp_size_in_bytes <= LATENT_PLAIN_TEMP_BYTES
    assert mem.alias_size_in_bytes >= LATENT_POOL_BYTES


def test_carrying_latent_decode_program_fits_the_chip(one_chip, as_tpu):
    """The latent decode program with a prompt's piece riding every step
    (PR 41: what ``doc-qa-64``'s engine jits as ``_decode_impl_n``): ONE
    page of 256 rows beside the 64 slots' rows, the piece's window through
    ``mla_paged_attention_mq`` at 32 heads x 640 inside the program. Three
    walls earlier PRs met: the window kernel's scoped VMEM inside the
    program (PR 36: 16.73 of 16 MB though the kernel compiled alone), a
    copy of a pool or an expert stack, and the cell's memory (it peaks at
    14.3 of 15.75 GB): the program compiles, copies neither, aliases the
    pool and holds no more temporaries than the program without pieces
    plus what 256 more rows' activations take."""
    carrying = _latent_decode_program(one_chip)(LATENT_PS)
    assert carrying.alias_size_in_bytes >= LATENT_POOL_BYTES
    assert carrying.temp_size_in_bytes < LATENT_POOL_BYTES // 7
    assert (carrying.temp_size_in_bytes
            < LATENT_PLAIN_TEMP_BYTES + PIECE_ROWS_BYTES), (
        carrying.temp_size_in_bytes)


def test_float32_carrying_latent_decode_program_fits_the_kernels_vmem(
        one_chip, as_tpu):
    """``chip_smoke.py``'s latent ``ride`` arm: Xing4.0's widths, the dense
    layer and one expert layer, FLOAT32 weights and a pool of 129 pages, 16
    slots, full-precision matmuls. At the bfloat16 tile (1,024 query rows:
    32 tokens x 32 heads) the window kernel's float32 blocks and its
    six-pass products asked for more than its 16 MB of VMEM and the chip
    refused the program (my chip run, PR 41, call 1; at the default
    precision it compiles); 4-byte operands take half the rows."""
    with jax.default_matmul_precision("highest"):     # as the smoke sets it
        _latent_decode_program(one_chip, layers=2, dtype=jnp.float32, B=16,
                               pages=129)(LATENT_PS)
