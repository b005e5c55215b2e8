"""The uniform stack's serve programs (dense GQA and the MoE), at the cells' shapes.

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
- a family of programs a file, compiled in the test's own process. The
  driver runs ``--dist load``, and these files end the run, six compiles
  abreast, as their names have it: the cheapest place for them
  (``tests/conftest.py``, beside ``topo``, has what was measured);
- the kernels pick ``interpret`` from ``jax.default_backend()``, which
  still says ``cpu`` here: the ``as_tpu`` fixture steers that, and every
  test asserts ``tpu_custom_call`` is in the compiled text so an
  interpreted lowering cannot pass.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from tpu_compile_support import (
    LAYOUTS,
    D,
    cell_pool,
    page_tokens,
    table_width,
    _sds,
    _pages,
)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_decode_program_updates_pool_in_place(one_chip, as_tpu, kv):
    """The multi-step decode program (serve.decode.decode_scan, what the
    engine jits as ``_decode_impl_n``) at the GQA 32/8 layout, 4 layers,
    8 slots, donated pools of eight tables' pages (2,049 of 64 tokens) at
    the page the rule gives the layout: the pools ride the step and
    layer loops as carries and every layer writes and reads them by its
    index, so nothing pool-sized is a temporary. With the pools as scanned
    inputs and stacked outputs the program held a second copy of both
    (temp >= two whole pools: 4 GB at the benchmark's size, PERF.md 4)."""
    import dataclasses

    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        decode_scan)
    nq, nkv = LAYOUTS["gqa32x8"]
    cfg = dataclasses.replace(
        get_model_config("mistral-7b"), num_layers=4, hidden_size=512,
        ffn_size=1408, dtype="bfloat16")
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (nq, nkv, D)
    sds = _sds(one_chip)
    MAXP = table_width(page_tokens(nkv, kv))
    B, num_pages = 8, 8 * 8 * MAXP + 1
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda k: gpt.init(cfg, k, jnp.bfloat16),
                       jax.random.PRNGKey(0)))
    layer_pages = _pages(sds, num_pages, nkv, kv)
    pool = jax.tree.map(
        lambda a: sds((cfg.num_layers, *a.shape), a.dtype), layer_pages)
    layer_pool_bytes = sum(a.size * a.dtype.itemsize
                           for a in jax.tree.leaves(layer_pages))

    def program(params, k_pages, v_pages, tokens, positions, tables, stops,
                keys, temp, top_k, top_p):
        return decode_scan(params, tokens, positions, k_pages, v_pages,
                           tables, stops, keys, temp, top_k, top_p, cfg, 8)

    i32 = lambda *shape: sds(shape, jnp.int32)
    compiled = jax.jit(program, donate_argnums=(1, 2)).lower(
        params, pool, pool, i32(B), i32(B), i32(B, MAXP), i32(B),
        sds((B, 2), jnp.uint32), sds((B,), jnp.float32), i32(B),
        sds((B,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_pool_bytes, (
        f"decode program holds {temp / 1e6:.1f} MB of temporaries; one "
        f"layer's K pool is {layer_pool_bytes / 1e6:.1f} MB")


def _mistral_decode_program(one_chip, layers, num_pages, dtype):
    """``decode_scan`` at mistral-7b's widths over ``layers`` layers, 32
    slots, 8 steps, donated pools of ``num_pages`` pages of the size the
    rule gives the layout and ``dtype``: (config, the page, the compile of it
    for the pieces such an engine carries, ``RIDE_PAGES`` pages or one of
    ``RIDE_ROWS``, or, ``carrying=False``, for none)."""
    import dataclasses

    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        PIECE_META, decode_scan)
    from distributed_llm_training_and_inference_system_tpu.serve.engine import (
        InferenceEngine)
    nq, nkv = LAYOUTS["gqa32x8"]
    cfg = dataclasses.replace(get_model_config("mistral-7b"),
                              num_layers=layers, dtype=jnp.dtype(dtype).name)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (nq, nkv, D)
    sds = _sds(one_chip)
    PS = page_tokens(nkv, itemsize=jnp.dtype(dtype).itemsize)
    MAXP = table_width(PS)
    B, K, C = 32, 8, InferenceEngine.piece_rows(PS)
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda k: gpt.init(cfg, k, dtype),
                       jax.random.PRNGKey(0)))
    pool = sds((layers, num_pages, nkv, PS, D), dtype)

    def program(params, k_pages, v_pages, tokens, positions, tables, stops,
                keys, temp, top_k, top_p, ride=None):
        return decode_scan(params, tokens, positions, k_pages, v_pages,
                           tables, stops, keys, temp, top_k, top_p, cfg, K,
                           ride=ride)

    i32 = lambda *shape: sds(shape, jnp.int32)
    args = (params, pool, pool, i32(B), i32(B), i32(B, MAXP), i32(B),
            sds((B, 2), jnp.uint32), sds((B,), jnp.float32), i32(B),
            sds((B,), jnp.float32))

    def compile_(carrying):
        ride = (i32(K, PIECE_META + C),) if carrying else ()
        compiled = jax.jit(program, donate_argnums=(1, 2)).lower(
            *args, *ride).compile()
        text = compiled.as_text()
        assert "tpu_custom_call" in text
        # the piece's window is the multi-query kernel, the slots' the T = 1
        assert ("paged_attention_mq" in text) == carrying
        return compiled
    return cfg, PS, compile_


# temporaries of the program WITHOUT pieces at the cell's shapes (the slow
# case below reads them again; a 40 s compile on 3.6 cores that the driver's
# run no longer pays: PR 61)
CELL_PLAIN_TEMP_BYTES = 820_287_488


@pytest.mark.slow     # ~45 s: the carrying case below is held against it
def test_plain_decode_program_at_the_cells_shapes_holds_what_was_read(
        one_chip, as_tpu):
    """``mistral-7b-16l``'s decode program WITHOUT pieces: 0.82 GB of
    temporaries, the q / k / v stacks' re-layouts (PERF.md 5), and no
    more than was read when the constant was written."""
    layers, num_pages = cell_pool("gqa32x8")
    _cfg, _PS, compile_ = _mistral_decode_program(one_chip, layers, num_pages,
                                                  jnp.bfloat16)
    temp = compile_(False).memory_analysis().temp_size_in_bytes
    assert temp <= CELL_PLAIN_TEMP_BYTES, temp


def test_carrying_decode_program_holds_no_pool_and_no_stack(one_chip, as_tpu):
    """The decode program with a prompt's piece riding every step
    (``decode_scan(ride=...)``: what an engine that rides jits as
    ``_decode_impl_n``) at ``mistral-7b-16l``'s shapes: 16 layers at the
    published widths, 32 slots, donated pools of the cell's budget (357
    pages of 128), 8 steps, pieces of one page. A step carries its piece or
    branches to the plain step through two loops of one or no trip, which
    carry the pools in place as the step and layer loops do; as the
    branches of a ``cond`` they were copied whole (2.18 GB of temporaries
    at a 4-layer size, PERF.md 6, PR 36). Held against the program
    WITHOUT pieces at the same shapes (0.82 GB, the q / k / v stacks'
    re-layouts, PERF.md 5): no more than one layer's K pool beyond it, and
    under the smallest of a pool and the gate / up / down stacks."""
    nkv = LAYOUTS["gqa32x8"][1]
    layers, num_pages = cell_pool("gqa32x8")
    cfg, PS, compile_ = _mistral_decode_program(one_chip, layers, num_pages,
                                                jnp.bfloat16)
    layer_pool_bytes = num_pages * nkv * PS * D * 2
    ffn_stack_bytes = layers * cfg.hidden_size * cfg.ffn_size * 2
    temps = {"plain": CELL_PLAIN_TEMP_BYTES,
             "carrying": compile_(True).memory_analysis().temp_size_in_bytes}
    assert temps["carrying"] < temps["plain"] + layer_pool_bytes, temps
    assert temps["carrying"] < min(layers * layer_pool_bytes,
                                   ffn_stack_bytes), temps


def test_float32_carrying_decode_program_fits_the_kernels_vmem(one_chip,
                                                                as_tpu):
    """``chip_smoke.py``'s ``ride`` phase: mistral-7b's widths, 4 layers,
    FLOAT32 weights and pools of 953 pages. Inside this program XLA keeps
    the window kernel's output in VMEM, and at the bfloat16 score tile (64
    query rows at GQA 32/8) the kernel asked for 16.73 of its 16 MB and the
    chip refused the program (my chip run, PR 36, call E2), though the
    kernel ALONE compiles at that tile; ``_query_tile`` gives 4-byte
    operands half the tile."""
    _cfg, PS, compile_ = _mistral_decode_program(one_chip, 4, 953,
                                                 jnp.float32)
    assert PS == 64         # a float32 row is twice the bytes
    compile_(carrying=True)


def test_an_engine_that_rides_holds_the_parents_programs():
    """``batch-64``'s traffic at a sixteenth of its sizes (prompts 2-64
    tokens over the ladder 16 / 32 / 64, pages of 8, 4 slots, 16 callers'
    worth of requests): the engine ends with the parent's resident
    programs, three cold rungs and ONE decode program, though most of its
    prompts rode the decode dispatches."""
    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ServeConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    from distributed_llm_training_and_inference_system_tpu.serve import (
        InferenceEngine, Request, SamplingParams)
    import numpy as np
    cfg = get_model_config("gpt-test")
    eng = InferenceEngine(
        cfg, ServeConfig(model="gpt-test", max_batch_size=4, max_seq_len=128,
                         prefill_chunk=16, kv_block_size=8, dtype="float32",
                         decode_steps_per_dispatch=4),
        params=gpt.init(cfg, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    lengths = np.clip(rng.lognormal(np.log(16), 0.8, 24), 2, 64).astype(int)
    lengths[:3] = (10, 30, 60)       # every rung, before anything can ride
    # (outputs of 5-30 tokens, so the slots do not all empty in one step)
    for i, n in enumerate(lengths):
        assert eng.scheduler.add_request(Request(
            f"r{i}", rng.integers(1, 250, n).tolist(), SamplingParams(
                temperature=0.0, max_tokens=int(rng.integers(5, 30)))))
    eng.run_until_idle()
    stats = eng.stats()
    assert stats["prefill_ride_tokens"] > stats["prefill_tokens"] // 2
    assert stats["compiled_programs"] == {
        "prefill_dense_buckets": 3, "prefill_extend_buckets": 0,
        "prefill_chunk_buckets": 0, "decode": 1, "decode_short": 0,
        "speculative": 0, "total": 4}


def test_olmoe_decode_program_takes_the_expert_stacks_whole(one_chip, as_tpu):
    """The multi-step decode program of an OLMoE-shaped model (published
    widths, 3 layers, 8 slots): the experts' [L, E, H, F] stacks stay
    outside the layer scan and the kernel indexes them, so the program
    holds no layer's 805 MB of experts as a temporary, and returns the
    routing counts beside the tokens."""
    import dataclasses

    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        decode_scan)
    cfg = dataclasses.replace(get_model_config("olmoe-1b-7b"), num_layers=3,
                              dtype="bfloat16")
    sds = _sds(one_chip)
    PS = page_tokens(cfg.num_kv_heads)
    MAXP = table_width(PS)
    B, num_pages = 8, 8 * MAXP + 1
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda k: gpt.init(cfg, k, jnp.bfloat16),
                       jax.random.PRNGKey(0)))
    pool = sds((cfg.num_layers, num_pages, cfg.num_kv_heads, PS, D),
               jnp.bfloat16)

    def program(params, k_pages, v_pages, tokens, positions, tables, stops,
                keys, temp, top_k, top_p):
        return decode_scan(params, tokens, positions, k_pages, v_pages,
                           tables, stops, keys, temp, top_k, top_p, cfg, 8,
                           return_moe_stats=True)

    i32 = lambda *shape: sds(shape, jnp.int32)
    compiled = jax.jit(program, donate_argnums=(1, 2)).lower(
        params, pool, pool, i32(B), i32(B), i32(B, MAXP), i32(B),
        sds((B, 2), jnp.uint32), sds((B,), jnp.float32), i32(B),
        sds((B,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "moe_gmm" in text and "paged_attention" in text
    one_layer_of_experts = 64 * 3 * 2048 * 1024 * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < one_layer_of_experts // 4, (
        f"decode program holds {temp / 1e6:.1f} MB of temporaries; one "
        f"layer's experts are {one_layer_of_experts / 1e6:.1f} MB")
