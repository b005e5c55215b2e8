"""The main path's Pallas kernels, compiled by the TPU v5e compiler.

Every other test runs the kernels in interpret mode on the CPU, which
cannot see what the chip's compiler refuses: a slice not aligned to the
tiling, a kernel that wants more than its 16 MB of scoped VMEM (the
multi-query paged-attention kernel did, at the 256- and 512-token windows
of cached-prefix and chunked prefill). libtpu is installed here and
compiles for a chip that is DESCRIBED, not attached; nothing runs, so
these tests say nothing about results or times — `chip_smoke.py` checks
each kernel's result against its XLA reference on the real chip.

Rules this file keeps (pytest-xdist runs six workers and each imports
every test file; only one process may load libtpu):

- the topology is described inside the module-scoped ``topo`` fixture,
  never at import, never in a ``skipif``/``parametrize`` argument, never
  in ``conftest.py``; shardings and shapes are built in fixtures/tests;
- all compile tests live in THIS file (one worker, one libtpu load), and
  compile in the test's own process;
- the kernels pick ``interpret`` from ``jax.default_backend()``, which
  still says ``cpu`` here: the ``as_tpu`` fixture steers that, and every
  test asserts ``tpu_custom_call`` is in the compiled text so an
  interpreted lowering cannot pass.
"""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest

# gpt-1b / gpt-750m head layout and the mistral-7b GQA layout
LAYOUTS = {"mha16": (16, 16), "gqa32x8": (32, 8)}
D, PS, MAXP = 128, 64, 32          # head_dim, ServeConfig.kv_block_size,
                                   # max_seq_len 2048 / page 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Make the package's own ``jax.default_backend()`` checks take their
    TPU branch (compiled kernel, not interpret) for this test."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "lowered without a Mosaic kernel (interpret mode?)"
    return compiled


def _kernel_grids(fn, *args) -> list[tuple[int, ...]]:
    """The grid (``iteration_bounds``) of every Mosaic kernel in the lowered
    text of ``fn``: the custom call carries its module as MLIR bytecode."""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    text = jax.jit(fn).lower(*args).as_text()
    bodies = re.findall(r'body\\22: \\22([A-Za-z0-9+/=]+)', text)
    assert bodies, "no tpu_custom_call in the lowered text"
    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True    # the versioned wrapper
    grids = []
    with ctx:
        for body in bodies:
            module = str(ir.Module.parse(base64.b64decode(body)))
            bounds, = re.findall(r"iteration_bounds = array<i64: ([^>]*)>",
                                 module)
            grids.append(tuple(int(n) for n in bounds.split(",")))
    return grids


def _sds(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _pages(sds, num_pages, nkv, kv, layers=()):
    """One layer's pages, or the [L, NP, ...] pool with ``layers=(L,)``."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        QuantPages)
    if kv == "int8":
        return QuantPages(sds((*layers, num_pages, nkv, PS, D), jnp.int8),
                          sds((*layers, num_pages, nkv, PS), jnp.float32))
    return sds((*layers, num_pages, nkv, PS, D), jnp.bfloat16)


# the serving cells' pools (benchmark/configs): layers, pages a layer
CELL_POOLS = {"gqa32x8": (16, 715), "mha16": (10, 715)}


def _cell_call(fn, sds, layout, kv, q_shape):
    """``fn`` on a cell's whole pool with a traced layer index, 32 slots:
    (callable, argument shapes)."""
    nq, nkv = LAYOUTS[layout]
    n_layers, num_pages = CELL_POOLS[layout]
    pool = _pages(sds, num_pages, nkv, kv, layers=(n_layers,))

    def call(q, kp, vp, tables, lengths, layer):
        return fn(q, kp, vp, tables, lengths, impl="auto", layer=layer)
    return call, (sds(q_shape(32, nq), jnp.bfloat16), pool, pool,
                  sds((32, MAXP), jnp.int32), sds((32,), jnp.int32),
                  sds((), jnp.int32))


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_paged_decode_kernel_compiles(one_chip, as_tpu, layout, kv):
    """Single-query decode attention: 8 slots, 32 pages of 64 per slot."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        paged_attention)
    nq, nkv = LAYOUTS[layout]
    sds = _sds(one_chip)
    B = 8
    pages = _pages(sds, B * MAXP + 1, nkv, kv)
    _compile(functools.partial(paged_attention, impl="auto"),
             sds((B, nq, D), jnp.bfloat16), pages, pages,
             sds((B, MAXP), jnp.int32), sds((B,), jnp.int32))


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_paged_decode_kernel_compiles_at_the_cells_shapes(one_chip, as_tpu,
                                                          layout, kv):
    """The decode kernel as the serving cells run it: 32 slots, a block
    table 32 pages wide, the whole [L, 715, ...] pool with a traced layer
    index. Its grid is one step a slot: the page axis is a loop inside."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        paged_attention)
    call, args = _cell_call(paged_attention, _sds(one_chip), layout, kv,
                            lambda slots, nq: (slots, nq, D))
    compiled = _compile(call, *args)
    assert "paged_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * MAXP * 4096, \
        "a pool-sized temporary beside the kernel"
    assert _kernel_grids(call, *args) == [(32, 1)]


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("window", [8, 64, 128, 256, 512])
def test_paged_multi_query_kernel_compiles(one_chip, as_tpu, window, layout,
                                           kv):
    """Every window the engine can hand the multi-query kernel at default
    settings: the speculative verify window (8, all slots) and the
    cached-prefix / chunked-prefill suffix buckets 64..512 (one slot;
    engine._suffix_bucket, prefill_chunk 256)."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        paged_attention_multi)
    nq, nkv = LAYOUTS[layout]
    sds = _sds(one_chip)
    B = 8 if window == 8 else 1
    pages = _pages(sds, 8 * MAXP + 1, nkv, kv)
    _compile(functools.partial(paged_attention_multi, impl="auto"),
             sds((B, window, nq, D), jnp.bfloat16), pages, pages,
             sds((B, MAXP), jnp.int32), sds((B,), jnp.int32))


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_paged_multi_query_kernel_compiles_at_the_cells_shapes(
        one_chip, as_tpu, layout, kv):
    """The speculative-verify window (8 tokens) over all 32 slots of a
    cell's pool, and a 512-token suffix tiled 8 x 64 along the query axis:
    the grid is (slots, query tiles) and never the table's width."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        paged_attention_multi)
    call, args = _cell_call(paged_attention_multi, _sds(one_chip), layout, kv,
                            lambda slots, nq: (slots, 8, nq, D))
    _compile(call, *args)
    assert _kernel_grids(call, *args) == [(32, 1)]
    nq, nkv = LAYOUTS[layout]
    sds = _sds(one_chip)
    pages = _pages(sds, 8 * MAXP + 1, nkv, kv)
    assert _kernel_grids(
        functools.partial(paged_attention_multi, impl="auto"),
        sds((1, 512, nq, D), jnp.bfloat16), pages, pages,
        sds((1, MAXP), jnp.int32), sds((1,), jnp.int32)) == [(1, 8)]


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_decode_program_updates_pool_in_place(one_chip, as_tpu, kv):
    """The multi-step decode program (serve.decode.decode_scan, what the
    engine jits as ``_decode_impl_n``) at the GQA 32/8 layout, 4 layers,
    8 slots, donated pools of 2,049 pages: the pools ride the step and
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
    slots, 8 steps, donated pools of ``num_pages`` pages: (config, the
    compile of it for pieces of ``InferenceEngine.RIDE_PAGES`` pages or,
    ``carrying=False``, for none)."""
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
    B, K, C = 32, 8, InferenceEngine.RIDE_PAGES * PS
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
    return cfg, compile_


def test_carrying_decode_program_holds_no_pool_and_no_stack(one_chip, as_tpu):
    """The decode program with a prompt's piece riding every step
    (``decode_scan(ride=...)``: what an engine that rides jits as
    ``_decode_impl_n``) at ``mistral-7b-16l``'s shapes: 16 layers at the
    published widths, 32 slots, donated pools of 715 pages, 8 steps, pieces
    of ``InferenceEngine.RIDE_PAGES`` pages. A step carries its piece or
    branches to the plain step through two loops of one or no trip, which
    carry the pools in place as the step and layer loops do; as the
    branches of a ``cond`` they were copied whole (2.18 GB of temporaries
    at a 4-layer size, PERF.md 6, PR 36). Held against the program
    WITHOUT pieces at the same shapes (0.82 GB, the q / k / v stacks'
    re-layouts, PERF.md 5): no more than one layer's K pool beyond it, and
    under the smallest of a pool and the gate / up / down stacks."""
    nkv = LAYOUTS["gqa32x8"][1]
    layers, num_pages = CELL_POOLS["gqa32x8"]
    cfg, compile_ = _mistral_decode_program(one_chip, layers, num_pages,
                                            jnp.bfloat16)
    layer_pool_bytes = num_pages * nkv * PS * D * 2
    ffn_stack_bytes = layers * cfg.hidden_size * cfg.ffn_size * 2
    temps = {name: compile_(carrying).memory_analysis().temp_size_in_bytes
             for name, carrying in (("plain", False), ("carrying", True))}
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
    _cfg, compile_ = _mistral_decode_program(one_chip, 4, 953, jnp.float32)
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


@pytest.mark.parametrize("rows,tm", [(256, 16), (2048, 32), (4096, 64),
                                     (8192, 128)])
@pytest.mark.parametrize("which", ["gate_up", "down"])
def test_moe_grouped_matmul_kernel_compiles(one_chip, as_tpu, rows, tm, which):
    """The dropless MoE block's grouped matmul at OLMoE's published widths
    (64 experts, 2048 x 1024) on the ten-layer expert stack as it lies, with
    a traced layer index: the decode step's 32 tokens x 8 choices, and the
    256-, 512- and 1,024-token prefill buckets with the tile
    ``moe_row_tile`` gives each. The stack is an operand WHOLE:
    nothing expert-sized may be a temporary."""
    from distributed_llm_training_and_inference_system_tpu.ops.moe_gmm import (
        grouped_matmul)
    E, H, F, L = 64, 2048, 1024, 10
    k, n = (H, F) if which == "gate_up" else (F, H)
    n_tiles = (rows + E * (tm - 1)) // tm
    sds = _sds(one_chip)
    compiled = _compile(
        functools.partial(grouped_matmul, tm=tm),
        sds((n_tiles * tm, k), jnp.bfloat16), sds((L, E, k, n), jnp.bfloat16),
        sds((n_tiles,), jnp.int32), sds((), jnp.int32), sds((), jnp.int32))
    assert "moe_gmm" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < E * k * n * 2 // 8, f"{temp / 1e6:.1f} MB of temporaries"


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


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("shape", ["gpt-750m-b4", "gqa32x8-b2"])
def test_flash_attention_compiles(one_chip, as_tpu, shape, grad):
    """Training attention at sequence 2048: gpt-750m's micro-batch of 4
    and the GQA 32/8 layout at 2."""
    from distributed_llm_training_and_inference_system_tpu.ops.attention import (
        flash_attention)
    B, (nq, nkv) = {"gpt-750m-b4": (4, LAYOUTS["mha16"]),
                    "gqa32x8-b2": (2, LAYOUTS["gqa32x8"])}[shape]
    sds = _sds(one_chip)
    q = sds((B, 2048, nq, D), jnp.bfloat16)
    kv = sds((B, 2048, nkv, D), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd, q, kv, kv)


def test_rmsnorm_kernel_compiles(one_chip, as_tpu):
    from distributed_llm_training_and_inference_system_tpu.ops.rmsnorm import (
        rms_norm_pallas)
    sds = _sds(one_chip)
    _compile(rms_norm_pallas, sds((4, 2048, 2048), jnp.bfloat16),
             sds((2048,), jnp.float32))


@pytest.mark.parametrize("leaf", ["qkv_stack", "ffn_stack", "embedding"])
def test_fused_adamw_kernel_compiles(one_chip, as_tpu, leaf):
    """The fused AdamW update over gpt-750m's leaf shapes (layer-stacked
    [L, in, out] kernels, the [V, H] embedding), fp32 params with the
    bf16 moments `bench.py` uses."""
    from distributed_llm_training_and_inference_system_tpu.exec.fused_update import (
        fused_adamw_apply)
    shape = {"qkv_stack": (12, 2048, 2048), "ffn_stack": (12, 2048, 5632),
             "embedding": (50304, 2048)}[leaf]
    sds = _sds(one_chip)
    p = {"w": sds(shape, jnp.float32)}
    m = {"w": sds(shape, jnp.bfloat16)}

    def step(p, g, mu, nu, count, lr, clip):
        return fused_adamw_apply(p, g, mu, nu, count, lr=lr, b1=0.9,
                                 b2=0.95, eps=1e-8, weight_decay=0.1,
                                 decay_mask={"w": True}, clip_scale=clip)

    _compile(step, p, p, m, m, sds((), jnp.int32), sds((), jnp.float32),
             sds((), jnp.float32))


@pytest.mark.parametrize("shape", [(8, 2048, 5632), (8, 4096, 11008)],
                         ids=["gpt-1b-ffn", "7b-ffn"])
def test_int4_matmul_kernel_compiles(one_chip, shape):
    """W4A16 decode matmul (takes ``interpret`` as an argument)."""
    from distributed_llm_training_and_inference_system_tpu.ops.int4_matmul_pallas import (
        matmul_w4)
    rows, n_in, n_out = shape
    sds = _sds(one_chip)
    _compile(functools.partial(matmul_w4, group=128, interpret=False),
             sds((rows, n_in), jnp.bfloat16),
             sds((n_in // 2, n_out), jnp.uint8),
             sds((n_in // 128, n_out), jnp.float32),
             sds((n_in,), jnp.float32))


# -- the hybrid cell (nemotron-3-nano-30b-a3b-14l-ep2) ---------------------------

def _hybrid_cell(one_chip):
    """(model config, shapes of params / page pool / state pools) of the
    hybrid cell as its configuration file states it: 64 slots, 1,537 pages
    of 64, 6 state-space layers, 64 of 128 experts held."""
    import json
    from pathlib import Path

    from benchmark import harness
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    file = (Path(__file__).resolve().parents[1] / "benchmark" / "configs"
            / "nemotron-3-nano-30b-a3b-14l-ep2.json")
    config = json.loads(file.read_text())
    cfg = ModelConfig.from_dict(harness.model_dict(config))
    sds = _sds(one_chip)
    B = config["serve"]["max_batch_size"]
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda k: gpt.init(cfg, k, jnp.bfloat16),
                       jax.random.PRNGKey(0)))
    pool = sds((cfg.kv_layers, 1537, cfg.num_kv_heads, PS, D), jnp.bfloat16)
    s = cfg.ssm
    state = {"conv": sds((cfg.ssm_layers, B, s.conv_kernel - 1,
                          s.conv_channels), jnp.bfloat16),
             "ssm": sds((cfg.ssm_layers, B, s.num_heads, s.head_dim,
                         s.state_size), jnp.float32)}
    return cfg, B, params, pool, state


_HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
              "u32": 4, "f32": 4}


def _no_copy_of(text: str, shapes: list[str],
                fused_into_at_most: int = 0) -> None:
    """No ``copy`` of a pool or a stack (a result that starts with one of
    ``shapes``) in an optimised HLO text, inside a fused computation or
    out of one.

    ``fused_into_at_most`` (bytes; the carrying linear program alone asks
    for it): a copy FUSED into an operation that keeps a small part of it
    (a slot's rows sliced out of a pool: the fusion computes those rows
    alone) passes where everything the OUTERMOST fusion that holds it hands
    out is no more than so many bytes; a fusion that hands the copy on
    converted or transposed is pool-sized, and fails."""
    import math
    import re
    computation, called_from = None, {}     # callee -> (caller, its line)
    copies = []
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            computation = head.group(1)
            continue
        for callee in re.findall(r"calls=%?([\w.\-]+)", line):
            called_from[callee] = (computation, line)
        if " copy(" in line and any(
                line.lstrip().split(" = ", 1)[-1].startswith(shape)
                for shape in shapes):
            copies.append((computation, line))
    for at, line in copies:
        made = line
        while fused_into_at_most and "fused" in at and at in called_from:
            at, made = called_from[at]
        result = made.lstrip().split(" = ", 1)[-1].split(" fusion(")[0]
        handed_out = sum(
            _HLO_BYTES.get(kind, 8) * math.prod(int(n) for n in dims.split(",") if n)
            for kind, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]",
                                         result))
        assert made is not line and handed_out <= fused_into_at_most, (
            f"a pool- or stack-sized copy: {line[:200]}\n"
            f"(handed out by: {made[:200]})")


@pytest.mark.parametrize("which", ["up", "down"])
def test_hybrid_grouped_matmul_kernels_compile(one_chip, as_tpu, which):
    """The grouped matmuls at Nemotron-3-Nano's widths on the six-layer
    stacks of the 64 held experts, decode's 16-row tiles: ``up`` stored
    (out, in) = [1856, 2688] and taken transposed with K in blocks (1856
    is no multiple of 128), ``down`` [1856, 2688] with 2688 in column
    blocks of 896. Neither stack may be a temporary."""
    from distributed_llm_training_and_inference_system_tpu.ops.moe_gmm import (
        grouped_matmul)
    E, H, F, L, tm = 64, 2688, 1856, 6, 16
    n_tiles = (384 + E * (tm - 1)) // tm
    sds = _sds(one_chip)
    k = H if which == "up" else F
    compiled = _compile(
        functools.partial(grouped_matmul, tm=tm,
                          rhs_transposed=which == "up"),
        sds((n_tiles * tm, k), jnp.bfloat16), sds((L, E, F, H), jnp.bfloat16),
        sds((n_tiles,), jnp.int32), sds((), jnp.int32), sds((), jnp.int32))
    text = compiled.as_text()
    assert "moe_gmm" in text
    _no_copy_of(text, ["bf16[6,64,1856,2688]"])
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < E * H * F * 2 // 8, f"{temp / 1e6:.1f} MB of temporaries"


def _hybrid_decode_program(one_chip):
    """``decode_scan`` at the hybrid cell's shapes, 2 steps: the compile of
    it with a piece of ``carry`` rows riding each step (what the cell's
    engine jits as ``_decode_impl_n`` since PR 44), or (0) the program
    without pieces; (its text, its memory analysis), checked for what no
    such program may do: copy a pool or an expert stack."""
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        PIECE_META, decode_scan)
    cfg, B, params, pool, state = _hybrid_cell(one_chip)
    sds = _sds(one_chip)
    K = 2

    def program(params, k_pages, v_pages, tokens, positions, tables, stops,
                keys, temp, top_k, top_p, state, ride=None):
        return decode_scan(params, tokens, positions, k_pages, v_pages,
                           tables, stops, keys, temp, top_k, top_p, cfg, K,
                           return_moe_stats=True, ssm_state=state, ride=ride)

    i32 = lambda *shape: sds(shape, jnp.int32)

    @functools.cache
    def compile_(carry):
        ride = (i32(K, PIECE_META + carry),) if carry else ()
        compiled = jax.jit(program, donate_argnums=(1, 2, 11)).lower(
            params, pool, pool, i32(B), i32(B), i32(B, MAXP), i32(B),
            sds((B, 2), jnp.uint32), sds((B,), jnp.float32), i32(B),
            sds((B,), jnp.float32), state, *ride).compile()
        text = compiled.as_text()
        assert "moe_gmm" in text and "paged_attention" in text
        # the piece's windows: the multi-query page kernel and the chunked
        # scan, under the names a prefill program's have
        assert ("paged_attention_mq" in text) == bool(carry)
        assert ("ssm_scan_prefill" in text) == bool(carry)
        _no_copy_of(text, ["bf16[6,64,1856,2688]", "f32[6,64,64,64,128]",
                           "bf16[2,1537,2,64,128]"]
                    # (the carrying program re-lays the 14 MB conv pool at
                    # its entry and its exit, outside the step loop, as
                    # the linear cell's does its own: below)
                    + ([] if carry else ["bf16[6,64,3,6144]"]),
                    # the piece's slot's rows of the state pool, read once:
                    # 12.6 MB of 0.8 GB
                    fused_into_at_most=(16 << 20) if carry else 0)
        return text, compiled.memory_analysis()
    return compile_


HYBRID_STATE_POOL = 6 * 64 * 64 * 64 * 128 * 4


def test_hybrid_decode_program_moves_no_pool_and_no_stack(one_chip, as_tpu):
    """The multi-step decode program at the hybrid cell's shapes: the page
    pools hold the two attention layers alone, the state pools ride the
    carry and are written at [layer], the expert stacks stay whole: no
    temporary the size of a state pool (0.82 GB), of an expert stack
    (3.8 GB) or of a layer's slab of state (134 MB) beyond the step's own
    working set, and no copy of any of them in the program."""
    _, mem = _hybrid_decode_program(one_chip)(0)
    assert mem.temp_size_in_bytes < HYBRID_STATE_POOL // 4, (
        f"decode program holds {mem.temp_size_in_bytes / 1e6:.1f} MB of "
        f"temporaries; the state pool is {HYBRID_STATE_POOL / 1e6:.1f} MB")
    # the donated pools come back in place
    assert mem.alias_size_in_bytes >= HYBRID_STATE_POOL


# the ``M`` in-projections' stack [6, 2688, 10304]: the chip keeps it with
# the 2,688 inputs on the lanes (10,304 columns are no whole number of
# lanes), and a loop that takes a layer of it by a traced index wants the
# other order: ONE copy of the stack, hoisted out of the step loops (a
# dispatch), as the linear cell's ``K`` in-projections
HYBRID_IN_PROJ_BYTES = 6 * 2688 * 10304 * 2


def test_carrying_hybrid_decode_program_fits_the_chip(one_chip, as_tpu):
    """The hybrid decode program with a prompt's piece riding every step
    (PR 44): 128 rows (two pages of 64: ONE chunk of the scan) beside the
    64 slots' rows, through ``paged_attention_mq`` in the 2 attention
    layers and through ``ssm_scan_prefill`` from the slot's own float32
    state in the 6 ``M`` layers, the table's two motifs walked by a loop
    (the piece's rows of the pools ride the loop's carry). The piece's
    slot's rows are read once before the first layer and written once
    after the last: no copy of the state pool (0.82 GB), of a page pool or
    of an expert stack, the pools aliased, and no more temporaries than
    the program without pieces plus a MB a piece row and the one copy of
    the ``M`` in-projections the loop costs (a slot's conv tails read by a
    slice put the 14 MB pool's 3 columns on the lanes inside the step
    loop instead: ~600 MB of padding copied a step, ``ops/ssm.py
    slot_state``). Each body holds the motif's layers ONCE: the 6 grouped
    matmuls of its 3 expert layers, the one T = 1 page kernel, and the
    carrying body the one multi-query kernel."""
    import re
    compile_ = _hybrid_decode_program(one_chip)
    (_, plain), (text, carrying) = compile_(0), compile_(2 * PS)
    assert carrying.alias_size_in_bytes >= HYBRID_STATE_POOL
    assert (carrying.temp_size_in_bytes < plain.temp_size_in_bytes
            + (2 * PS << 20) + HYBRID_IN_PROJ_BYTES), (
        plain.temp_size_in_bytes, carrying.temp_size_in_bytes)

    def kernels(name):
        return len(set(re.findall(rf"%({name}(?:\.\d+)?) = ", text)))
    assert kernels("moe_gmm") == 2 * 6
    assert kernels("paged_attention") == 2
    assert kernels("paged_attention_mq") == 1


def test_hybrid_prefill_program_compiles(one_chip, as_tpu):
    """Cold prefill of a 256-row bucket at the hybrid cell's shapes: the
    chunked scan, the prefill's grouped matmuls, the dense attention cache
    of the two attention layers, and the slot's rows of both state pools
    written in place."""
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    cfg, B, params, pool, state = _hybrid_cell(one_chip)
    sds = _sds(one_chip)
    bucket = 256

    def prefill(params, tokens, length, state, slot):
        live = (jnp.arange(bucket)[None] < length[:, None]).astype(jnp.int32)
        logits, (kd, vd), stats, (tails, hs) = gpt.forward(
            params, tokens, cfg,
            kv_cache=gpt.init_kv_cache(cfg, 1, bucket, dtype=jnp.bfloat16),
            cache_offset=jnp.zeros((1,), jnp.int32),
            unembed_positions=length - 1, return_moe_stats=True,
            segment_ids=live, return_ssm_state=True)
        state = {"conv": state["conv"].at[:, slot].set(
                     tails[:, 0].astype(jnp.bfloat16)),
                 "ssm": state["ssm"].at[:, slot].set(hs[:, 0])}
        return logits, kd, vd, stats, state

    compiled = jax.jit(prefill, donate_argnums=(3,)).lower(
        params, sds((1, bucket), jnp.int32), sds((1,), jnp.int32), state,
        sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "moe_gmm_prefill" in text
    _no_copy_of(text, ["bf16[6,64,1856,2688]", "f32[6,64,64,64,128]"])
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 256e6, f"{temp / 1e6:.1f} MB of temporaries"


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

LATENT_PS, LATENT_MAXP, LATENT_PAGES = 256, 68, 1307


@pytest.mark.parametrize("B,T", [(64, 1), (1, 512), (1, 1024)],
                         ids=["decode", "suffix-512", "chunk-1024"])
def test_latent_paged_attention_kernel_compiles(one_chip, as_tpu, B, T):
    """32 heads over ONE pool of 640-wide rows (576 + padding), pages of
    256: one query a slot, and the windows of suffix and chunked prefill
    tiled 32 tokens a grid step."""
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


LATENT_POOL_BYTES = 7 * LATENT_PAGES * LATENT_PS * 640 * 2
# what a piece's 256 rows may add to the program's temporaries: half a MB a
# row (a row's four float32 streams are 57 KB, its 32 absorbed queries and
# outputs 74 KB, its 4 expert choices' hidden rows 8 KB, a few of each live
# at once); the compiler read 84 MB over the plain program's 270 (PR 41)
PIECE_ROWS_BYTES = LATENT_PS << 19


def test_latent_decode_program_moves_no_pool_and_no_stack(one_chip, as_tpu):
    """The multi-step decode program at the latent cell's shapes: the ONE
    latent pool (3.0 GB) rides the carry and is aliased to the output, the
    expert stacks stay whole: no temporary the size of the pool, of a
    layer's slab of it (428 MB) or of an expert stack (2.8 GB)."""
    mem = _latent_decode_program(one_chip)(0)
    assert mem.temp_size_in_bytes < LATENT_POOL_BYTES // 7, (
        f"decode program holds {mem.temp_size_in_bytes / 1e6:.1f} MB of "
        "temporaries")
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
    compile_ = _latent_decode_program(one_chip)
    plain, carrying = compile_(0), compile_(LATENT_PS)
    assert carrying.alias_size_in_bytes >= LATENT_POOL_BYTES
    assert carrying.temp_size_in_bytes < LATENT_POOL_BYTES // 7
    assert (carrying.temp_size_in_bytes
            < plain.temp_size_in_bytes + PIECE_ROWS_BYTES), (
        plain.temp_size_in_bytes, carrying.temp_size_in_bytes)


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


# -- the linear cell (Kimi-Linear-48B-A3B, 12 layers, 32 of 256 experts): the
# one-step kernel and the two programs that carry state, at the published
# widths: 128 slots, 9 K layers (2.4 GB of state), 3 latent layers ----------

LINEAR_PAGES = 1525


def _linear_cell(one_chip, periods=3, dtype=jnp.bfloat16):
    """(model config, shapes of params / latent pool / state pools) of the
    linear cell as its configuration file states it (3 periods ``K K K *``;
    ``chip_smoke.py``'s riding arm has one, in float32)."""
    import json
    from pathlib import Path

    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    config = json.loads((Path(__file__).resolve().parents[1] / "benchmark"
                         / "configs" / "kimi-linear-48b-a3b-12l-ep8.json"
                         ).read_text())
    group = config["linear_attn_config"]
    cfg = dataclasses.replace(ModelConfig.from_published(dict(
        config, num_hidden_layers=4 * periods, linear_attn_config=dict(
            group, **{k: [i for i in group[k] if i <= 4 * periods]
                      for k in ("kda_layers", "full_attn_layers")}))),
        dtype=jnp.dtype(dtype).name)
    sds = _sds(one_chip)
    B = config["serve"]["max_batch_size"]
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda k: gpt.init(cfg, k, dtype), jax.random.PRNGKey(0)))
    pool = sds((cfg.kv_layers, LINEAR_PAGES, 1, LATENT_PS, 640), dtype)
    k = cfg.kda
    state = {"conv": sds((cfg.kda_layers, k.conv_kernel - 1, B,
                          k.conv_channels), dtype),
             "ssm": sds((cfg.kda_layers, B, k.num_heads, k.head_dim,
                         k.head_dim), jnp.float32)}
    return cfg, B, params, pool, state


def test_kda_decode_kernel_updates_the_state_pool_in_place(one_chip, as_tpu):
    """The one-step delta-rule kernel on the cell's state pool (9 layers x
    128 slots x 32 heads x 128 x 128 float32 = 2.4 GB), 16 heads a grid
    step: Mosaic takes the transposes that turn q, k and the decays into
    columns, the pool is aliased to the output and nothing is temporary."""
    from distributed_llm_training_and_inference_system_tpu.ops import kda
    sds = _sds(one_chip)
    B, nh, d = 128, 32, 128
    compiled = jax.jit(
        lambda q, k, v, g, beta, pool: kda.kda_decode_pool(
            q, k, v, g, beta, pool, 3), donate_argnums=(5,)).lower(
        *(sds((B, nh, d), jnp.bfloat16),) * 3, sds((B, nh, d), jnp.float32),
        sds((B, nh), jnp.float32),
        sds((9, B, nh, d, d), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 9 * B * nh * d * d * 4
    assert mem.temp_size_in_bytes < 32 << 20


@functools.cache
def _linear_decode_program(one_chip, periods=3, dtype=jnp.bfloat16):
    """``decode_scan`` at the linear cell's shapes, 2 steps: the compile of
    it with a piece of ``carry`` rows riding each step (what the cell's
    engine jits as ``_decode_impl_n`` since PR 43), or (0) the program
    without pieces; (its text, its memory analysis)."""
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        PIECE_META, decode_scan)
    cfg, B, params, pool, state = _linear_cell(one_chip, periods, dtype)
    sds = _sds(one_chip)
    K = 2

    def program(params, pool, tokens, positions, tables, stops, keys, temp,
                top_k, top_p, state, ride=None):
        return decode_scan(params, tokens, positions, pool, None, tables,
                           stops, keys, temp, top_k, top_p, cfg, K,
                           return_moe_stats=True, ssm_state=state, ride=ride)

    i32 = lambda *shape: sds(shape, jnp.int32)

    @functools.cache     # (two tests read the plain program's analysis)
    def compile_(carry):
        ride = (i32(K, PIECE_META + carry),) if carry else ()
        compiled = jax.jit(program, donate_argnums=(1, 10)).lower(
            params, pool, i32(B), i32(B), i32(B, 64), i32(B),
            sds((B, 2), jnp.uint32), sds((B,), jnp.float32), i32(B),
            sds((B,), jnp.float32), state, *ride).compile()
        text = compiled.as_text()
        assert all(k in text for k in ("moe_gmm", "mla_paged_attention",
                                       "kda_decode"))
        # the piece's windows: the multi-query latent kernel and the
        # chunked delta rule, under the names a chunk program's have
        assert ("mla_paged_attention_mq" in text) == bool(carry)
        assert ("kda_chunk_prefill" in text) == bool(carry)
        kind = {"bfloat16": "bf16", "float32": "f32"}[cfg.dtype]
        Lk, E = cfg.kda_layers, cfg.layers_of("E")
        _no_copy_of(text, [f"f32[{Lk},128,32,128,128]", "f32[128,32,128,128]",
                           f"{kind}[{periods},1525,1,256,640]",
                           f"{kind}[{E},32,2304,1024]",
                           f"{kind}[{E},32,1024,2304]"],
                    # the piece's slot's rows of the state pool, read once:
                    # 19 MB of 2.4 GB
                    fused_into_at_most=(32 << 20) if carry else 0)
        return text, compiled.memory_analysis()
    return compile_


def test_linear_decode_program_moves_no_pool(one_chip, as_tpu):
    """The multi-step decode program at the linear cell's shapes: the latent
    pool (1.5 GB) and both state pools (2.4 GB + 85 MB) ride the carry and
    come back in place; no copy of the latent pool, of the K state pool or
    a layer's slab of it (268 MB), or of an expert stack (1.7 GB). (The
    85 MB conv-window pool is re-laid once at the program's entry and once
    at its exit, outside the step loop: the compiler keeps the slots on the
    lanes inside it, as it computes the 128-row projections.)"""
    _, mem = _linear_decode_program(one_chip)(0)
    assert mem.temp_size_in_bytes < 512 << 20, (
        f"{mem.temp_size_in_bytes / 1e6:.1f} MB of temporaries")
    assert mem.alias_size_in_bytes >= 3.9e9


# the K in-projections' stack [9, 2304, 12576]: the chip keeps it with the
# 2,304 inputs on the lanes (12,576 columns are no whole number of lanes),
# and a loop that takes a layer of it by a traced index wants the other
# order: ONE copy of the stack, hoisted out of the step loops (a dispatch)
LINEAR_IN_PROJ_BYTES = 9 * 2304 * 12576 * 2


def test_carrying_linear_decode_program_fits_the_chip(one_chip, as_tpu):
    """The linear decode program with a prompt's piece riding every step
    (PR 43): ONE page of 256 rows beside the 128 slots' rows, through
    ``mla_paged_attention_mq`` in the 3 latent layers and through
    ``kda_chunk_prefill`` (4 sub-chunks of 64 from the slot's own float32
    state) in the 9 ``K`` layers, the table's periodic part walked by a
    loop (the one-step kernel takes its layer as a prefetched scalar, the
    piece's rows of the pools ride the loop's carry). The piece's slot's
    rows are read once before the first layer and written once after the
    last: no copy of either state pool (2.5 GB), of the latent pool or of
    an expert stack, the pools aliased, and no more temporaries than the
    program without pieces plus half a MB a piece row and the one copy of
    the ``K`` in-projections the loop costs (a slot's conv windows read by
    a slice put the 81 MB pool's 3 columns on the lanes instead: 3.4 GB of
    padding, ``ops/kda.py slot_state``). Both window kernels' scoped VMEM
    is the compile itself."""
    compile_ = _linear_decode_program(one_chip)
    (_, plain), (_, carrying) = compile_(0), compile_(LATENT_PS)
    assert carrying.alias_size_in_bytes >= 3.9e9
    assert (carrying.temp_size_in_bytes < plain.temp_size_in_bytes
            + PIECE_ROWS_BYTES + LINEAR_IN_PROJ_BYTES), (
        plain.temp_size_in_bytes, carrying.temp_size_in_bytes)


@pytest.mark.slow     # ~2 min; run it before the smoke's ride phase changes
def test_float32_carrying_linear_decode_program_compiles(one_chip, as_tpu):
    """``chip_smoke.py``'s linear ``ride`` arm: Kimi-Linear's widths, one
    period ``K K K *``, FLOAT32 weights, pools and conv windows, 128 slots,
    full-precision matmuls: the window kernel's float32 half tile and the
    chunked delta rule's float32 operands inside the decode program."""
    with jax.default_matmul_precision("highest"):     # as the smoke sets it
        _linear_decode_program(one_chip, 1, jnp.float32)(LATENT_PS)


def test_linear_chunk_program_reads_a_slots_state_once(one_chip, as_tpu):
    """The chunk program (1,024 rows of ONE slot's prompt over the latent
    pages, the slot's K state and conv window carried) at the cell's shapes.
    Read a layer at a time between the layers' writes, the compiler kept the
    state pool as it came beside the pool it wrote: 3.8 GB of temporaries,
    which the chip does not have beside 10.3 GB of weights and pools. Read
    once before the layers and written once after them: under 0.5 GB."""
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        extend_step_forward)
    cfg, B, params, pool, state = _linear_cell(one_chip)
    sds = _sds(one_chip)
    T = 1024

    def chunk(params, tokens, start, m, pool, table, state, slot):
        ok = jnp.arange(T)[None] < m[:, None]
        _, pool, _, state = extend_step_forward(
            params, tokens, start, pool, None, table, cfg, write_ok=ok,
            ssm_state=state, state_slot=slot)
        return pool, state

    i32 = lambda *shape: sds(shape, jnp.int32)
    compiled = jax.jit(chunk, donate_argnums=(4, 6)).lower(
        params, i32(1, T), i32(1), i32(1), pool, i32(1, 64), state,
        i32()).compile()
    text = compiled.as_text()
    assert "mla_paged_attention_mq" in text and "moe_gmm_prefill" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 512 << 20, (
        f"{mem.temp_size_in_bytes / 1e6:.1f} MB of temporaries")
    assert mem.alias_size_in_bytes >= 3.9e9


# -- the four-chip training cell (internlm2-1.8b.pretrain-4k-fsdp4) -----------

def test_fsdp4_train_step_moves_rows_not_the_head(topo, as_tpu):
    """The cell's step (fsdp=4, micro-batch 1 x 4, flash, chunked loss) at
    InternLM2's widths with 2 of its 24 layers, partitioned for the four
    described chips: NO collective has a vocabulary-wide operand (92,544 or
    a quarter of it), and inside the chunked loss's two loops nothing is
    larger than the rows of one chunk. With fsdp on the head's hidden axis
    (before PR 34) each loop gathered the head, ``bf16[2048,92544]``, and
    the backward loop all-reduced its gradient, 379 MB each, once a chunk;
    that step's temporaries at this depth were 3.86 GiB (2.25 since)."""
    import json
    from pathlib import Path

    from benchmark import traffic as traffic_mod
    from benchmark.runners import train as train_runner
    from distributed_llm_training_and_inference_system_tpu.comms.hlo import (
        collectives)
    from distributed_llm_training_and_inference_system_tpu.parallel import (
        ShardedTrainer)
    bench = Path(__file__).resolve().parents[1] / "benchmark"
    config = json.loads((bench / "configs" / "internlm2-1.8b.json").read_text())
    config["num_hidden_layers"] = 2
    traffic = traffic_mod.load(str(bench / "traffic" / "pretrain-4k-fsdp4.json"))
    cfg = train_runner.run_config(config, traffic, seed=0, ckpt_dir="/unused")
    assert cfg.parallel.fsdp == 4 and not cfg.model.tie_word_embeddings
    trainer = ShardedTrainer(cfg.model, cfg.optimizer, cfg.parallel,
                             devices=list(topo.devices), attn_impl="flash")
    V, H, S = cfg.model.vocab_size, cfg.model.hidden_size, cfg.data.max_length
    batch = {k: jax.ShapeDtypeStruct((cfg.parallel.global_batch_size, S),
                                     jnp.int32)
             for k in ("tokens", "segment_ids", "positions")}
    compiled = trainer.lower_step(batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "flash_fwd" in text
    found = collectives(text)
    in_loss = [c for c in found if "chunked_loss" in c.loop]
    assert in_loss, "no collective is named for the loss's loops"
    wide = [c for c in found if c.has_axis(V) or c.has_axis(V // 4)]
    assert not wide, [(c.op, c.shapes, c.loop) for c in wide]
    # every shard's rows of a 512-position chunk; their gradient leaves the
    # matmul in float32 (16.9 MB with the padding of an all-reduce-scatter)
    chunk_rows = 4 * cfg.parallel.micro_batch_size * 512 * H * 4
    big = [c for c in in_loss if c.nbytes > 1.05 * chunk_rows]
    assert not big, [(c.op, c.shapes, c.nbytes) for c in big]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 3.86 * 2 ** 30, f"{temp / 2 ** 30:.2f} GiB of temporaries"


# -- generation by diffusion over blocks (benchmark/configs/sdar-30b-a3b-7l) ---

@pytest.mark.parametrize("B, T", [(64, 4), (1, 256), (1, 512)],
                         ids=["denoise-window", "suffix-256", "suffix-512"])
def test_block_rule_page_kernel_compiles_at_the_cells_shapes(one_chip, as_tpu,
                                                             B, T):
    """The page kernel under the block rule (``paged_attention_blk``) on
    the SDAR cell's pool (7 layers, 2,179 pages, GQA 32 / 4): the denoise
    window of one block over 64 slots, and a prefill window of many."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        paged_attention_multi)
    sds = _sds(one_chip)
    pool = sds((7, 2179, 4, PS, D), jnp.bfloat16)

    def call(q, kp, vp, tables, starts, layer):
        return paged_attention_multi(q, kp, vp, tables, starts, impl="auto",
                                     layer=layer, block=4)
    compiled = _compile(call, sds((B, T, 32, D), jnp.bfloat16), pool, pool,
                        sds((B, MAXP), jnp.int32), sds((B,), jnp.int32),
                        sds((), jnp.int32))
    assert "paged_attention_blk" in compiled.as_text()


def test_diffusion_decode_program_fits_the_chip(one_chip, as_tpu):
    """The denoise dispatch of the SDAR cell (published widths, 7 layers,
    64 slots x 4 rows, 8 forwards): it compiles for the chip, updates the
    pools in place, holds no layer's 1.2 GB of experts as a temporary, and
    weights + pools + temporaries fit the chip's 16 GB."""
    import json
    from pathlib import Path

    from benchmark import harness
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        denoise_scan)
    config = json.loads((Path(__file__).parents[1] / "benchmark/configs"
                         / "sdar-30b-a3b-7l.json").read_text())
    cfg = ModelConfig.from_dict(harness.model_dict(config))
    sds = _sds(one_chip)
    B, Bd, num_pages = 64, cfg.diffusion.block_length, 2179
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda k: gpt.init(cfg, k, jnp.bfloat16),
                       jax.random.PRNGKey(0)))
    pool = sds((cfg.num_layers, num_pages, cfg.num_kv_heads, PS, D),
               jnp.bfloat16)

    def program(params, k_pages, v_pages, window, starts, tables, stops,
                keys, temp, top_k, top_p):
        return denoise_scan(params, window, starts, k_pages, v_pages, tables,
                            stops, keys, temp, top_k, top_p, cfg, 8)

    i32 = lambda *shape: sds(shape, jnp.int32)
    compiled = jax.jit(program, donate_argnums=(1, 2)).lower(
        params, pool, pool, (i32(B, Bd), i32(B, Bd), i32(B)), i32(B),
        i32(B, MAXP), i32(B), sds((B, 2), jnp.uint32),
        sds((B,), jnp.float32), i32(B), sds((B,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "paged_attention_blk" in text and "moe_gmm_prefill" in text
    mem = compiled.memory_analysis()
    # (the temporaries are the head's and the sampler's: [256, 151936]
    # float32 logits are 156 MB, and the sampling branches that a greedy
    # batch never runs are sized for all the same: 1.58 GB as compiled for
    # PR 42. One layer's experts are 1.21 GB, the pools 2.0 GB)
    assert mem.temp_size_in_bytes < 2.0e9, (
        f"{mem.temp_size_in_bytes / 1e6:.1f} MB of temporaries")
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"denoise program: arguments {mem.argument_size_in_bytes / 1e9:.2f}"
          f" GB, temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, total "
          f"{total / 1e9:.2f} GB")
    assert total < 14.5e9, f"{total / 1e9:.2f} GB on a 16 GB chip"
