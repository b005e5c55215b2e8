"""The linear cell's kernel and serve programs (Kimi-Linear-48B-A3B, 12 layers).

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
    PIECE_ROWS_BYTES,
    _largest_f32_under,
    _pair_forms_traced,
    _sds,
    _no_copy_of,
)


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


# temporaries of the program WITHOUT pieces (the slow case below reads them
# again; 103 s of compile that the driver's run no longer pays: PR 53)
LINEAR_PLAIN_TEMP_BYTES = 344_486_400


@pytest.mark.slow     # ~100 s: the carrying case below holds every property
def test_linear_decode_program_moves_no_pool(one_chip, as_tpu):
    """The multi-step decode program at the linear cell's shapes: the latent
    pool (1.5 GB) and both state pools (2.4 GB + 85 MB) ride the carry and
    come back in place; no copy of the latent pool, of the K state pool or
    a layer's slab of it (268 MB), or of an expert stack (1.7 GB). (The
    85 MB conv-window pool is re-laid once at the program's entry and once
    at its exit, outside the step loop: the compiler keeps the slots on the
    lanes inside it, as it computes the 128-row projections.)"""
    _, mem = _linear_decode_program(one_chip)(0)
    assert mem.temp_size_in_bytes <= LINEAR_PLAIN_TEMP_BYTES < 512 << 20, (
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
    state; the pair decays exp(G_t - G_s) formed whole inside a sub-block
    of 16 rows, and between sub-blocks as exp(G_t - G_r) exp(G_r - G_s)
    through the first row r of t's sub-block, two float32 matmuls over the
    channels: never the exponential of a positive number, s < r <= t) in
    the 9 ``K`` layers, the table's periodic part walked by a
    loop (the one-step kernel takes its layer as a prefetched scalar, the
    piece's rows of the pools ride the loop's carry). The piece's slot's
    rows are read once before the first layer and written once after the
    last: no copy of either state pool (2.5 GB), of the latent pool or of
    an expert stack, the pools aliased, and no more temporaries than the
    program without pieces plus half a MB a piece row and the one copy of
    the ``K`` in-projections the loop costs (a slot's conv windows read by
    a slice put the 81 MB pool's 3 columns on the lanes instead: 3.4 GB of
    padding, ``ops/kda.py slot_state``). Both window kernels' scoped VMEM
    is the compile itself. Nothing under ``kda_chunk_prefill`` walks a
    float32 array of chunk x chunk x 128 a head any more (the pair decays
    of every (t, s) of a chunk, 67 M elements a layer: two multiply-reduce
    fusions on the vector unit before PR 62); the largest is the diagonal
    sub-blocks' 16 x 16 x 128 a sub-block (a quarter), and the matmuls'
    s-side operand, the chunk's keys scaled once a sub-block
    ([4 chunks, 32 heads, 4 sub-blocks, 64, 128] float32: 16.8 MB a layer
    if the compiler keeps it, 4 times the keys), stays inside the pins."""
    text, carrying = _linear_decode_program(one_chip)(LATENT_PS)
    chunks, heads, Q, d = LATENT_PS // 64, 32, 64, 128
    most, shape = _largest_f32_under(text, "kda_chunk_prefill")
    assert most <= chunks * heads * Q * 16 * d < chunks * heads * Q * Q * d, (
        shape)
    assert _pair_forms_traced(LATENT_PS, heads) == {"16x16 + matmul"}
    assert carrying.alias_size_in_bytes >= 3.9e9
    assert (carrying.temp_size_in_bytes < LINEAR_PLAIN_TEMP_BYTES
            + PIECE_ROWS_BYTES + LINEAR_IN_PROJ_BYTES), (
        carrying.temp_size_in_bytes)


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
        _, pool, _, _, state = extend_step_forward(
            params, tokens, start, pool, None, table, cfg, write_ok=ok,
            ssm_state=state, state_slot=slot)
        return pool, state

    i32 = lambda *shape: sds(shape, jnp.int32)
    compiled = jax.jit(chunk, donate_argnums=(4, 6)).lower(
        params, i32(1, T), i32(1), i32(1), pool, i32(1, 64), state,
        i32()).compile()
    text = compiled.as_text()
    assert "mla_paged_attention_mq" in text and "moe_gmm_prefill" in text
    assert _pair_forms_traced(T, 32) == {"16x16 + matmul"}
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 512 << 20, (
        f"{mem.temp_size_in_bytes / 1e6:.1f} MB of temporaries")
    assert mem.alias_size_in_bytes >= 3.9e9
