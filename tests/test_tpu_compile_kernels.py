"""The main path's Pallas kernels, each alone, at the cells' shapes.

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

import functools

import jax
import jax.numpy as jnp
import pytest

from tpu_compile_support import (
    LAYOUTS,
    PAGES,
    D,
    _compile,
    _kernel_grids,
    _sds,
    _pages,
    _cell_call,
    table_width,
)


@pytest.mark.parametrize("page", PAGES)
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_paged_decode_kernel_compiles(one_chip, as_tpu, layout, kv, page):
    """Single-query decode attention: 8 slots, 2,048 tokens of pages of 64
    or 128 a slot."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        paged_attention)
    nq, nkv = LAYOUTS[layout]
    sds = _sds(one_chip)
    B, maxp = 8, table_width(page)
    pages = _pages(sds, B * maxp + 1, nkv, kv, page=page)
    _compile(functools.partial(paged_attention, impl="auto"),
             sds((B, nq, D), jnp.bfloat16), pages, pages,
             sds((B, maxp), jnp.int32), sds((B,), jnp.int32))


@pytest.mark.parametrize("page", PAGES)
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_paged_decode_kernel_compiles_at_the_cells_shapes(one_chip, as_tpu,
                                                          layout, kv, page):
    """The decode kernel as the serving cells run it: 32 slots, a block
    table of 2,048 tokens, the whole [L, NP, ...] pool of the cell's budget
    (715 pages of 64) with a traced layer index. Its grid is one step a
    slot: the page axis is a loop inside."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        paged_attention)
    call, args = _cell_call(paged_attention, _sds(one_chip), layout, kv,
                            lambda slots, nq: (slots, nq, D), page=page)
    compiled = _compile(call, *args)
    assert "paged_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 32 * 4096, \
        "a pool-sized temporary beside the kernel"
    assert _kernel_grids(call, *args) == [(32, 1)]


@pytest.mark.parametrize("page", PAGES)
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("window", [8, 64, 128, 256, 512])
def test_paged_multi_query_kernel_compiles(one_chip, as_tpu, window, layout,
                                           kv, page):
    """Every window the engine can hand the multi-query kernel at default
    settings: the speculative verify window (8, all slots) and the
    cached-prefix / chunked-prefill suffix buckets 64..512 (one slot;
    engine._suffix_bucket, prefill_chunk 256), over pages of 64 and of 128
    (``_query_tile`` halves the tile where the page doubles)."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        paged_attention_multi)
    nq, nkv = LAYOUTS[layout]
    sds = _sds(one_chip)
    B, maxp = 8 if window == 8 else 1, table_width(page)
    pages = _pages(sds, 8 * maxp + 1, nkv, kv, page=page)
    _compile(functools.partial(paged_attention_multi, impl="auto"),
             sds((B, window, nq, D), jnp.bfloat16), pages, pages,
             sds((B, maxp), jnp.int32), sds((B,), jnp.int32))


@pytest.mark.parametrize("page", PAGES)
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_paged_multi_query_kernel_compiles_at_the_cells_shapes(
        one_chip, as_tpu, layout, kv, page):
    """The speculative-verify window (8 tokens) over all 32 slots of a
    cell's pool, and a 512-token suffix tiled along the query axis (8 x 64
    over pages of 64, 16 x 32 over pages of 128: the same score tile): the
    grid is (slots, query tiles) and never the table's width."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        paged_attention_multi)
    call, args = _cell_call(paged_attention_multi, _sds(one_chip), layout, kv,
                            lambda slots, nq: (slots, 8, nq, D), page=page)
    _compile(call, *args)
    assert _kernel_grids(call, *args) == [(32, 1)]
    nq, nkv = LAYOUTS[layout]
    sds = _sds(one_chip)
    maxp = table_width(page)
    pages = _pages(sds, 8 * maxp + 1, nkv, kv, page=page)
    assert _kernel_grids(
        functools.partial(paged_attention_multi, impl="auto"),
        sds((1, 512, nq, D), jnp.bfloat16), pages, pages,
        sds((1, maxp), jnp.int32), sds((1,), jnp.int32)) == [
            (1, 8 * page // 64)]


# the layouts whose default page the rule takes to its cap: Falcon-H1's
# 20 / 4, SDAR's 32 / 4 under the block rule, Nemotron-3-Nano's 32 / 2
FEW_KV_HEADS = {"gqa20x4": (20, 4, 0), "gqa32x4-blocks-of-4": (32, 4, 4),
                "gqa32x2": (32, 2, 0)}


@pytest.mark.parametrize("layout,window", [
    (layout, window) for layout, (_, _, block) in FEW_KV_HEADS.items()
    for window in (1, 8, 128, 256, 512)
    if not window % max(block, 1)])  # a diffusion model's window: whole blocks
def test_paged_kernels_compile_over_few_kv_heads_at_pages_of_128(
        one_chip, as_tpu, layout, window):
    """4 and 2 K/V heads over the 128-token pages the rule gives them: the
    decode step (window 1; the diffusion model's is a window of blocks, 8
    rows and more), a 128-row piece of a carrying step over all 64 slots,
    and the suffix buckets of one slot."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention_pallas import (
        paged_attention_pallas_multi)
    nq, nkv, block = FEW_KV_HEADS[layout]
    sds = _sds(one_chip)
    page = 128
    B, maxp = 64 if window <= 128 else 1, table_width(page)
    pages = _pages(sds, B * maxp + 1, nkv, "bf16", page=page)
    compiled = _compile(
        functools.partial(paged_attention_pallas_multi, block=block),
        sds((B, window, nq, D), jnp.bfloat16), pages, pages,
        sds((B, maxp), jnp.int32), sds((B,), jnp.int32))
    assert ("paged_attention_blk" if block else
            "paged_attention" if window == 1 else
            "paged_attention_mq") in compiled.as_text()


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
