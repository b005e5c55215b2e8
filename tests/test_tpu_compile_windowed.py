"""The serve programs of a stack with WINDOW layers, at the cell's shapes
(``mellum2-12b-a2.5b-8l``: 6 window layers and 2 full ones, a ring pool and
a chain pool), compiled by the TPU v5e compiler for a chip that is
DESCRIBED, not attached. ``tests/test_tpu_compile_uniform.py`` has the rules
these files keep.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from tpu_compile_support import D, _sds

SLOTS, STEPS, PAGE, SPAN, BUDGET = 48, 8, 128, 16384, 3.0e9


def _cell_cfg():
    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    cfg = get_model_config("mellum2-12b-a2.5b")
    return dataclasses.replace(cfg, num_layers=8,
                               layer_types=cfg.layer_types[:8])


def _pools(cfg, sds, ring, slots=SLOTS, page=PAGE, budget=BUDGET):
    """The two donated pools as ``PagedKVCache`` sizes them: the ring pool
    exactly (slots x ring + scratch), the full pool from the rest."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        SplitPages)
    dtype = jnp.dtype(cfg.dtype)
    row = 2 * cfg.num_kv_heads * cfg.head_dim * dtype.itemsize
    n_win, n_full = cfg.window_layers, cfg.num_layers - cfg.window_layers
    ring_pages = slots * ring + 1
    pages = int((budget - n_win * row * page * ring_pages)
                // (n_full * row * page))
    shape = (cfg.num_kv_heads, page, cfg.head_dim)
    pool = SplitPages(sds((n_full, pages, *shape), dtype),
                      sds((n_win, ring_pages, *shape), dtype), ring,
                      tuple(t == "sliding" for t in cfg.layer_types))
    bytes_ = (n_full * pages + n_win * ring_pages) * row // 2 * page
    return pool, pages, bytes_


def _params(cfg, sds):
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    return jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda k: gpt.init(cfg, k, jnp.dtype(cfg.dtype)),
                       jax.random.PRNGKey(0)))


def _decode_program(cfg, sds, ring, piece, slots=SLOTS, steps=STEPS,
                    page=PAGE, span=SPAN):
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        PIECE_META, decode_scan)
    pool, pages, pool_bytes = _pools(cfg, sds, ring, slots, page)

    def program(params, k_pages, v_pages, tokens, positions, tables, stops,
                keys, temp, top_k, top_p, ride=None):
        return decode_scan(params, tokens, positions, k_pages, v_pages,
                           tables, stops, keys, temp, top_k, top_p, cfg,
                           steps, return_moe_stats=True, ride=ride)

    i32 = lambda *shape: sds(shape, jnp.int32)
    B = slots
    args = (_params(cfg, sds), pool, pool, i32(B), i32(B),
            i32(B, span // page + ring), i32(B), sds((B, 2), jnp.uint32),
            sds((B,), jnp.float32), i32(B), sds((B,), jnp.float32))
    return (jax.jit(program, donate_argnums=(1, 2)), args,
            (i32(steps, PIECE_META + piece),), pool_bytes)


def test_windowed_decode_program_updates_both_pools_in_place(one_chip, as_tpu):
    """The decode program the cell's engine jits (``decode_scan`` with a
    piece of 128 rows riding, 48 slots, 8 steps) at the cell's
    configuration: both donated pools (the ring pool 0.757 GB, the full
    pool 2.24 GB) are the carry of the step loop and of the scan over
    periods, a window layer writes and walks its plane of the ring pool and
    a full layer its plane of the chain pool, and the compiled program
    aliases all four arrays and holds nothing pool-sized beside them. Both
    page kernels are in it under their own names."""
    from distributed_llm_training_and_inference_system_tpu.serve.engine import (
        InferenceEngine)
    from distributed_llm_training_and_inference_system_tpu.serve.kv_cache import (
        WINDOW_CHUNK_PAGES, kv_row_bytes, page_size_by_rows, ring_pages)
    cfg = _cell_cfg()
    assert (cfg.window_layers, cfg.head_dim, cfg.dtype) == (6, D, "bfloat16")
    assert page_size_by_rows(kv_row_bytes(cfg),
                             InferenceEngine.RIDE_ROWS) == PAGE
    ring = ring_pages(cfg.sliding_window, PAGE,
                      WINDOW_CHUNK_PAGES * PAGE)
    assert ring == 10
    program, args, ride, pool_bytes = _decode_program(
        cfg, _sds(one_chip), ring, InferenceEngine.piece_rows(PAGE))
    compiled = program.lower(*args, *ride).compile()
    text = compiled.as_text()
    for kernel in ("window_attention", "window_attention_mq",
                   "paged_attention", "paged_attention_mq"):
        assert re.search(rf'(?<![A-Za-z_]){kernel}(?![A-Za-z_])', text), kernel
    memory = compiled.memory_analysis()
    assert 2.9e9 < 2 * pool_bytes <= BUDGET
    assert memory.alias_size_in_bytes >= 2 * pool_bytes
    # beside the pools: the re-laid q / k / v / o stacks of 8 layers and the
    # step's activations, nothing of a pool's size (the smaller pool is
    # 0.38 GB an array)
    assert memory.temp_size_in_bytes < 0.3e9, (
        f"{memory.temp_size_in_bytes / 1e6:.0f} MB of temporaries beside "
        f"pools of {2 * pool_bytes / 1e6:.0f} MB")
    # weights + pools + temporaries fit the chip's 15.75 GiB
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.75 * 2 ** 30)


@pytest.mark.slow      # (~45 s more at the run's very end: name it to run it)
def test_the_chunk_program_over_the_ring_compiles(one_chip, as_tpu):
    """A chunk of 256 rows (two pages) of ONE slot through
    ``extend_step_forward`` over the donated pools: the longest window a
    program of the cell writes into a ring of 10 pages; the window layers'
    multi-query kernel tiles its 256 rows."""
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        extend_step_forward)
    cfg = _cell_cfg()
    sds = _sds(one_chip)
    pool, _, pool_bytes = _pools(cfg, sds, 10)

    def chunk(params, tokens, start, m, k_pages, v_pages, table):
        ok = jnp.arange(256, dtype=jnp.int32)[None] < m[:, None]
        step = extend_step_forward(params, tokens, start, k_pages, v_pages,
                                   table, cfg, write_ok=ok)
        return step.k_pages, step.v_pages

    i32 = lambda *shape: sds(shape, jnp.int32)
    compiled = jax.jit(chunk, donate_argnums=(4, 5)).lower(
        _params(cfg, sds), i32(1, 256), i32(1), i32(1), pool, pool,
        i32(1, SPAN // PAGE + 10)).compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * pool_bytes
    # what it holds, as every uniform stack's program does, is the re-laid
    # q / k / v / o stacks (8 x 21.2 M parameters: 340 MB), and 256 rows'
    # activations; the smaller pool is 378 MB an array, and TWO arrays (K
    # and V) would have to be copied
    qkvo_stacks = 8 * 21_233_664 * 2
    assert memory.temp_size_in_bytes < qkvo_stacks + 0.1e9 < 2 * 0.378e9


def test_a_window_longer_than_the_ring_allows_is_refused():
    """A window of 384 rows over a ring of 10 pages of 128 would overwrite
    rows its own first query sees: refused where the program is built."""
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        extend_step_forward)
    cfg = _cell_cfg()
    sds = jax.ShapeDtypeStruct
    pool, _, _ = _pools(cfg, sds, 10, slots=2, budget=0.1e9)
    i32 = lambda *shape: sds(shape, jnp.int32)
    with pytest.raises(ValueError, match="would overwrite rows"):
        jax.eval_shape(
            lambda p, t, s, k, v, tb: extend_step_forward(
                p, t, s, k, v, tb, cfg).logits,
            _params(cfg, sds), i32(1, 384), i32(1), pool, pool,
            i32(1, SPAN // PAGE + 10))


@pytest.mark.parametrize("position,pages", [(0, 1), (1023, 8), (1024, 9),
                                            (1100, 9), (1151, 8), (1152, 9),
                                            (16000, 9)])
def test_the_window_kernels_page_loop_is_bounded_by_9(position, pages):
    """The window kernel's walk for ONE query at ``position`` over pages of
    128: from the page of its first visible key (position - 1,023) to its
    own, at most 9 whatever the position, where the full layers' kernel
    walks position // 128 + 1."""
    window, page = 1024, 128
    first = max(position - (window - 1), 0) // page
    last = position // page
    assert last - first + 1 == pages <= 9
