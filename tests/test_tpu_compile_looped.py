"""The looped stack's serve programs, at the cell's shapes (``ouro-2.6b``:
48 layers walked 4 times, a pool of 192 planes), compiled by the TPU v5e
compiler for a chip that is DESCRIBED, not attached; and what the loop must
not cost a stack that is walked once. ``tests/test_tpu_compile_uniform.py``
has the rules these files keep.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from tpu_compile_support import D, _sds


def _decode_program(cfg, sds, slots, steps, pages, page, table, piece):
    """``decode_scan`` over abstract weights and donated pools of
    ``cfg.kv_layers`` planes: (the jitted program, its arguments without a
    piece, the piece's)."""
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        PIECE_META, decode_scan)
    dtype = jnp.dtype(cfg.dtype)
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda k: gpt.init(cfg, k, dtype),
                       jax.random.PRNGKey(0)))
    pool = sds((cfg.kv_layers, pages, cfg.num_kv_heads, page, cfg.head_dim),
               dtype)

    def program(params, k_pages, v_pages, tokens, positions, tables, stops,
                keys, temp, top_k, top_p, ride=None):
        return decode_scan(params, tokens, positions, k_pages, v_pages,
                           tables, stops, keys, temp, top_k, top_p, cfg,
                           steps, ride=ride)

    i32 = lambda *shape: sds(shape, jnp.int32)
    B = slots
    args = (params, pool, pool, i32(B), i32(B), i32(B, table), i32(B),
            sds((B, 2), jnp.uint32), sds((B,), jnp.float32), i32(B),
            sds((B,), jnp.float32))
    return (jax.jit(program, donate_argnums=(1, 2)), args,
            (i32(steps, PIECE_META + piece),))


def test_looped_decode_program_updates_the_pool_in_place(one_chip, as_tpu):
    """The decode program the cell's engine jits (``decode_scan`` with a
    piece of 128 rows riding, 16 slots, 8 steps) at the PUBLISHED
    configuration, all 48 layers and 4 passes, donated pools of the cell's
    8.0 GB (79 pages of 64 tokens x 192 planes, 3.98 GB each): the pools
    are the carry of the step loop, the pass loop and the layer loop, every
    (pass, layer) writes and reads plane ``pass * 48 + layer``, and the
    compiled program aliases both pools and holds nothing pool-sized beside
    them (at 8 GB of pool a second copy of one does not fit the chip: the
    compiler would refuse the program). What it does hold, as every uniform
    stack's decode program does, is the re-laid q / k / v stacks (403 MB
    each here; PERF.md 5)."""
    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    from distributed_llm_training_and_inference_system_tpu.serve.engine import (
        InferenceEngine)
    from distributed_llm_training_and_inference_system_tpu.serve.kv_cache import (
        kv_row_bytes, page_size_by_rows)
    cfg = get_model_config("ouro-2.6b")
    assert (cfg.kv_layers, cfg.head_dim, cfg.dtype) == (192, D, "bfloat16")
    page = page_size_by_rows(kv_row_bytes(cfg), InferenceEngine.RIDE_ROWS)
    pages = int(8.0e9 // (cfg.kv_bytes_per_token() * page))
    assert (page, pages) == (64, 79)
    program, args, ride = _decode_program(
        cfg, _sds(one_chip), slots=16, steps=8, pages=pages, page=page,
        table=1024 // page, piece=InferenceEngine.piece_rows(page))
    compiled = program.lower(*args, *ride).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention_mq" in text
    pool_bytes = 192 * pages * 16 * page * D * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * pool_bytes
    qkv_stacks = 3 * 48 * 2048 * 2048 * 2
    assert memory.temp_size_in_bytes < qkv_stacks + pool_bytes // 16, (
        f"{memory.temp_size_in_bytes / 1e6:.0f} MB of temporaries beside a "
        f"pool of {pool_bytes / 1e6:.0f} MB")
    # weights + pools + temporaries fit the chip's 15.75 GiB
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.75 * 2 ** 30)


@pytest.mark.parametrize("carrying,whiles", [(False, 4), (True, 6)])
def test_a_stack_walked_once_gets_no_pass_loop(carrying, whiles):
    """``mistral-7b``'s stack in small (GQA 32 / 8, one pass, plain norms)
    lowers to the ``while`` ops it lowered to before a stack could be
    looped (the step loop, the layer scan, the sampler's two; a riding
    program's second body's two more): no outer loop of trip count 1. The
    same program of the looped test model has one more a body, and the
    sandwich's norms twice the ``rsqrt``s a layer."""
    from distributed_llm_training_and_inference_system_tpu.config.presets import (
        get_model_config)
    once = dataclasses.replace(
        get_model_config("mistral-7b"), num_layers=3, hidden_size=128,
        ffn_size=256, head_dim=16, vocab_size=320, dtype="float32")
    looped = get_model_config("ouro-test")

    def lowered(cfg):
        program, args, ride = _decode_program(
            cfg, jax.ShapeDtypeStruct, slots=4, steps=4, pages=33, page=8,
            table=8, piece=16)
        return program.lower(*args, *(ride if carrying else ())).as_text()
    text = lowered(once)
    assert text.count("stablehlo.while") == whiles
    bodies = 2 if carrying else 1
    # attn_norm, mlp_norm a layer body; the final norm a step body
    assert text.count("rsqrt") == 3 * bodies
    text = lowered(looped)
    assert text.count("stablehlo.while") == whiles + bodies
    assert text.count("rsqrt") == 5 * bodies
