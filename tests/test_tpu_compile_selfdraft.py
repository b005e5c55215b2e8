"""The self-drafting cell's step (JoyAI-LLM-Flash, 8 layers + the
prediction module, 128 of 256 experts held).

Compiled by the TPU v5e compiler for a chip that is DESCRIBED, not attached;
nothing runs (``tests/test_tpu_compile_latent.py`` has the rules these files
keep). ONE compile: the draft-and-verify program is the carrying program of
this cell (the main stack's window of two rows a slot, acceptance, the
module's window, both head passes, the pool donated and carried through the
scan), and what it holds is the property the plain programs' cases hold
elsewhere: it fits the chip and moves no pool and no expert stack.
"""

import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from tpu_compile_support import LATENT_PS, _no_copy_of, _sds

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "benchmark"
                     / "configs" / "joyai-llm-flash-8l-ep2.json").read_text())


def test_the_draft_and_verify_step_compiles_and_fits_the_chip(one_chip,
                                                              as_tpu):
    """``draft_verify_scan`` at the cell's shapes (published widths, 1 dense
    + 7 expert layers + the module, 64 slots, a donated pool of 9 layers x
    1,017 pages of 256 rows of 640): the window kernel in every cached
    layer, the grouped matmul over the held experts, no copy of the
    pool, of a layer's slab of it or of an expert stack, and weights
    + pool + temporaries inside the chip's 15.75 GiB."""
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig, ServeConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        draft_verify_scan)
    from benchmark import harness
    serve = ServeConfig(model="joyai", **CONFIG["serve"])
    cfg = dataclasses.replace(
        ModelConfig.from_dict(harness.model_dict(CONFIG)), dtype="bfloat16")
    assert cfg.mtp_layers == 1 and cfg.kv_layers == 9
    B, K, dtype = serve.max_batch_size, 2, jnp.bfloat16
    row_bytes = cfg.kv_layers * 640 * 2
    pages = int(serve.kv_hbm_budget_gb * 1e9) // (LATENT_PS * row_bytes)
    max_pages = serve.max_seq_len // LATENT_PS
    sds = _sds(one_chip)
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda k: gpt.init(cfg, k, dtype), jax.random.PRNGKey(0)))
    pool = sds((cfg.kv_layers, pages, 1, LATENT_PS, 640), dtype)

    def program(params, pool, tokens, drafts, positions, tables, stops, keys,
                temp, top_k, top_p):
        return draft_verify_scan(params, (tokens, drafts), positions, pool,
                                 None, tables, stops, keys, temp, top_k,
                                 top_p, cfg, K, return_moe_stats=True)

    def i32(*shape):
        return sds(shape, jnp.int32)
    compiled = jax.jit(program, donate_argnums=(1,)).lower(
        params, pool, i32(B), i32(B), i32(B), i32(B, max_pages), i32(B),
        sds((B, 2), jnp.uint32), sds((B,), jnp.float32), i32(B),
        sds((B,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "mla_paged_attention_mq" in text and "moe_gmm" in text
    for scope in ("mtp_embed_proj", "mtp_layer", "mtp_head", "draft_verify"):
        assert scope in text, scope
    _no_copy_of(text, [f"bf16[{cfg.kv_layers},{pages},1,256,640]",
                       f"bf16[{pages},1,256,640]",
                       "bf16[8,128,2048,768]", "bf16[8,128,768,2048]"])
    # the window of two rows stages the tiles it touches (two of 16 rows a
    # slot), merged in bfloat16: no two whole pages a slot, in float32 (84
    # MB a layer until PR 54) or in the pool's dtype
    assert "f32[64,512,1,640]" not in text and "[64,2,1,256,640]" not in text
    mem = compiled.memory_analysis()
    pool_bytes = cfg.kv_layers * pages * LATENT_PS * 640 * 2
    # 294 MB here: the q_b and kv_b stacks laid out for the absorbed form
    # once a DISPATCH (170 + 76 MB, as the latent cell's carrying program
    # copies its own); a layer's window write stages 2.6 MB (345 MB with
    # two whole pages a slot merged in float32, PR 53)
    assert mem.temp_size_in_bytes < 320 << 20, (
        f"the step holds {mem.temp_size_in_bytes / 1e6:.1f} MB of "
        "temporaries")
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert 10.8e9 < weights < 10.95e9
    assert weights + pool_bytes + mem.temp_size_in_bytes < 15.75 * 2 ** 30


@pytest.mark.parametrize("pool, T, maxP", [
    ((9, 1017, 1, LATENT_PS, 640), 2, 49),      # this cell's latent pool
    ((7, 2179, 4, 64, 128), 8, 32),             # the diffusion cell's K or V
], ids=["latent-window-2", "kv-window-8"])
def test_a_short_windows_write_stages_tiles_in_place(one_chip, pool, T,
                                                     maxP):
    """``write_window_to_pages`` alone over a donated pool carried through
    a scan over its layers, at the two cells' shapes whose step is a short
    window: the tile gather and scatter leave the pool where it stands (no
    copy of it or of a layer's slab) and stage tiles, not pages (the
    temporaries are two tiles a slot and the masks: under 8 MB, where two
    whole pages a slot in float32 were 84 MB)."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        write_window_to_pages)
    sds = _sds(one_chip)
    L, _, Nkv, _, D = pool

    def program(pool, rows, tables, starts, ok):
        def body(pool, layer):
            return write_window_to_pages(pool, rows, tables, starts, ok,
                                         layer), None
        return jax.lax.scan(body, pool, jnp.arange(L, dtype=jnp.int32))[0]
    compiled = jax.jit(program, donate_argnums=(0,)).lower(
        sds(pool, jnp.bfloat16), sds((64, T, Nkv, D), jnp.bfloat16),
        sds((64, maxP), jnp.int32), sds((64,), jnp.int32),
        sds((64, T), jnp.bool_)).compile()
    text = compiled.as_text()
    shape = ",".join(map(str, pool))
    _no_copy_of(text, [f"bf16[{shape}]", f"bf16[{shape.split(',', 1)[1]}]"])
    assert f"bf16[64,2,{Nkv},16,{D}]" in text      # two tiles a slot
    assert "f32[64," not in text                   # merged in bfloat16
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * math.prod(pool)
    assert mem.temp_size_in_bytes < 8 << 20, mem.temp_size_in_bytes
