"""The sessions cell's serve programs (Solar-Open2-250B, one period of 4
layers, 40 of 320 experts): delta-rule layers at 64 heads beside ONE gated
softmax layer over plain K/V pages, and the two copies of a snapshot pool.

Compiled by the TPU v5e compiler for a chip that is DESCRIBED, not attached
(libtpu is installed here); nothing runs. The rules of the
``tests/test_tpu_compile_*.py`` files are in ``test_tpu_compile_linear.py``.
"""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from tpu_compile_support import (
    _largest_f32_under,
    _no_copy_of,
    _pair_forms_traced,
    _sds,
)

PS = 256


def _cell(one_chip):
    """(model config, serve table, shapes of params / K pool / V pool /
    state pools / snapshot pools) as the configuration file states them."""
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    config = json.loads((Path(__file__).resolve().parents[1] / "benchmark"
                         / "configs" / "solar-open2-250b-4l-ep8.json"
                         ).read_text())
    cfg, serve = ModelConfig.from_published(config), config["serve"]
    sds = _sds(one_chip)
    B, bf16 = serve["max_batch_size"], jnp.bfloat16
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda k: gpt.init(cfg, k, bf16), jax.random.PRNGKey(0)))
    pages = int(serve["kv_hbm_budget_gb"] * 1e9
                // (cfg.kv_bytes_per_token(2) * PS))
    pool = sds((cfg.kv_layers, pages, cfg.num_kv_heads, PS, cfg.head_dim),
               bf16)
    k = cfg.kda

    def pools(n):
        return {"conv": sds((cfg.kda_layers, k.conv_kernel - 1, n,
                             k.conv_channels), bf16),
                "ssm": sds((cfg.kda_layers, n, k.num_heads, k.head_dim,
                            k.head_dim), jnp.float32)}
    return cfg, serve, params, pool, pools(B), pools(
        serve["state_snapshot_entries"])


@functools.cache
def _decode_program(one_chip, carry):
    """``decode_scan`` at the cell's shapes, 2 steps, with a piece of
    ``carry`` rows riding each step (what the cell's engine jits as
    ``_decode_impl_n``): (its text, its memory analysis)."""
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        PIECE_META, decode_scan)
    cfg, serve, params, pool, state, _ = _cell(one_chip)
    sds, B, K = _sds(one_chip), serve["max_batch_size"], 2

    def program(params, kp, vp, tokens, positions, tables, stops, keys, temp,
                top_k, top_p, state, ride):
        return decode_scan(params, tokens, positions, kp, vp, tables, stops,
                           keys, temp, top_k, top_p, cfg, K,
                           return_moe_stats=True, ssm_state=state, ride=ride)

    i32 = lambda *shape: sds(shape, jnp.int32)
    compiled = jax.jit(program, donate_argnums=(1, 2, 11)).lower(
        params, pool, pool, i32(B), i32(B),
        i32(B, serve["max_seq_len"] // PS), i32(B), sds((B, 2), jnp.uint32),
        sds((B,), jnp.float32), i32(B), sds((B,), jnp.float32), state,
        i32(K, PIECE_META + carry)).compile()
    return compiled.as_text(), compiled.memory_analysis()


def test_the_riding_decode_program_fits_the_chip(one_chip, as_tpu):
    """The cell's decode program with ONE page of 256 prompt rows riding
    every step: the one-step delta-rule kernel at 64 heads and the chunked
    form from the slot's own state in the 3 ``K`` layers, the paged kernel
    (one query a slot, 8 query heads a K/V head) and its multi-query form
    over the piece's window in the softmax layer, the gate, the grouped
    matmuls. The pools ride the carry in place (K and V pages 2 x 2.15 GB,
    the 0.8 GB state pool): no copy of either, nor of an expert stack.
    The chunked form's pair products walk no float32 array of chunk x chunk
    x 128 a head (134 M elements a layer at 64 heads, on the vector unit
    before PR 62): sub-blocks of 16 x 16 x 128 on the diagonal, float32
    matmuls between sub-blocks whose s-side operand is the chunk's keys
    scaled once a sub-block ([4 chunks, 64 heads, 4 sub-blocks, 64, 128]
    float32: 33.5 MB a layer if the compiler keeps it; 134 MB in the chunk
    program of 1,024 rows, inside its pin below)."""
    cfg, serve, *_ = _cell(one_chip)
    text, mem = _decode_program(one_chip, PS)
    chunks, heads, Q, d = PS // 64, cfg.kda.num_heads, 64, cfg.kda.head_dim
    most, shape = _largest_f32_under(text, "kda_chunk_prefill")
    assert most <= chunks * heads * Q * 16 * d < chunks * heads * Q * Q * d, (
        shape)
    assert _pair_forms_traced(PS, heads) == {"16x16 + matmul"}
    for kernel in ("moe_gmm", "paged_attention", "paged_attention_mq",
                   "kda_decode", "kda_chunk_prefill", "attn_gate"):
        assert kernel in text, kernel
    B, pages = serve["max_batch_size"], int(
        serve["kv_hbm_budget_gb"] * 1e9 // (4096 * PS))
    _no_copy_of(text, [f"f32[3,{B},64,128,128]", f"f32[{B},64,128,128]",
                       f"bf16[1,{pages},8,256,128]",
                       "bf16[4,40,4096,1280]", "bf16[4,40,1280,4096]"],
                fused_into_at_most=32 << 20)
    assert mem.alias_size_in_bytes >= 2 * pages * 8 * 256 * 128 * 2
    assert mem.temp_size_in_bytes < 1 << 30, (
        f"{mem.temp_size_in_bytes / 1e6:.1f} MB of temporaries")


def test_the_chunk_program_reads_a_slots_state_once(one_chip, as_tpu):
    """The chunk program (1,024 rows of ONE slot's prompt over the K/V
    pages, the slot's delta-rule state and conv window carried) at the
    cell's shapes: under 1 GB of temporaries beside 13 GB of weights and
    pools."""
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        extend_step_forward)
    cfg, serve, params, pool, state, _ = _cell(one_chip)
    sds, T = _sds(one_chip), serve["chunked_prefill_tokens"]

    def chunk(params, tokens, start, m, kp, vp, table, state, slot):
        ok = jnp.arange(T)[None] < m[:, None]
        out = extend_step_forward(
            params, tokens, start, kp, vp, table, cfg, write_ok=ok,
            ssm_state=state, state_slot=slot)
        return out.k_pages, out.v_pages, out.state

    i32 = lambda *shape: sds(shape, jnp.int32)
    compiled = jax.jit(chunk, donate_argnums=(4, 5, 7)).lower(
        params, i32(1, T), i32(1), i32(1), pool, pool,
        i32(1, serve["max_seq_len"] // PS), state, i32()).compile()
    text = compiled.as_text()
    assert "paged_attention_mq" in text and "moe_gmm_prefill" in text
    assert _pair_forms_traced(T, cfg.kda.num_heads) == {"16x16 + matmul"}
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30, (
        f"{mem.temp_size_in_bytes / 1e6:.1f} MB of temporaries")


@pytest.mark.parametrize("which", ["take", "arm"])
def test_a_snapshot_copy_moves_one_slots_rows(one_chip, which):
    """Each copy of a snapshot is a program of a slot's 13 MB: the pool it
    writes is aliased, and nothing pool-sized is temporary."""
    from distributed_llm_training_and_inference_system_tpu.ops import kda
    *_, state, snaps = _cell(one_chip)
    sds = _sds(one_chip)
    fn, donate = ((kda.kda_snapshot_take, (2, 3)) if which == "take"
                  else (kda.kda_snapshot_arm, (0, 1)))
    compiled = jax.jit(fn, donate_argnums=donate).lower(
        state["conv"], state["ssm"], snaps["conv"], snaps["ssm"],
        sds((), jnp.int32), sds((), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    written = snaps if which == "take" else state
    assert mem.alias_size_in_bytes >= 3 * written["ssm"].shape[1] * (
        64 * 128 * 128 * 4)
    assert mem.temp_size_in_bytes < 64 << 20, mem.temp_size_in_bytes
