"""The short-conv cell's kernels and serve programs (lfm2-8b-a1b-16l: gated
short convolutions 3 : 1 with GQA 32 / 8 of head_dim 64, whose K/V lie in
PAIRS of heads on the pool's 128 lanes; 32 experts all held).

Compiled by the TPU v5e compiler for a chip that is DESCRIBED, not attached
(libtpu is installed here); nothing runs, so these tests say nothing about
results or times: ``chip_smoke.py`` and the cell's own check hold results on
the real chip. What interpret mode on the CPU cannot vouch for is here: that
Mosaic takes the page-streaming kernel over a pool of paired 64-wide heads
(T = 1 and a riding piece's window of 256 rows), that the decode program
with a piece riding fits the chip beside 10.8 GB of weights, and that it
moves no pool and no expert stack. The rules of
``tests/test_tpu_compile_hybrid.py`` hold: the topology is described inside
the ``topo`` fixture, shapes are built in the tests, and every compile
asserts its Mosaic kernels.
"""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from tpu_compile_support import _compile, _no_copy_of, _sds

ROOT = Path(__file__).resolve().parents[1]
CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "lfm2-8b-a1b-16l.json").read_text())
PS = CONFIG["serve"]["kv_block_size"]               # 256
MAXP = CONFIG["serve"]["max_seq_len"] // PS         # 8 pages a slot
# 3.0 GB of 8,192 B a token in pages of 256
PAGES = int(CONFIG["serve"]["kv_hbm_budget_gb"] * 1e9) // (8192 * PS)
POOL = (4, PAGES, 4, PS, 128)       # 4 layers, 4 PAIRS of heads, 128 lanes


def _model():
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    cfg = ModelConfig.from_published(CONFIG)
    assert cfg.layer_pattern == "CDCD*ECE" + "CECE*ECE" * 3
    return cfg


def test_the_cells_pool_is_pairs_of_heads():
    """What ``PagedKVCache`` allocates for this model: [layer, page, 4
    pairs, 256, 128], at the bytes of head_dim 64 (8 KB a token)."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        heads_a_row)
    cfg = _model()
    assert heads_a_row(cfg.num_kv_heads, cfg.head_dim) == 2
    assert heads_a_row(8, 128) == heads_a_row(32, 128) == 1     # never
    assert heads_a_row(3, 64) == 1          # an odd count cannot pair
    assert cfg.kv_bytes_per_token(2) == 8192
    assert POOL[2] * POOL[4] == cfg.num_kv_heads * cfg.head_dim
    assert PAGES == 1430


@pytest.mark.parametrize("T", [1, 256], ids=["decode", "riding-piece"])
def test_head_dim_64_takes_the_page_streaming_kernel(one_chip, as_tpu, T):
    """``paged_attention_multi`` with ``impl="auto"`` at the cell's layout
    (32 query heads of 64 over a pool of 4 pairs, 256 slots or one slot's
    window of 256 rows) resolves to the kernel and Mosaic compiles it: no
    gather of the table's pages, the pool never copied."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        paged_attention_multi)
    sds = _sds(one_chip)
    B = 256 if T == 1 else 1
    pool = sds(POOL, jnp.bfloat16)

    def call(q, kp, vp, tables, starts, layer):
        return paged_attention_multi(q, kp, vp, tables, starts, impl="auto",
                                     layer=layer)
    text = _compile(call, sds((B, T, 32, 64), jnp.bfloat16), pool, pool,
                    sds((B, MAXP), jnp.int32), sds((B,), jnp.int32),
                    sds((), jnp.int32)).as_text()
    assert "tpu_custom_call" in text
    assert ("paged_attention_mq" if T > 1 else "paged_attention") in text
    _no_copy_of(text, ["bf16[" + ",".join(map(str, POOL)) + "]",
                       "bf16[" + ",".join(map(str, POOL[1:])) + "]"])


@pytest.fixture(scope="module")
def decode_program(one_chip):
    """``decode_scan`` at the cell's shapes (256 slots, the donated K and V
    pools and the conv pool), 2 steps, with a piece of ``carry`` rows riding
    each step: (optimised text, memory analysis)."""
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        PIECE_META, decode_scan)
    cfg = _model()
    sds = _sds(one_chip)
    B, K = CONFIG["serve"]["max_batch_size"], 2
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda k: gpt.init(cfg, k, jnp.bfloat16),
                       jax.random.PRNGKey(0)))
    pool = sds(POOL, jnp.bfloat16)
    state = {"conv": sds((cfg.conv_layers, cfg.shortconv_kernel - 1, B,
                          cfg.hidden_size), jnp.bfloat16)}

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
        weights = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(params))
        return compiled.as_text(), compiled.memory_analysis(), weights
    return compile_


def test_carrying_decode_program_fits_the_chip(decode_program, as_tpu):
    """The decode program with a prompt's piece of 256 rows riding every
    step: the T = 1 page kernel over the paired pool in the attention
    layers, the piece through ``paged_attention_mq`` over its slot's pages
    and through the conv from its slot's two rows, the grouped matmul over
    all 32 experts. No copy of a page pool, of an expert stack or of the
    embedding; the conv pool, the K and V pools donated and aliased;
    weights + pools + temporaries inside the chip's 15.75 GiB. A step's one
    row a slot stages its sublane tile and not its page (PR 56): the
    program holds no whole page of each of the 256 slots, in the pool's
    dtype or as the float32 one-hot product that merged a row into it."""
    text, mem, weights = decode_program(PS)
    assert "tpu_custom_call" in text
    for kernel in ("paged_attention", "paged_attention_mq", "moe_gmm"):
        assert kernel in text, kernel
    for scope in ("shortconv_mixer", "shortconv_step", "shortconv_conv"):
        assert scope in text, scope
    _no_copy_of(text, ["bf16[" + ",".join(map(str, POOL)) + "]",
                       "bf16[" + ",".join(map(str, POOL[1:])) + "]",
                       "bf16[14,32,2048,1792]", "bf16[14,32,1792,2048]",
                       "bf16[65536,2048]"], fused_into_at_most=32 << 20)
    B, (_, _, pairs, rows, lanes) = CONFIG["serve"]["max_batch_size"], POOL
    for staged in (f"bf16[{B},{pairs},{rows},{lanes}]",
                   f"bf16[{B},{rows},{pairs},{lanes}]",
                   f"f32[{B},{rows},{pairs},{lanes}]"):
        assert staged not in text, f"a whole page a slot is staged: {staged}"
    assert f"bf16[{B},{pairs},16,{lanes}]" in text      # the tile a slot
    pools = 2 * 2 * POOL[0] * POOL[1] * POOL[2] * POOL[3] * POOL[4]
    conv = 12 * 2 * 256 * 2048 * 2
    assert 10.75e9 < weights < 10.9e9
    assert mem.alias_size_in_bytes >= pools + conv
    assert weights + pools + conv + mem.temp_size_in_bytes < 15.75 * 2 ** 30, (
        f"{mem.temp_size_in_bytes / 1e6:.1f} MB of temporaries beside "
        f"{(weights + pools + conv) / 1e9:.2f} GB")
