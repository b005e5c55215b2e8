"""The parallel cell's serve programs (falcon-h1-34b-4l: attention AND a
Mamba-2 mixer in every layer, a query group of FIVE, 2 groups of state 256,
an in-projection 9,248 wide, a head of 261,120 rows).

Compiled by the TPU v5e compiler for a chip that is DESCRIBED, not attached
(libtpu is installed here); nothing runs, so these tests say nothing about
results or times: ``chip_smoke.py`` and the cell's own check hold results on
the real chip. The rules of ``tests/test_tpu_compile_hybrid.py`` hold here:
the topology is described inside the ``topo`` fixture, shapes are built in
the tests, and every compile asserts its Mosaic kernels.
"""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from distributed_llm_training_and_inference_system_tpu.serve.engine import (
    InferenceEngine)
from tpu_compile_support import (
    D,
    page_tokens,
    _no_copy_of,
    _sds,
    _ssm_decode_pool_compiles,
    _state_update_is_the_kernel,
)

ROOT = Path(__file__).resolve().parents[1]
PS = page_tokens(4)     # 4 K/V heads and no stated page: the rule's, 128
RIDE_ROWS = InferenceEngine.piece_rows(PS)          # a decode step's carry
PAGES = int(1.25e9) // (8192 * PS)  # 1.25 GB of 8,192 B a token: 1,192
MAXP = 4096 // PS       # max_seq_len 4096: 32 pages a slot


def _parallel_cell(one_chip):
    """(model config, slots, shapes of params / a page pool / the state
    pools) of the parallel cell as its configuration file states it: 128
    slots, 4 layers that each keep K/V pages AND a recurrent state."""
    from distributed_llm_training_and_inference_system_tpu.config.schema import (
        ModelConfig)
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    config = json.loads((ROOT / "benchmark" / "configs"
                         / "falcon-h1-34b-4l.json").read_text())
    cfg = ModelConfig.from_published(config)
    sds = _sds(one_chip)
    B = config["serve"]["max_batch_size"]
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda k: gpt.init(cfg, k, jnp.bfloat16),
                       jax.random.PRNGKey(0)))
    pool = sds((cfg.kv_layers, PAGES, cfg.num_kv_heads, PS, D), jnp.bfloat16)
    s = cfg.ssm
    state = {"conv": sds((cfg.ssm_layers, B, s.conv_kernel - 1,
                          s.conv_channels), jnp.bfloat16),
             "ssm": sds((cfg.ssm_layers, B, s.num_heads, s.head_dim,
                         s.state_size), jnp.float32)}
    return cfg, B, params, pool, state


STATE_POOL = 4 * 128 * 32 * 128 * 256 * 4           # 2.15 GB
# the stacks and pools no program may copy: the MLP's three [4, 5120,
# 21504] / [4, 21504, 5120] kernels (881 MB each), the head and the
# embedding (2.67 GB each), the state pool, a page pool (625 MB)
NO_COPY = ["bf16[4,5120,21504]", "bf16[4,21504,5120]", "bf16[5120,261120]",
           "bf16[261120,5120]", "f32[4,128,32,128,256]",
           f"bf16[4,{PAGES},4,{PS},128]"]
# ... and the ones the carrying program DOES copy once a dispatch, in its
# entry computation, outside its step loops, named here (as the hybrid and
# linear cells' are): the in-projections' stack [4, 5120, 9248], whose 9,248
# columns are no whole number of the chip's 128 lanes (72.25), and the q / k
# / v stacks ([4, 5120, 2560] and 2 x [4, 5120, 512]): the loop over the
# table's period takes a layer of each by a traced index and wants the
# other order. 526 MB read and written in ~1.3 ms of a dispatch of 12 steps
# (the plain program, which walks the table by a Python loop, copies none)
IN_PROJ_BYTES = 4 * 5120 * 9248 * 2                 # 379 MB
QKV_BYTES = 4 * 5120 * (2560 + 2 * 512) * 2         # 147 MB


@pytest.fixture(scope="module")
def decode_program(one_chip):
    """``decode_scan`` at the cell's shapes, 2 steps, with a piece of
    ``carry`` rows riding each step (0: the program without pieces):
    (optimised text, memory analysis)."""
    from distributed_llm_training_and_inference_system_tpu.serve.decode import (
        PIECE_META, decode_scan)
    cfg, B, params, pool, state = _parallel_cell(one_chip)
    sds = _sds(one_chip)
    K = 2

    def program(params, k_pages, v_pages, tokens, positions, tables, stops,
                keys, temp, top_k, top_p, state, ride=None):
        return decode_scan(params, tokens, positions, k_pages, v_pages,
                           tables, stops, keys, temp, top_k, top_p, cfg, K,
                           ssm_state=state, ride=ride)

    i32 = lambda *shape: sds(shape, jnp.int32)

    @functools.cache
    def compile_(carry):
        ride = (i32(K, PIECE_META + carry),) if carry else ()
        compiled = jax.jit(program, donate_argnums=(1, 2, 11)).lower(
            params, pool, pool, i32(B), i32(B), i32(B, MAXP), i32(B),
            sds((B, 2), jnp.uint32), sds((B,), jnp.float32), i32(B),
            sds((B,), jnp.float32), state, *ride).compile()
        text = compiled.as_text()
        assert "tpu_custom_call" in text and "paged_attention" in text
        # the piece's windows: the multi-query page kernel and the chunked
        # scan, under the names a prefill program's have, in ONE layer
        assert ("paged_attention_mq" in text) == bool(carry)
        assert ("ssm_scan_prefill" in text) == bool(carry)
        return text, compiled.memory_analysis()
    return compile_


def test_ssm_decode_kernel_updates_the_state_pool_in_place(one_chip, as_tpu):
    """The one-token update as a kernel on the cell's state pool (4 layers
    x 128 slots x 32 heads x [128, 256] float32 = 2.15 GB), 8 heads a grid
    step inside one B/C group of 16: Mosaic takes the [128, 128]
    transposes that turn ``dt x`` into columns and put a row's sums over
    the state back on the lanes."""
    _ssm_decode_pool_compiles(_sds(one_chip), 4, 128, 32, 128, 256, 2,
                              heads_a_block=8)


# temporaries of the program WITHOUT pieces (the slow case below reads them
# again; a 40 s compile on 3.6 cores that the driver's run no longer pays:
# PR 61)
PLAIN_TEMP_BYTES = 1_125_128_704


@pytest.mark.slow     # ~90 s: the carrying case below holds every property
def test_decode_program_moves_no_pool_and_no_stack(decode_program, as_tpu):
    """The multi-step decode program: both pools of every layer ride the
    carry and are written at [layer]; no copy of a page pool, of the state
    pool (2.15 GB), of an MLP stack, of the head or of the in-projections
    in the program, the donated pools aliased. Its temporaries (1.13 GB)
    are the sampler's: 134 MB of float32 logits over 261,120 rows, and the
    sort and top-k passes over them that a slot with a temperature takes
    (branches the cell's greedy traffic never runs, whose buffers the
    program still holds): under 1.3 GB, so that weights, pools and
    temporaries stay under 14 GB."""
    text, mem = decode_program(0)
    # (the 7.9 MB conv-window pool [4, 128, 3, 5120] IS re-laid at the
    # program's entry and exit, outside the step loop, once a dispatch: the
    # step wants its 3 columns off the lanes. 2 x 7.9 MB in ~12 steps)
    _no_copy_of(text, NO_COPY + ["bf16[4,5120,9248]"])
    _state_update_is_the_kernel(text, "f32[128,32,128,256]")
    assert mem.temp_size_in_bytes <= PLAIN_TEMP_BYTES < 1.3e9, (
        f"{mem.temp_size_in_bytes} bytes of temporaries")
    assert mem.alias_size_in_bytes >= STATE_POOL + 2 * 625e6


def test_carrying_decode_program_fits_the_chip(decode_program, as_tpu):
    """The decode program with a prompt's piece of 128 rows riding every
    step: in EVERY layer the piece goes through ``paged_attention_mq`` over
    its slot's pages and through ``ssm_scan_prefill`` from its slot's own
    float32 state. No copy of a pool, an MLP stack or the head; the copies
    it makes are named (``IN_PROJ_BYTES``, ``QKV_BYTES``) and are made once
    a dispatch, in the entry computation; no more temporaries than the
    plain program's plus a MB a piece row and those copies."""
    import re
    text, carrying = decode_program(RIDE_ROWS)
    _no_copy_of(text, NO_COPY, fused_into_at_most=32 << 20)
    _state_update_is_the_kernel(text, "f32[128,32,128,256]")
    # the named copies are the ENTRY computation's, not a step's
    entry = text[text.index("\nENTRY "):]
    for shape in ("bf16[4,5120,9248]", "bf16[4,5120,2560]"):
        copies = re.findall(rf" = {re.escape(shape)}\S* copy\(", text)
        assert len(copies) == len(re.findall(
            rf" = {re.escape(shape)}\S* copy\(", entry)) <= 1, shape
    assert carrying.alias_size_in_bytes >= STATE_POOL + 2 * 625e6
    assert (carrying.temp_size_in_bytes < PLAIN_TEMP_BYTES
            + (RIDE_ROWS << 20) + IN_PROJ_BYTES + QKV_BYTES), (
        carrying.temp_size_in_bytes)
    # weights 8.79 GB + pools 3.4 GB + temporaries fit the chip's 16 GB
    assert 8.79e9 + 3.4e9 + carrying.temp_size_in_bytes < 15.7e9


@pytest.mark.parametrize("bucket", [2048])
def test_cold_prefill_program_compiles(one_chip, as_tpu, bucket):
    """Cold prefill of the ladder's top rung: the dense forward over 2,048
    rows (the widest matmul in the benchmark, [2048, 5120] x [5120,
    21504]), every layer's K/V handed out for the pages and the slot's rows
    of both state pools written in place."""
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    cfg, B, params, pool, state = _parallel_cell(one_chip)
    sds = _sds(one_chip)

    def prefill(params, tokens, length, state, slot):
        live = (jnp.arange(bucket)[None] < length[:, None]).astype(jnp.int32)
        logits, (kd, vd), (tails, hs) = gpt.forward(
            params, tokens, cfg,
            kv_cache=gpt.init_kv_cache(cfg, 1, bucket, dtype=jnp.bfloat16),
            cache_offset=jnp.zeros((1,), jnp.int32),
            unembed_positions=length - 1, segment_ids=live,
            return_ssm_state=True)
        state = {"conv": state["conv"].at[:, slot].set(
                     tails[:, 0].astype(jnp.bfloat16)),
                 "ssm": state["ssm"].at[:, slot].set(hs[:, 0])}
        return logits, kd, vd, state

    compiled = jax.jit(prefill, donate_argnums=(3,)).lower(
        params, sds((1, bucket), jnp.int32), sds((1,), jnp.int32), state,
        sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "ssm_scan_prefill" in text
    _no_copy_of(text, NO_COPY)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2.5e9, f"{temp / 1e6:.1f} MB of temporaries"
