"""The hybrid cell's kernels and serve programs (nemotron-3-nano-30b-a3b-14l-ep2).

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
    D,
    page_tokens,
    table_width,
    _compile,
    _sds,
    _no_copy_of,
    _ssm_decode_pool_compiles,
    _state_update_is_the_kernel,
)


# -- the hybrid cell (nemotron-3-nano-30b-a3b-14l-ep2) ---------------------------

def _hybrid_cell(one_chip):
    """(model config, shapes of params / page pool / state pools) of the
    hybrid cell as its configuration file states it: 64 slots, the pages
    its budget buys at the size the rule gives 2 K/V heads (128 tokens), 6
    state-space layers, 64 of 128 experts held."""
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
    PS = page_tokens(cfg.num_kv_heads)
    pages = (int(config["serve"]["kv_hbm_budget_gb"] * 1e9)
             // (cfg.kv_bytes_per_token(2) * PS))
    pool = sds((cfg.kv_layers, pages, cfg.num_kv_heads, PS, D), jnp.bfloat16)
    s = cfg.ssm
    state = {"conv": sds((cfg.ssm_layers, B, s.conv_kernel - 1,
                          s.conv_channels), jnp.bfloat16),
             "ssm": sds((cfg.ssm_layers, B, s.num_heads, s.head_dim,
                         s.state_size), jnp.float32)}
    return cfg, B, params, pool, state


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
            params, pool, pool, i32(B), i32(B),
            i32(B, table_width(pool.shape[3])), i32(B),
            sds((B, 2), jnp.uint32), sds((B,), jnp.float32), i32(B),
            sds((B,), jnp.float32), state, *ride).compile()
        text = compiled.as_text()
        assert "moe_gmm" in text and "paged_attention" in text
        # the piece's windows: the multi-query page kernel and the chunked
        # scan, under the names a prefill program's have
        assert ("paged_attention_mq" in text) == bool(carry)
        assert ("ssm_scan_prefill" in text) == bool(carry)
        _state_update_is_the_kernel(text, "f32[64,64,64,128]")
        _no_copy_of(text, ["bf16[6,64,1856,2688]", "f32[6,64,64,64,128]",
                           "bf16[%s]" % ",".join(map(str, pool.shape))]
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


def test_ssm_decode_kernel_updates_the_state_pool_in_place(one_chip, as_tpu):
    """The one-token update as a kernel on the hybrid cell's state pool (6
    layers x 64 slots x 64 heads x [64, 128] float32 = 0.8 GB): 32 heads
    = 4 B/C groups a grid step, a row of 128 (head, channel) pairs two
    whole heads."""
    _ssm_decode_pool_compiles(_sds(one_chip), 6, 64, 64, 64, 128, 8,
                              heads_a_block=32)


# temporaries of the program WITHOUT pieces (the slow case below reads them
# again; 59 s of compile that the driver's run no longer pays: PR 53)
HYBRID_PLAIN_TEMP_BYTES = 126_138_368


@pytest.mark.slow     # ~60 s: the carrying case below holds every property
def test_hybrid_decode_program_moves_no_pool_and_no_stack(one_chip, as_tpu):
    """The multi-step decode program at the hybrid cell's shapes: the page
    pools hold the two attention layers alone, the state pools ride the
    carry and are written at [layer], the expert stacks stay whole: no
    temporary the size of a state pool (0.82 GB), of an expert stack
    (3.8 GB) or of a layer's slab of state (134 MB) beyond the step's own
    working set, and no copy of any of them in the program."""
    _, mem = _hybrid_decode_program(one_chip)(0)
    assert mem.temp_size_in_bytes <= HYBRID_PLAIN_TEMP_BYTES
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
    (PR 44): 128 rows (one page of 128: ONE chunk of the scan) beside the
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
    from distributed_llm_training_and_inference_system_tpu.serve.engine import (
        InferenceEngine)
    rows = InferenceEngine.piece_rows(page_tokens(2))
    text, carrying = _hybrid_decode_program(one_chip)(rows)
    assert carrying.alias_size_in_bytes >= HYBRID_STATE_POOL
    assert (carrying.temp_size_in_bytes < HYBRID_PLAIN_TEMP_BYTES
            + (rows << 20) + HYBRID_IN_PROJ_BYTES), (
        carrying.temp_size_in_bytes)

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
