"""The four-chip training cell's step and the diffusion cell's kernel and program.

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

import re

import jax
import jax.numpy as jnp
import pytest

from tpu_compile_support import (
    D,
    page_tokens,
    table_width,
    _compile,
    _no_copy_of,
    _sds,
)


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

# the SDAR cell's pool: 4 K/V heads and no stated page, so the rule's (128
# tokens), and the pages 2.0 GB buy of 7 layers x 2,048 B a token (1,089;
# 2,179 of 64)
PS = page_tokens(4)
MAXP = table_width(PS)
SDAR_PAGES = int(2.0e9) // (7 * 2048 * PS)


@pytest.mark.parametrize("B, T", [(64, 4), (64, 8), (1, 256), (1, 512)],
                         ids=["one-block", "denoise-window", "suffix-256",
                              "suffix-512"])
def test_block_rule_page_kernel_compiles_at_the_cells_shapes(one_chip, as_tpu,
                                                             B, T):
    """The page kernel under the block rule (``paged_attention_blk``) on
    the SDAR cell's pool (7 layers, ``SDAR_PAGES``, GQA 32 / 4): one block and
    the denoise window of two over 64 slots, and a prefill window of many."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        paged_attention_multi)
    sds = _sds(one_chip)
    pool = sds((7, SDAR_PAGES, 4, PS, D), jnp.bfloat16)

    def call(q, kp, vp, tables, starts, layer):
        return paged_attention_multi(q, kp, vp, tables, starts, impl="auto",
                                     layer=layer, block=4)
    compiled = _compile(call, sds((B, T, 32, D), jnp.bfloat16), pool, pool,
                        sds((B, MAXP), jnp.int32), sds((B,), jnp.int32),
                        sds((), jnp.int32))
    assert "paged_attention_blk" in compiled.as_text()


def test_diffusion_decode_program_fits_the_chip(one_chip, as_tpu):
    """The denoise dispatch of the SDAR cell (published widths, 7 layers,
    64 slots x 2 blocks of 4 rows, 8 forwards): it compiles for the chip,
    updates the pools in place, holds no layer's 1.2 GB of experts as a
    temporary, runs the head over the second block's 256 rows alone, and
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
    B, Bd, num_pages = 64, cfg.diffusion.block_length, SDAR_PAGES
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
        params, pool, pool,
        (i32(B, 2 * Bd), i32(B, Bd), i32(B), sds((B,), jnp.bool_)), i32(B),
        i32(B, MAXP), i32(B), sds((B, 2), jnp.uint32),
        sds((B,), jnp.float32), i32(B), sds((B,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "paged_attention_blk" in text and "moe_gmm_prefill" in text
    # the head and the sampler see the rows that draw tokens: no array of
    # the window's 512 rows by the vocabulary
    V = cfg.vocab_size
    assert f"[{B * Bd},{V}]" in text or f"[{B},{Bd},{V}]" in text
    assert f"[{2 * B * Bd},{V}]" not in text
    assert f"[{B},{2 * Bd},{V}]" not in text
    # ... and the pools are written where they stand: no copy of one
    shape = ",".join(map(str, pool.shape))
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(rf"= bf16\[{shape}\]\S* copy\(", line)]
    assert not copies, copies
    _no_copy_of(text, [f"bf16[{shape}]", f"bf16[{shape.split(',', 1)[1]}]"])
    # the window of two blocks (8 rows) stages the two 16-row tiles it can
    # touch of K and of V, merged in bfloat16: not two whole pages a slot
    # through float32 (PR 54)
    assert f"bf16[{B},2,{cfg.num_kv_heads},16,{D}]" in text
    assert f"f32[{B},{2 * PS},{cfg.num_kv_heads},{D}]" not in text
    assert f"[{B},2,{cfg.num_kv_heads},{PS},{D}]" not in text
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
