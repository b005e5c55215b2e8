"""What the files of ``tests/test_tpu_compile_*.py`` share: the cells' shapes
and the helpers that compile a function for the DESCRIBED v5e and read the
result (the ``topo`` / ``one_chip`` / ``as_tpu`` fixtures are in
``tests/conftest.py``). Not a test module: nothing here is collected, and
nothing here loads libtpu or describes a topology."""

import jax
import jax.numpy as jnp

# gpt-1b / gpt-750m head layout and the mistral-7b GQA layout
LAYOUTS = {"mha16": (16, 16), "gqa32x8": (32, 8)}
D, MAX_SEQ_LEN = 128, 2048         # head_dim, the cells' max_seq_len
PAGES = (64, 128)                  # a 16-head bf16 page by the rule, and
                                   # every smaller row's (the rule's cap)


def page_tokens(nkv: int, kv: str = "bf16", itemsize: int = 2) -> int:
    """The page an engine builds over ``nkv`` K/V heads of ``D`` where its
    configuration states none (``ServeConfig.kv_block_size`` 0): the rule's,
    ``serve/kv_cache.py page_size_by_rows``, not a literal."""
    import types

    from distributed_llm_training_and_inference_system_tpu.serve.engine import (
        InferenceEngine)
    from distributed_llm_training_and_inference_system_tpu.serve.kv_cache import (
        kv_row_bytes, page_size_by_rows)
    heads = types.SimpleNamespace(is_latent=False, num_kv_heads=nkv,
                                  head_dim=D)
    row = kv_row_bytes(heads, itemsize, "none" if kv == "bf16" else kv)
    return page_size_by_rows(row, most=InferenceEngine.RIDE_ROWS)


def table_width(page: int, max_seq_len: int = MAX_SEQ_LEN) -> int:
    """Pages a slot's block table names."""
    return max_seq_len // page


# the latent cell's page size, pages a slot and pages of the pool (the linear
# cell's latent pool has pages of the same size)
LATENT_PS, LATENT_MAXP, LATENT_PAGES = 256, 68, 1307
LATENT_POOL_BYTES = 7 * LATENT_PAGES * LATENT_PS * 640 * 2
# what a piece's 256 rows may add to the program's temporaries: half a MB a
# row (a row's four float32 streams are 57 KB, its 32 absorbed queries and
# outputs 74 KB, its 4 expert choices' hidden rows 8 KB, a few of each live
# at once); the compiler read 84 MB over the plain program's 270 (PR 41)
PIECE_ROWS_BYTES = LATENT_PS << 19


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "lowered without a Mosaic kernel (interpret mode?)"
    return compiled


def _kernel_grids(fn, *args) -> list[tuple[int, ...]]:
    """The grid (``iteration_bounds``) of every Mosaic kernel in the lowered
    text of ``fn``: the custom call carries its module as MLIR bytecode."""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    text = jax.jit(fn).lower(*args).as_text()
    bodies = re.findall(r'body\\22: \\22([A-Za-z0-9+/=]+)', text)
    assert bodies, "no tpu_custom_call in the lowered text"
    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True    # the versioned wrapper
    grids = []
    with ctx:
        for body in bodies:
            module = str(ir.Module.parse(base64.b64decode(body)))
            bounds, = re.findall(r"iteration_bounds = array<i64: ([^>]*)>",
                                 module)
            grids.append(tuple(int(n) for n in bounds.split(",")))
    return grids


def _sds(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _pages(sds, num_pages, nkv, kv, layers=(), page=None):
    """One layer's pages, or the [L, NP, ...] pool with ``layers=(L,)``, of
    ``page`` tokens (None: what the rule gives the layout)."""
    from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
        QuantPages)
    page = page or page_tokens(nkv, kv)
    if kv == "int8":
        return QuantPages(sds((*layers, num_pages, nkv, page, D), jnp.int8),
                          sds((*layers, num_pages, nkv, page), jnp.float32))
    return sds((*layers, num_pages, nkv, page, D), jnp.bfloat16)


# the serving cells' pools (benchmark/configs): layers, kv_hbm_budget_gb
CELL_POOLS = {"gqa32x8": (16, 3.0), "mha16": (10, 3.75)}


def cell_pool(layout, page=None):
    """(layers, pages a layer) of a cell's bf16 pool at pages of ``page``
    tokens (None: the rule's), as ``PagedKVCache`` sizes it from the
    budget: 715 pages of 64."""
    n_layers, budget_gb = CELL_POOLS[layout]
    nkv = LAYOUTS[layout][1]
    page = page or page_tokens(nkv)
    return n_layers, int(budget_gb * 1e9) // (
        n_layers * 2 * nkv * D * 2 * page)


def _cell_call(fn, sds, layout, kv, q_shape, page=None):
    """``fn`` on a cell's whole pool with a traced layer index, 32 slots:
    (callable, argument shapes)."""
    nq, nkv = LAYOUTS[layout]
    page = page or page_tokens(nkv, kv)
    n_layers, num_pages = cell_pool(layout, page)
    pool = _pages(sds, num_pages, nkv, kv, layers=(n_layers,), page=page)

    def call(q, kp, vp, tables, lengths, layer):
        return fn(q, kp, vp, tables, lengths, impl="auto", layer=layer)
    return call, (sds(q_shape(32, nq), jnp.bfloat16), pool, pool,
                  sds((32, table_width(page)), jnp.int32),
                  sds((32,), jnp.int32), sds((), jnp.int32))


_HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
              "u32": 4, "f32": 4}


def _no_copy_of(text: str, shapes: list[str],
                fused_into_at_most: int = 0) -> None:
    """No ``copy`` of a pool or a stack (a result that starts with one of
    ``shapes``) in an optimised HLO text, inside a fused computation or
    out of one.

    ``fused_into_at_most`` (bytes; the carrying linear program alone asks
    for it): a copy FUSED into an operation that keeps a small part of it
    (a slot's rows sliced out of a pool: the fusion computes those rows
    alone) passes where everything the OUTERMOST fusion that holds it hands
    out is no more than so many bytes; a fusion that hands the copy on
    converted or transposed is pool-sized, and fails."""
    import math
    import re
    computation, called_from = None, {}     # callee -> (caller, its line)
    copies = []
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            computation = head.group(1)
            continue
        for callee in re.findall(r"calls=%?([\w.\-]+)", line):
            called_from[callee] = (computation, line)
        if " copy(" in line and any(
                line.lstrip().split(" = ", 1)[-1].startswith(shape)
                for shape in shapes):
            copies.append((computation, line))
    for at, line in copies:
        made = line
        while fused_into_at_most and "fused" in at and at in called_from:
            at, made = called_from[at]
        result = made.lstrip().split(" = ", 1)[-1].split(" fusion(")[0]
        handed_out = sum(
            _HLO_BYTES.get(kind, 8) * math.prod(int(n) for n in dims.split(",") if n)
            for kind, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]",
                                         result))
        assert made is not line and handed_out <= fused_into_at_most, (
            f"a pool- or stack-sized copy: {line[:200]}\n"
            f"(handed out by: {made[:200]})")


def _ssm_decode_pool_compiles(sds, Lm, B, nh, P, N, G, heads_a_block):
    """``ops/ssm.py ssm_decode_pool`` on a cell's whole state pool [Lm, B,
    nh, P, N] float32, the layer traced and the slots masked, compiled for
    the described chip: a Mosaic kernel of ``heads_a_block`` heads a grid
    step, the donated pool aliased to the output, nothing the size of a
    layer's slab temporary."""
    from distributed_llm_training_and_inference_system_tpu.ops import ssm
    assert ssm._heads_a_block(nh, P, N, G) == heads_a_block
    compiled = jax.jit(ssm.ssm_decode_pool, donate_argnums=(6,)).lower(
        sds((B, nh, P), jnp.bfloat16), sds((B, nh), jnp.float32),
        sds((nh,), jnp.float32), sds((B, G, N), jnp.bfloat16),
        sds((B, G, N), jnp.bfloat16), sds((nh,), jnp.float32),
        sds((Lm, B, nh, P, N), jnp.float32), sds((), jnp.int32),
        sds((B, 1), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= Lm * B * nh * P * N * 4
    assert mem.temp_size_in_bytes < 32 << 20


def _state_update_is_the_kernel(text: str, slab: str) -> None:
    """A decode program's one-token update of the state pool is the Mosaic
    kernel ``ssm_decode`` on the WHOLE pool (PR 50), and no operation is
    left that takes or makes a layer's ``slab`` of it (the XLA form's
    update fusion did, and at a state of 256 the ``multiply_reduce`` that
    read the new state again for ``y``)."""
    import re
    assert re.search(r"%ssm_decode(\.\d+)? = .*custom-call\(", text)
    held = [line.strip()[:160] for line in text.splitlines() if slab in line]
    assert not held, held[:3]


def _largest_f32_under(text: str, scope: str) -> tuple[int, str]:
    """(elements, shape) of the largest float32 array any operation under the
    named ``scope`` of an optimised HLO text makes or reads, inside a fused
    computation or out of one: what a fusion walks over counts as what a
    program keeps does."""
    import math
    import re
    shapes = {m.group(0) for line in text.splitlines() if f"/{scope}/" in line
              for m in re.finditer(r"f32\[[\d,]+\]", line)}
    assert shapes, f"no operation under {scope}"
    return max((math.prod(int(n) for n in s[4:-1].split(",")), s)
               for s in shapes)


def _pair_forms_traced(rows: int, heads: int) -> set[str]:
    """How this process has traced ``kda_chunk_prefill`` over windows of
    ``rows`` x ``heads`` heads of 128: the ``pairs ...`` part of every such
    ``report_impl`` line (``ops/kda.py``: the form is static a program)."""
    from distributed_llm_training_and_inference_system_tpu.utils.platform import (
        reported_impls)
    return {detail.split(" pairs ", 1)[1] for op, _, detail in reported_impls()
            if op == "kda_chunk_prefill"
            and detail.startswith(f"q(1, {rows}, {heads}, 128)")}

