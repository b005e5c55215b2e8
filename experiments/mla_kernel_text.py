"""The latent page kernel's Mosaic text, hashed, for one tree: has a change
to ops/mla_paged_attention.py moved the kernel a call's shapes get?

    JAX_PLATFORMS=cpu python experiments/mla_kernel_text.py [--tree DIR]

Lowers ``mla_paged_attention`` for the TPU (nothing is compiled or run) at
the doc-qa cell's widths (32 heads, 640-wide bf16 rows, pages of 256) for a
few (slots, window) shapes and prints, a shape, the pages a loop step scores
and the SHA-256 of the kernel's MLIR WITHOUT its source locations (the
serialised module inside the StableHLO carries line numbers, so a comment
added above the kernel changes that text and not this one). Run it in two
trees (the parent: ``git archive`` into a git-ignored directory) and compare
the lines: a tile over the grouping threshold must read the parent's hash.
"""

from __future__ import annotations

import argparse
import functools
import hashlib
import importlib
import os
import sys

PKG = "distributed_llm_training_and_inference_system_tpu"
SHAPES = ((1, 1024), (1, 512), (8, 32), (64, 8), (64, 4), (64, 2), (64, 1))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    a = ap.parse_args()
    sys.path.insert(0, os.path.abspath(a.tree))
    import jax
    import jax.numpy as jnp
    from jax._src import tpu_custom_call
    jax.default_backend = lambda: "tpu"     # the kernel, not its twin
    mla = importlib.import_module(f"{PKG}.ops.mla_paged_attention")
    texts = []
    serialise = tpu_custom_call._lower_mosaic_module_to_asm

    def keep(module, **kw):
        texts.append(module.operation.get_asm(enable_debug_info=False))
        return serialise(module, **kw)
    tpu_custom_call._lower_mosaic_module_to_asm = keep
    sds = jax.ShapeDtypeStruct
    for B, T in SHAPES:
        q = sds((B, T, 32, 640), jnp.bfloat16)
        pool = sds((7, 1307, 1, 256, 640), jnp.bfloat16)
        jax.jit(functools.partial(
            mla.mla_paged_attention, scale=0.14, value_width=512,
            layer=3)).trace(q, pool, sds((B, 68), jnp.int32),
                            sds((B,), jnp.int32)).lower(
                                lowering_platforms=("tpu",))
        group = mla._tiling(q, pool)[1] if hasattr(mla, "_tiling") else 1
        print(f"slots {B:3d} window {T:5d} group {group} "
              f"{hashlib.sha256(texts[-1].encode()).hexdigest()[:16]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
