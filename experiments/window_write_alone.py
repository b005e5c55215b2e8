"""A decode step's page write alone on the chip: the whole-page route
against the tile route of ``ops/paged_attention.py write_window_to_pages``.

    chiprun -- python experiments/window_write_alone.py [--out FILE]

Four pools at the cells' shapes: the self-drafting cell's latent pool
(joyai-llm-flash-8l-ep2: 9 layers x 1,017 pages x 1 x 256 rows x 640 bf16,
one write a layer under ``mla_page_write``) and the diffusion cell's K/V
pools (sdar-30b-a3b-7l: 7 layers x 2,179 pages x 4 heads x 64 rows x 128
bf16 each, K and V written a layer under ``kv_page_write``), 64 slots, for
T = 1, 2, 8 rows a slot; the short-conv cell's K/V pools (lfm2-8b-a1b-16l:
4 layers x 1,430 pages x 4 paired heads x 256 rows x 128, 256 slots) and
Mistral's (mistral-7b-16l: 16 layers x 715 pages x 8 heads x 64 rows x 128,
32 slots) for the decode step's T = 1. Every slot's window at a position of
its own (a few cross a tile, a few a page). One jitted program walks the
layers of the donated pools ``ROUNDS`` times, so the host clock around
``block_until_ready`` reads the writes and not the dispatch (the fastest of
five batches). For each T and each route, called directly
(``_write_window_to_whole_pages``, ``_write_window_to_tiles``): us a layer
(K and V together for the K/V pools), and the two routes' pools held equal
bit for bit outside scratch page 0. Fails (exit 2) without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

PKG = "distributed_llm_training_and_inference_system_tpu"
# (pools a layer, layers, pages, kv heads, page rows, row width, pages a
# slot holds, the table's width, slots, windows)
POOLS = {"joyai_latent": (1, 9, 1017, 1, 256, 640, 15, 49, 64, (1, 2, 8)),
         "sdar_kv": (2, 7, 2179, 4, 64, 128, 32, 32, 64, (1, 2, 8)),
         "lfm2_kv": (2, 4, 1430, 4, 256, 128, 5, 8, 256, (1,)),
         "mistral_kv": (2, 16, 715, 8, 64, 128, 22, 32, 32, (1,))}
ROUNDS, BATCHES = 8, 5


def traffic(rng, pages, held, width, PS, T, slots):
    """Tables of distinct pages a slot (scratch page 0 nobody's) and starts
    that put some windows across a tile's and some across a page's end."""
    ids = 1 + rng.permutation(pages - 1)[:slots * held].reshape(slots, held)
    tables = np.zeros((slots, width), np.int32)
    tables[:, :held] = ids
    starts = rng.integers(0, held * PS - T, slots)
    starts[:8] = starts[:8] // 16 * 16 + 15         # the last row of a tile
    starts[8:12] = starts[8:12] // PS * PS + PS - 1   # ... and of a page
    starts[12] = held * PS - T                      # ends in the last page
    return jnp.asarray(tables), jnp.asarray(starts, jnp.int32)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/window_write_alone.json")
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: nothing measured", file=sys.stderr)
        return 2
    from importlib import import_module
    pa = import_module(f"{PKG}.ops.paged_attention")
    routes = {
        "pages": pa._write_window_to_whole_pages,
        "tiles": lambda *args: pa._write_window_to_tiles(*args, 16),
    }

    def walk(write, layers):
        def program(pools, rows, tables, starts, ok):
            def body(pools, layer):
                return tuple(write(p, r, tables, starts, ok, layer)
                             for p, r in zip(pools, rows)), None
            return jax.lax.scan(body, pools, jnp.tile(
                jnp.arange(layers, dtype=jnp.int32), ROUNDS))[0]
        return jax.jit(program, donate_argnums=(0,))

    results = {"device": str(jax.devices()[0].device_kind), "cases": []}
    bad = []
    rng = np.random.default_rng(54)
    for name, (n, L, NP, Nkv, PS, D, held, width, slots, windows) in (
            POOLS.items()):
        for T in windows:
            tables, starts = traffic(rng, NP, held, width, PS, T, slots)
            ok = jnp.asarray(rng.random((slots, T)) > 0.1)
            keys = jax.random.split(jax.random.PRNGKey(T), 2 * n)
            rows = tuple(jax.random.normal(k, (slots, T, Nkv, D),
                                           jnp.bfloat16) for k in keys[:n])
            fresh = lambda: tuple(jax.random.normal(
                k, (L, NP, Nkv, PS, D), jnp.bfloat16) for k in keys[n:])
            row = {"pool": name, "slots": slots, "T": T}
            kept = {}
            for route, write in routes.items():
                fn = walk(write, L)
                pools = fn(fresh(), rows, tables, starts, ok)
                jax.block_until_ready(pools)
                # the first and the last layer, scratch page 0 apart (whole
                # pools of both routes do not fit the chip side by side)
                kept[route] = [p[jnp.asarray([0, L - 1]), 1:] for p in pools]
                best = float("inf")
                for _ in range(BATCHES):
                    t0 = time.perf_counter()
                    pools = fn(pools, rows, tables, starts, ok)
                    jax.block_until_ready(pools)
                    best = min(best, time.perf_counter() - t0)
                del pools
                row[route + "_us_a_layer"] = best / (L * ROUNDS) * 1e6
            same = all(bool(jnp.array_equal(x, y)) for x, y in
                       zip(kept["pages"], kept["tiles"]))
            del kept
            row["routes_equal"] = same
            if not same:
                bad.append(f"{name} T={T}: the routes' pools differ")
            results["cases"].append(row)
            print(json.dumps(row), flush=True)
    results["failures"] = bad
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(results, f, indent=1)
    if bad:
        print("WRONG:\n  " + "\n  ".join(bad), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
