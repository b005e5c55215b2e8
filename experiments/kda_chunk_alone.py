"""``ops/kda.py kda_chunk_prefill`` alone on the chip, at the two delta-rule
cells' shapes: ms a ``K`` layer and where in the scope it goes (PERF.md 6,
PR 62).

    chiprun -- python experiments/kda_chunk_alone.py [--parent DIR]

Four windows of ONE slot, heads of 128 x 128 in bfloat16, chunks of 64 from
a non-zero float32 state: the riding piece of 256 rows at
``kimi-linear-48b-a3b-12l-ep8``'s 32 heads and at
``solar-open2-250b-4l-ep8``'s 64, and a chunk program's 1,024 rows of each.
One jitted program walks ``LAYERS`` layers' inputs ``ROUNDS`` times with the
state carried from call to call (an input a layer, indexed by the loop's
counter: nothing is invariant, so nothing is hoisted), so the host clock
around ``block_until_ready`` reads the function and not the dispatch (the
fastest of ``BATCHES`` batches). One batch more runs under the profiler:
the leaf operations' device time by KIND of operation (a name without its
number: ``multiply_reduce_fusion`` is a pair product on the vector unit; a
fusion whose computation holds a ``convolution`` in the compiled text, which
the chip's compiler names plain ``fusion.N``, is counted as ``matmul``), as
shares of the call.

``--parent DIR`` (the parent commit unpacked by ``git archive`` into a
git-ignored directory of the repo) times that tree's ``ops/kda.py`` beside
this one and prints how far the two forms' outputs and states lie apart;
``--ops N`` adds each case's N longest operations by name and keeps its
compiled text beside ``--out`` (which fusion is which).
Fails (exit 2) without a TPU; ``--rehearse`` runs tiny shapes anywhere and
times nothing worth keeping.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import re
import sys
import time
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

PKG = "distributed_llm_training_and_inference_system_tpu"
SHAPES = [(256, 32), (256, 64), (1024, 32), (1024, 64)]    # rows, heads
HEAD_DIM, CHUNK = 128, 64
LAYERS, ROUNDS, BATCHES = 3, 8, 5


def load_kda(tree: str | None):
    name = f"{PKG}.ops.kda"
    if tree is None:
        return importlib.import_module(name)
    spec = importlib.util.spec_from_file_location(
        name + "_parent", os.path.join(tree, PKG, "ops", "kda.py"))
    module = importlib.util.module_from_spec(spec)
    module.__package__ = f"{PKG}.ops"
    spec.loader.exec_module(module)
    return module


def inputs(kda, rows: int, heads: int, d: int):
    """A layer's (q, k, v, g, beta) a row of [LAYERS, 1, rows, heads, ...]
    and the state: decays of a few percent a token a channel, as a trained
    gate's softplus gives them."""
    ks = jax.random.split(jax.random.PRNGKey(rows + heads), 6)
    shape = (LAYERS, 1, rows, heads, d)
    bf = jnp.bfloat16
    q = (kda.l2norm(jax.random.normal(ks[0], shape)) * d ** -0.5).astype(bf)
    k = kda.l2norm(jax.random.normal(ks[1], shape)).astype(bf)
    v = jax.random.normal(ks[2], shape, bf)
    g = -0.1 * jax.random.uniform(ks[3], shape)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:-1]))
    state = jax.random.normal(ks[5], (1, heads, d, d))
    return (q, k, v, g, beta), state


def program(kda):
    @jax.jit
    def run(xs, state):
        def body(carry, i):
            S, acc = carry
            o, S = kda.kda_chunk_prefill(
                *(x[i % LAYERS] for x in xs), S, CHUNK)
            return (S, acc + o.astype(jnp.float32)), None
        zero = jnp.zeros(xs[2].shape[1:], jnp.float32)
        return jax.lax.scan(body, (state, zero), jnp.arange(
            ROUNDS * LAYERS, dtype=jnp.int32))[0]
    return run


def matmul_fusions(text: str) -> set:
    """Names of the fusions of a compiled program's text whose computation
    holds a ``convolution`` (every matmul on the chip is one)."""
    holds = {m.group(1) for m in re.finditer(
        r"^%?(\S+) \([^\n]*\{\n(?:(?!^\}).*\n)*?.* convolution\(", text, re.M)}
    return {m.group(1) for m in re.finditer(
        r"%(\S+) = [^\n]* fusion\([^\n]*calls=%(\S+?)[,\s]", text)
        if m.group(2) in holds}


def traced_ops(call) -> dict:
    """One call under the profiler: {leaf operation: device ms a layer},
    the longest first."""
    from benchmark import harness, trace_reduce
    with harness.scratch_dir("kda_chunk_trace_") as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        jax.block_until_ready(call())
        jax.profiler.stop_trace()
        profile = jax.profiler.ProfileData.from_file(
            trace_reduce.find_xplane(tmp))
    plane = next(iter(trace_reduce.load(profile).values()), {"ops": []})
    ops: dict = defaultdict(float)
    for name, s, e in trace_reduce.leaves(plane["ops"]):
        ops[name] += (e - s) * 1e3 / (ROUNDS * LAYERS)
    return dict(sorted(ops.items(), key=lambda kv: -kv[1]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None,
                    help="another checkout whose ops/kda.py to time beside")
    ap.add_argument("--out", default="chiprun_out/kda_chunk_alone.json")
    ap.add_argument("--ops", type=int, default=0, help="list the so many "
                    "longest operations of each case by name and keep the "
                    "compiled text beside --out")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    shapes, d = SHAPES, HEAD_DIM
    if a.rehearse:
        shapes, d = [(128, 2)], 16
    elif jax.default_backend() != "tpu":
        print("no TPU: nothing measured", file=sys.stderr)
        return 2
    platform = importlib.import_module(f"{PKG}.utils.platform")
    trees = {"this tree": load_kda(None)}
    if a.parent:
        trees = {"parent": load_kda(a.parent), **trees}
    result = {"device": str(jax.devices()[0].device_kind), "cases": []}
    for rows, heads in shapes:
        xs, state = inputs(trees["this tree"], rows, heads, d)
        row = {"rows": rows, "heads": heads}
        outs = {}
        for tree, kda in trees.items():
            reported = set(platform.reported_impls())
            compiled = program(kda).lower(xs, state).compile()
            outs[tree] = jax.block_until_ready(compiled(xs, state))
            best = float("inf")
            for _ in range(BATCHES):
                t0 = time.perf_counter()
                jax.block_until_ready(compiled(xs, state))
                best = min(best, time.perf_counter() - t0)
            text = compiled.as_text()
            matmuls = matmul_fusions(text)
            ops = traced_ops(lambda: compiled(xs, state))
            kind = lambda name: ("matmul" if name in matmuls
                                 else re.sub(r"[.\d]+$", "", name))
            kinds: dict = defaultdict(float)
            for name, ms in ops.items():
                kinds[kind(name)] += ms
            kinds = dict(sorted(kinds.items(), key=lambda kv: -kv[1]))
            total = sum(kinds.values()) or 1.0
            row[tree] = {
                "ms_a_layer": best / (ROUNDS * LAYERS) * 1e3,
                "device_ms_a_layer": sum(kinds.values()),
                "multiply_reduce_share": kinds.get(
                    "multiply_reduce_fusion", 0.0) / total,
                "matmul_share": kinds.get("matmul", 0.0) / total,
                "ms_a_layer_by_kind": dict(list(kinds.items())[:10]),
                "temporaries_bytes": int(
                    compiled.memory_analysis().temp_size_in_bytes),
                # the report_impl lines this compile added (the parent's
                # name no form of the pairs: it had one)
                "traced_as": [detail for op, _, detail in sorted(
                    set(platform.reported_impls()) - reported)
                    if op == "kda_chunk_prefill"]}
            if a.ops:
                row[tree]["ms_a_layer_by_op"] = {
                    f"{name} ({kind(name)})": ms
                    for name, ms in list(ops.items())[:a.ops]}
                path = f"{os.path.splitext(a.out)[0]}.{tree}.{rows}x{heads}"
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
                with open(path.replace(" ", "_") + ".hlo.txt", "w") as f:
                    f.write(text)
            del compiled
        if a.parent:
            row["max_abs_diff_state_and_output"] = [
                float(np.abs(np.asarray(x) - np.asarray(y)).max())
                for x, y in zip(outs["parent"], outs["this tree"])]
            row["max_abs_state_and_output"] = [
                float(np.abs(np.asarray(x)).max()) for x in outs["parent"]]
        result["cases"].append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
