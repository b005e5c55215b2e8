"""The chunked loss's forward + backward alone on the chip, at the two
training cells' shapes: ms a micro-batch and the program's temporaries,
the backward walking rows against walking the vocabulary (PERF.md 6, PR 48).

    chiprun -- python experiments/loss_backward_alone.py [--tree DIR]
    chiprun --chips 4 -- python experiments/loss_backward_alone.py --chips 4

One chip: ``internlm2-1.8b-6l.pretrain-4k``'s micro-batch, ``[2, 4096]``
rows of 2,048 in bfloat16 against the untied float32 head ``[2048,
92544]``. Four chips: ``internlm2-1.8b.pretrain-4k-fsdp4``'s, ``[4, 4096]``
rows a step of the accumulation over an ``fsdp=4`` mesh, the head
vocabulary-parallel as ``PARAM_RULES`` puts it. The program is
``value_and_grad`` of ``chunked_next_token_loss`` over ``(hidden,
unembed_w)`` inside a scan over ``ACCUM`` micro-batches that adds the
gradients up in float32, as ``exec/train_step.py`` does, so the head's
gradient keeps the layout the compiler gives it in the step. Cases: the walk
the tree's rule picks, then each walk it has for these shapes forced
(``models/loss.py loss_backward_plans``: a head spread over devices has the
row walk alone, PERF.md 6 has what its vocabulary walk measured before PR 48
took it out; a tree without the function, the parent of PR 48 under
``--tree``, has one case: its scan's transpose). Every case prints how far
its two gradients lie from the first case's.

``--trace`` profiles one more batch a case and lists device 0's longest
operations. Times are the host clock around ``block_until_ready`` over chained calls
(the fastest of ``BATCHES`` batches of ``REPS``). Fails (exit 2) without a
TPU; ``--rehearse`` runs tiny shapes anywhere and times nothing worth
keeping.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from importlib import import_module

PKG = "distributed_llm_training_and_inference_system_tpu"
HIDDEN, VOCAB, SEQ, CHUNK = 2048, 92544, 4096, 512
ACCUM = 4
REPS, BATCHES = 5, 3


def program(loss_mod, mesh, sharding):
    import jax
    import jax.numpy as jnp

    def grads(w, hidden, tokens, segments):
        def one(h, w, t, s):
            return loss_mod.chunked_next_token_loss(
                h, w, t, s, chunk=CHUNK, tied=False)[0]

        def micro(acc, xs):
            loss, (d_h, d_w) = jax.value_and_grad(one, argnums=(0, 1))(
                xs[0], w, xs[1], xs[2])
            return (acc[0] + d_w, acc[1] + loss), d_h
        (d_w, loss), d_h = jax.lax.scan(
            micro, (jnp.zeros(w.shape, jnp.float32), jnp.float32(0.0)),
            (hidden, tokens, segments))
        return loss / ACCUM, d_h, d_w
    if mesh is None:
        return jax.jit(grads)
    w_s, h_s, t_s = sharding
    return jax.jit(grads, in_shardings=(w_s, h_s, t_s, t_s),
                   out_shardings=(None, h_s, w_s))


def traced(call, top: int = 14) -> dict:
    """One batch of ``REPS`` calls under the profiler: device 0's ``top``
    longest operations as ``[name and result, calls, ms a micro-batch]``,
    and the ms a micro-batch its collectives run with nothing beside them."""
    import jax
    from benchmark import harness, trace_reduce
    with harness.scratch_dir("loss_alone_trace_") as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        for _ in range(REPS):
            out = call()
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        profile = jax.profiler.ProfileData.from_file(
            trace_reduce.find_xplane(tmp))
    per = 1e3 / (REPS * ACCUM)
    line = next((v for k, v in sorted(
        trace_reduce.listing(profile, top=top).items())
        if "TPU:0" in k and k.endswith("| XLA Ops")), [])
    first = next(iter(trace_reduce.load(profile).values()), {"ops": []})
    return {"device_0_ops": [[name[:160], n, round(seconds * per, 3)]
                             for name, n, seconds in line],
            "exposed_collective_ms": round(
                trace_reduce.exposed_collective_seconds(first["ops"]) * per,
                3)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the checkout to import from")
    ap.add_argument("--out", default="chiprun_out/loss_backward_alone.json")
    ap.add_argument("--trace", action="store_true", help="profile one batch "
                    "a case more and list device 0's longest operations")
    ap.add_argument("--rehearse", action="store_true", help="tiny shapes "
                    "on whatever devices there are: the control flow alone")
    args = ap.parse_args()
    if args.rehearse:
        global HIDDEN, VOCAB, SEQ, CHUNK
        HIDDEN, VOCAB, SEQ, CHUNK = 64, 1536, 256, 64
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))       # the benchmark's trace readers
    sys.path.insert(0, os.path.abspath(args.tree))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    device = jax.devices()[0]
    if not args.rehearse and (device.platform != "tpu"
                              or len(jax.devices()) < args.chips):
        print("loss_backward_alone: needs the chip(s); a time comes from "
              "the chip", file=sys.stderr)
        return 2
    loss_mod = import_module(f"{PKG}.models.loss")
    sharding_mod = import_module(f"{PKG}.parallel.sharding")
    mesh = sharding = None
    batch = 2
    if args.chips == 4:
        schema = import_module(f"{PKG}.config.schema")
        mesh = import_module(f"{PKG}.parallel.mesh").build_mesh(
            schema.ParallelConfig(fsdp=4), jax.devices()[:4])
        batch = 4
        sharding = (
            NamedSharding(mesh, sharding_mod.spec_for_path("lm_head.kernel")),
            NamedSharding(mesh, P(None, ("dp", "fsdp"), "sp", None)),
            NamedSharding(mesh, P(None, ("dp", "fsdp"), "sp")))
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    put = (lambda x, s: x) if mesh is None else jax.device_put
    w_s, h_s, t_s = sharding or (None,) * 3
    w = put(jax.random.normal(keys[0], (HIDDEN, VOCAB), jnp.float32) * 0.02,
            w_s)
    hidden = put(jax.random.normal(
        keys[1], (ACCUM, batch, SEQ, HIDDEN), jnp.bfloat16), h_s)
    tokens = put(jax.random.randint(
        keys[2], (ACCUM, batch, SEQ), 1, VOCAB), t_s)
    # two documents a sequence, as a packed batch has them
    segments = put(jnp.broadcast_to(
        1 + (jnp.arange(SEQ) >= 1500).astype(jnp.int32),
        (ACCUM, batch, SEQ)), t_s)

    cases = [("the rule's walk", None)]
    if hasattr(loss_mod, "loss_backward_plans"):
        cases += [("rows", 0), ("vocabulary", 1)]
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind, "chips": args.chips},
              "tree": os.path.abspath(args.tree), "cases": {}}
    rule = getattr(loss_mod, "plan_loss_backward", None)
    first = None
    for label, forced in cases:
        if forced is not None:
            loss_mod.plan_loss_backward = (
                lambda _i=forced, **shapes:
                loss_mod.loss_backward_plans(**shapes)[_i])
        elif rule is not None:
            loss_mod.plan_loss_backward = rule
        fn = program(loss_mod, mesh, sharding)
        with (sharding_mod.use_mesh(mesh) if mesh is not None
              else contextlib.nullcontext()):
            plan = rule and loss_mod.chunked_loss_backward_plan(
                batch, SEQ, HIDDEN, VOCAB, CHUNK)
            if rule and plan is None:
                print(f"{label:18s} these shapes have no such walk",
                      flush=True)
                continue
            compiled = fn.lower(w, hidden, tokens, segments).compile()
            out = jax.block_until_ready(
                compiled(w, hidden, tokens, segments))
            best = float("inf")
            for _ in range(BATCHES):
                t0 = time.perf_counter()
                for _ in range(REPS):
                    out = compiled(w, hidden, tokens, segments)
                jax.block_until_ready(out)
                best = min(best, (time.perf_counter() - t0) / REPS)
            if args.trace:
                print(json.dumps(traced(
                    lambda: compiled(w, hidden, tokens, segments))),
                    flush=True)
        temp = compiled.memory_analysis().temp_size_in_bytes
        loss, d_h, d_w = out
        got = (np.asarray(d_h.astype(jnp.float32)[0, 0]), np.asarray(d_w))
        first = first or got
        case = {
            "ms_a_micro_batch": best / ACCUM * 1e3,
            "temporaries_bytes": int(temp), "loss": float(loss),
            "plan": plan and plan._asdict(),
            "max_abs_diff_to_first": [float(np.abs(a - b).max())
                                      for a, b in zip(got, first)],
            "max_abs_first": [float(np.abs(a).max()) for a in first]}
        result["cases"][label] = case
        print(f"{label:18s} {case['ms_a_micro_batch']:8.3f} ms a "
              f"micro-batch, temporaries {temp / 1e9:.3f} GB  "
              f"{json.dumps(case)}", flush=True)
        del compiled, out, fn
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
