"""Every collective of a training step, with the loop that holds it.

    JAX_PLATFORMS=cpu python experiments/collectives_by_loop.py \
        --workload internlm2-1.8b.pretrain-4k-fsdp4 [--layers 2] \
        [--topology v5e:2x2 | --host-devices 4] [--tree DIR] [--save FILE]
    python experiments/collectives_by_loop.py --hlo FILE
    python experiments/collectives_by_loop.py --run-traced SEED --dump FILE
    python experiments/collectives_by_loop.py --trace-dump FILE --hlo FILE

Builds the cell's ``ShardedTrainer`` (configuration and traffic from
``BENCHMARK.json``, as ``benchmark/runners/train.py`` does) over the devices
of a DESCRIBED topology (the TPU compiler is installed here and compiles for
a chip that is not attached: nothing runs) or over host devices, compiles
``train_step`` for shapes alone and lists the partitioned program's
collectives: operation, result shape, bytes a device, inside a loop or not,
and the ``op_name`` of the innermost loop. Then the compiled program's
memory. ``--tree`` imports the program from another checkout (the parent
unpacked by ``git archive``); ``--hlo`` reads a text saved by ``--save``.

On the chip, ``--run-traced`` is ``benchmark/run.py --trace 1 --dump FILE``
for the cell with the dump's per-line listing uncut (the benchmark keeps 12
events a line), and ``--trace-dump`` reads device 0's operations out of it:
every collective the program's text names (``--hlo``, from a compile HERE:
the same compiler numbers the same program alike), with its calls, seconds
and loop, beside what else leads the stretch.

PR 34 found the LM head this way: a ``bf16[2048,92544]`` all-reduce and two
all-gathers inside ``chunked_loss``'s loops, 379 MB each, 16 times a
micro-batch. What it ranks next is in ROADMAP A11.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
PKG = "distributed_llm_training_and_inference_system_tpu"


def compile_step(workload: str, layers: int | None, topology: str | None,
                 host_devices: int):
    """(compiled ``train_step``, the trainer) for the cell's shapes."""
    import jax
    import jax.numpy as jnp
    from importlib import import_module

    from benchmark import run as bench_run, traffic as traffic_mod
    from benchmark.runners import train as train_runner

    cell = bench_run.load_cell(workload)
    traffic = traffic_mod.load(cell["traffic_path"])
    config = dict(cell["config"])
    if layers:
        config["num_hidden_layers"] = layers
    cfg = train_runner.run_config(config, traffic, seed=0, ckpt_dir="/unused")
    chips = cell["cell"]["chips"]
    if topology:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name=topology).devices[:chips]
        # the kernels pick interpret mode from the backend, which is the
        # CPU here: take their TPU branch, as tests/conftest.py ``as_tpu`` does
        jax.default_backend = lambda: "tpu"
        attn_impl = "flash"
    else:
        devices = jax.devices()[:host_devices]
        attn_impl = "xla"
    mesh_mod = import_module(f"{PKG}.parallel.mesh")
    api = import_module(f"{PKG}.parallel.api")
    par = mesh_mod.infer_data_parallel(cfg.parallel, len(devices))
    trainer = api.ShardedTrainer(cfg.model, cfg.optimizer, par,
                                 devices=list(devices), attn_impl=attn_impl)
    shape = (par.global_batch_size, cfg.data.max_length)
    batch = {k: jax.ShapeDtypeStruct(shape, jnp.int32)
             for k in ("tokens", "segment_ids", "positions")}
    return trainer.lower_step(batch).compile(), trainer


def read_collectives(text: str) -> list:
    from importlib import import_module
    return import_module(f"{PKG}.comms.hlo").collectives(text)


def run_traced(workload: str, seed: int, dump: str) -> int:
    """One traced run of the cell as the benchmark makes it, the dump's
    listing uncut. Needs the chip."""
    import functools

    from benchmark import run as bench_run, trace_reduce
    trace_reduce.listing = functools.partial(trace_reduce.listing,
                                             top=1 << 20)
    seconds = json.loads((ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    return bench_run.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", "1",
                           "--dump", dump])


def report_trace(dump: str, text: str | None, top: int = 12) -> None:
    from benchmark.trace_reduce import short_name
    run = json.loads(Path(dump).read_text())
    line = next((v for k, v in sorted(run["trace_listing"].items())
                 if "TPU:0" in k and k.endswith("| XLA Ops")), None)
    if line is None:
        raise SystemExit(f"no device-0 'XLA Ops' line in {dump}: "
                         f"{sorted(run['trace_listing'])[:8]}")
    by_trace_name: dict[str, list] = {}
    for c in read_collectives(text) if text else ():
        by_trace_name.setdefault(c.fusion or c.name, []).append(c)
    steps = run["trace_steps"]
    print(f"device 0, {steps} steps traced: collectives (calls, seconds, "
          f"ms a step | operation shape | loop)")
    # the listing names an event by its whole HLO line
    line = [(short_name(text), n, seconds) for text, n, seconds in line]
    total = 0.0
    for name, n, seconds in line:
        held = by_trace_name.get(name.split(":")[0], [])
        if not held and not any(op in name for op in (
                "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "async-collective")):
            continue
        beside = any(c.overlapped for c in held)
        if not beside:
            total += seconds
        what = "; ".join(
            f"{c.op} {c.widest[0]}[{','.join(map(str, c.widest[1]))}] | "
            f"{c.loop or '-'}" for c in held)
        print(f"{n:6d} {seconds:9.4f} {seconds / steps * 1e3:9.2f}  "
              f"{name:<28} {'(a matmul, the gather beside it) ' * beside}"
              f"{what}")
    print(f"collectives that run alone, together {total:.4f} s = "
          f"{total / steps * 1e3:.1f} ms a step; the stretch's {top} "
          f"longest operations:")
    for name, n, seconds in line[:top]:
        print(f"{n:6d} {seconds:9.4f} {seconds / steps * 1e3:9.2f}  {name}")


def report(text: str, at_least: int) -> None:
    found = read_collectives(text)
    shown = [c for c in found if c.nbytes >= at_least]
    print(f"{len(found)} collectives, {len(shown)} of at least "
          f"{at_least:,} bytes; largest first")
    print(f"{'MB':>9}  {'operation':<18} {'in loop':<8} shape | loop")
    for c in sorted(shown, key=lambda c: -c.nbytes):
        dtype, dims = c.widest
        print(f"{c.nbytes / 1e6:9.2f}  {c.op:<18} "
              f"{'yes' if c.in_loop else 'no':<8} "
              f"{dtype}[{','.join(map(str, dims))}]"
              f"{' (+%d)' % (len(c.shapes) - 1) if len(c.shapes) > 1 else ''}"
              f" | {c.loop or c.op_name or '-'}  ({c.name})")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="internlm2-1.8b.pretrain-4k-fsdp4")
    ap.add_argument("--layers", type=int, help="cut the depth (2 compiles "
                    "in seconds and holds every loop the full model has)")
    ap.add_argument("--topology", default="v5e:2x2",
                    help="described TPU topology; '' with --host-devices")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="compile for this many CPU devices instead (needs "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    ap.add_argument("--tree", default=str(ROOT),
                    help="the checkout to import the program from")
    ap.add_argument("--hlo", help="read this saved text, compile nothing")
    ap.add_argument("--run-traced", type=int, metavar="SEED",
                    help="on the chip: one traced benchmark run, --dump FILE")
    ap.add_argument("--dump", help="where --run-traced writes the run")
    ap.add_argument("--trace-dump", metavar="FILE",
                    help="list the collectives a --run-traced dump timed")
    ap.add_argument("--save", help="write the program's text here")
    ap.add_argument("--at-least", type=int, default=1 << 20,
                    help="list collectives of at least this many bytes")
    args = ap.parse_args()
    sys.path.insert(0, args.tree)
    if args.run_traced is not None:
        sys.exit(run_traced(args.workload, args.run_traced, args.dump))
    if args.trace_dump:
        report_trace(args.trace_dump,
                     Path(args.hlo).read_text() if args.hlo else None)
        return
    if args.hlo:
        report(Path(args.hlo).read_text(), args.at_least)
        return
    compiled, trainer = compile_step(
        args.workload, args.layers,
        None if args.host_devices else args.topology, args.host_devices)
    text = compiled.as_text()
    if args.save:
        Path(args.save).write_text(text)
    print(f"{args.workload}: {trainer.model_cfg.num_layers} layers, mesh "
          f"{dict(trainer.mesh.shape)}, lm_head "
          f"{trainer.describe_shardings().get('lm_head.kernel')}")
    report(text, args.at_least)
    mem = compiled.memory_analysis()
    gib = 1 << 30
    print(f"a device: arguments {mem.argument_size_in_bytes / gib:.2f} GiB, "
          f"outputs {mem.output_size_in_bytes / gib:.2f}, aliased "
          f"{mem.alias_size_in_bytes / gib:.2f}, temporaries "
          f"{mem.temp_size_in_bytes / gib:.2f} GiB")


if __name__ == "__main__":
    main()
