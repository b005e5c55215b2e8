"""`llmctl tune` — autotuning entry points.

Parity: reference cli/commands/tune.py (kernels :13-69, comms :71-131,
full :133-209) — backed by plugins/autotuning.py, which measures real ops
and real collectives (the reference simulated comm timings,
autotuning.py:222-245).
"""

from __future__ import annotations

import json
from pathlib import Path

import click


def _tuner(max_iterations, timeout, trials):
    from ...plugins.autotuning import AutoTuner, TuningConfig
    return AutoTuner(TuningConfig(max_iterations=max_iterations,
                                  timeout_seconds=timeout,
                                  num_trials=trials))


def _report(name, res):
    click.echo(f"{name}: best={res.best_params} "
               f"latency={res.best_latency_ms:.3f} ms "
               f"(+{res.improvement_pct:.1f}% vs first config, "
               f"{res.num_evaluated} evaluated)")


@click.group(name="tune", invoke_without_command=True)
@click.pass_context
def app(ctx):
    """Autotuning."""
    if ctx.invoked_subcommand is None:
        click.echo(ctx.get_help())


@app.command()
@click.option("--matmul-size", nargs=3, type=int, default=(1024, 1024, 1024),
              show_default=True, help="M K N.")
@click.option("--seq-len", default=512, show_default=True)
@click.option("--head-dim", default=64, show_default=True)
@click.option("--heads", default=8, show_default=True)
@click.option("--batch", default=8, show_default=True)
@click.option("--max-iterations", default=32, show_default=True)
@click.option("--timeout", default=120.0, show_default=True)
@click.option("--trials", default=5, show_default=True)
@click.option("--output-dir", default="tuning_results", show_default=True)
def kernels(matmul_size, seq_len, head_dim, heads, batch, max_iterations,
            timeout, trials, output_dir):
    """Tune matmul + attention kernels (parity: reference tune.py:13-69)."""
    tuner = _tuner(max_iterations, timeout, trials)
    m, k, n = matmul_size
    _report("matmul", tuner.tune_matmul(m, k, n))
    _report("attention", tuner.tune_attention(seq_len, head_dim, heads, batch))
    out = Path(output_dir) / "tuning_cache.json"
    tuner.save_results(out)
    click.echo(f"results cached to {out}")


@app.command()
@click.option("--size-mb", default=8.0, show_default=True, type=float)
@click.option("--devices", "n_devices", default=None, type=int)
@click.option("--max-iterations", default=32, show_default=True)
@click.option("--timeout", default=120.0, show_default=True)
@click.option("--trials", default=5, show_default=True)
@click.option("--output-dir", default="tuning_results", show_default=True)
def comms(size_mb, n_devices, max_iterations, timeout, trials, output_dir):
    """Tune collective dispatch over the live mesh
    (parity: reference tune.py:71-131 — but measured, not simulated)."""
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()[:n_devices] if n_devices else jax.devices()
    if len(devs) < 2:
        raise click.ClickException(
            "need >=2 devices; run under JAX_PLATFORMS=cpu "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    tuner = _tuner(max_iterations, timeout, trials)
    mesh = Mesh(devs, ("x",))
    _report("collective", tuner.tune_collective(mesh, "x", size_mb))
    out = Path(output_dir) / "tuning_cache.json"
    tuner.save_results(out)
    click.echo(f"results cached to {out}")


@app.command()
@click.option("--seq-lens", default="8192,16384", show_default=True,
              help="Comma-separated probe sequence lengths.")
@click.option("--sp", default=8, show_default=True,
              help="Sequence-parallel degree the probe shapes model.")
@click.option("--heads", default=16, show_default=True)
@click.option("--head-dim", default=128, show_default=True)
@click.option("--repeats", default=8, show_default=True)
@click.option("--save/--no-save", "save_calib", default=True,
              show_default=True)
def sp(seq_lens, sp, heads, head_dim, repeats, save_calib):
    """Measure ring-vs-Ulysses per-device attention cost and persist the
    per-scheme efficiencies the planner's selection rule uses
    (`parallel.planner.choose_sp_scheme`).

    Single-chip proxy: ring = sp lock-step (S/sp x S/sp) unmasked flash
    blocks (causal pruning can't shorten the ppermute-serialised critical
    path); ulysses = full-S causal flash over heads/sp. The measured
    efficiency vs each scheme's ideal FLOPs time extrapolates to any
    (model, S, sp) through the same FLOPs model the planner prices with.
    """

    import jax
    import jax.numpy as jnp

    from ...config.presets import get_hardware_preset
    from ...ops.attention import flash_attention
    from ...parallel.planner import (
        calibrate_sp_schemes, choose_sp_scheme, save_sp_calibration)

    if jax.default_backend() != "tpu":
        raise click.ClickException(
            "refusing to calibrate SP schemes on a "
            f"{jax.default_backend()} backend — efficiencies are measured "
            "against the TPU MXU peak and a CPU run would poison every "
            "future scheme choice")
    if sp < 2 or heads % sp or any(int(x) % sp for x in seq_lens.split(",")):
        raise click.ClickException(
            f"probe needs sp >= 2, heads ({heads}) % sp == 0 and every "
            f"seq len % sp == 0 — got sp={sp}, seq_lens={seq_lens}")
    # derive the peak from the ATTACHED chip, not an assumed generation —
    # efficiencies divided by the wrong peak poison every future choice
    kind = jax.devices()[0].device_kind.lower()
    if "v5 lite" in kind or "v5e" in kind:
        hw = get_hardware_preset("v5e-1")
    else:
        raise click.ClickException(
            f"no hardware preset for device kind '{kind}' — add its peak "
            "to config/presets.py HARDWARE_PRESETS before calibrating")

    def _time(causal, q, k):
        # scan the kernel `repeats` times inside ONE jitted program,
        # feeding each output back as the next query: serialises the
        # iterations and defeats DCE, so the figure is device compute —
        # per-call dispatch (~ms) otherwise dwarfs
        # these sub-ms kernels (the first round-3 battery measured a 16k
        # causal attention at an impossible 0.02 ms this way)
        def scanned(q_, k_):
            def body(carry, _):
                out = flash_attention(carry, k_, k_, causal=causal)
                return out.astype(carry.dtype), None
            return jax.lax.scan(body, q_, None, length=repeats)[0]

        prog = jax.jit(scanned)          # k as an ARG, not a baked constant
        # utils.timing fences by fetching a REDUCTION over the result:
        # battery-2 measured a 1024x1024 flash call at an impossible 4 us
        # when it trusted block_until_ready alone (not re-measured on a
        # directly attached chip; bench.py fences the same way)
        from ...utils.timing import time_fn
        return time_fn(prog, q, k, warmup=1, iters=4,
                       windows=2) / repeats * 1e3

    rows = []
    for s in (int(x) for x in seq_lens.split(",")):
        key = jax.random.PRNGKey(0)
        # ring step shape: local q against one rotating kv chunk, unmasked
        q = jax.random.normal(key, (1, s // sp, heads, head_dim),
                              jnp.bfloat16)
        k = jax.random.normal(key, (1, s // sp, heads, head_dim),
                              jnp.bfloat16)
        ring_step = _time(False, q, k)
        # ulysses shape: full sequence, heads/sp, causal
        qU = jax.random.normal(key, (1, s, heads // sp, head_dim),
                               jnp.bfloat16)
        kU = jax.random.normal(key, (1, s, heads // sp, head_dim),
                               jnp.bfloat16)
        uly = _time(True, qU, kU)
        rows.append({"S": s,
                     "ring_compute_ms_per_device": round(ring_step * sp, 3),
                     "ulysses_compute_ms_per_device": round(uly, 3)})
        click.echo(json.dumps(rows[-1]))

    calib = calibrate_sp_schemes(rows, hw, num_heads=heads,
                                 head_dim=head_dim, sp=sp)
    click.echo(json.dumps(calib))
    if save_calib:
        path = save_sp_calibration(calib)
        click.echo(f"sp calibration saved to {path}")
        from ...config.presets import get_model_config
        m = get_model_config("gpt-7b")
        for s in (8192, 16384, 32768):
            scheme, costs = choose_sp_scheme(m, sp, s, hw=hw,
                                             calibration=calib)
            click.echo(f"gpt-7b S={s} sp={sp}: {scheme} "
                       f"(ring {costs['ring_ms']:.0f} ms vs ulysses "
                       f"{costs['ulysses_ms']:.0f} ms)")


@app.command()
@click.option("--output-dir", default="tuning_results", show_default=True)
@click.option("--max-iterations", default=32, show_default=True)
@click.option("--timeout", default=300.0, show_default=True)
@click.option("--trials", default=5, show_default=True)
def full(output_dir, max_iterations, timeout, trials):
    """Tune everything and write a summary
    (parity: reference tune.py:133-209)."""
    import jax
    from jax.sharding import Mesh

    tuner = _tuner(max_iterations, timeout / 3, trials)
    summary = {}

    r = tuner.tune_matmul(1024, 1024, 1024)
    _report("matmul", r)
    summary["matmul"] = r.to_dict()

    r = tuner.tune_attention(512, 64, 8, 8)
    _report("attention", r)
    summary["attention"] = r.to_dict()

    devs = jax.devices()
    if len(devs) >= 2:
        r = tuner.tune_collective(Mesh(devs, ("x",)), "x", 8.0)
        _report("collective", r)
        summary["collective"] = r.to_dict()
    else:
        click.echo("collective: skipped (single device)")

    out_dir = Path(output_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "full_tuning_results.json").write_text(
        json.dumps(summary, indent=2))
    tuner.save_results(out_dir / "tuning_cache.json")
    click.echo(f"summary written to {out_dir}/full_tuning_results.json")
