"""`llmctl hw` — hardware probe and microbenchmark.

Parity: reference cli/commands/hw.py (probe :133-282, benchmark :284-345) —
reshaped for TPU: the probe reads `jax.devices()` / chip topology / HBM
instead of nvidia-smi, and the benchmark measures real matmul FLOPs and
memory bandwidth on the active backend (the reference hardcodes A100 limits,
hw.py:179-184).
"""

from __future__ import annotations

import platform as _platform
from pathlib import Path

import click

from ...utils.tomlio import dump_toml


def _cpu_info() -> dict:
    import psutil
    freq = psutil.cpu_freq()
    return {
        "model": _platform.processor() or _platform.machine(),
        "cores_physical": psutil.cpu_count(logical=False) or 0,
        "cores_logical": psutil.cpu_count(logical=True) or 0,
        "freq_mhz": freq.current if freq else 0.0,
    }


def _memory_info() -> dict:
    import psutil
    vm = psutil.virtual_memory()
    return {"total_gb": vm.total / 1e9, "available_gb": vm.available / 1e9}


def _chip_info() -> dict:
    """TPU probe: devices, topology coords, memory stats where exposed."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    info = {
        "platform": d0.platform,
        "num_chips": len(devices),
        "num_hosts": jax.process_count(),
        "device_kind": d0.device_kind,
        "devices": [
            {"id": d.id, "process": d.process_index,
             "coords": list(getattr(d, "coords", []) or []),
             "core_on_chip": getattr(d, "core_on_chip", 0)}
            for d in devices
        ],
    }
    try:
        stats = d0.memory_stats()
        if stats:
            info["hbm_gb_per_chip"] = stats.get("bytes_limit", 0) / 1e9
    except Exception:
        pass
    return info


def _limits(chips: dict) -> dict:
    """Peaks for the probed device from the one table
    (utils/platform.CHIP_PEAKS). An accelerator the table does not know
    is an error; the CPU gets the nominal figures of the ``cpu`` hardware
    preset (what `llmctl plan` on a host simulation needs), labelled as
    NOT a device peak."""
    from ...utils.platform import UnknownChipError, chip_peaks
    try:
        peaks = chip_peaks(chips.get("platform", ""),
                           chips.get("device_kind", ""))
    except UnknownChipError as e:
        raise click.ClickException(str(e)) from None
    if peaks is None:
        from ...config.presets import get_hardware_preset
        cpu = get_hardware_preset("cpu-8")
        return {"peak_bf16_tflops": cpu.peak_bf16_tflops,
                "hbm_bw_gbps": cpu.hbm_bw_gbps,
                "source": "cpu-nominal (not a device peak)",
                "chip_family": "cpu"}
    return peaks


@click.group(name="hw", invoke_without_command=True)
@click.pass_context
def app(ctx):
    """Hardware probing and benchmarking."""
    if ctx.invoked_subcommand is None:
        ctx.invoke(probe)


@app.command()
@click.option("--emit", "emit_path", default=None,
              type=click.Path(dir_okay=False),
              help="Write the profile to a TOML/JSON file.")
def probe(emit_path):
    """Probe CPU, memory, and accelerator chips; optionally emit a profile."""
    from rich.console import Console
    from rich.table import Table

    cpu, mem, chips = _cpu_info(), _memory_info(), _chip_info()
    limits = _limits(chips)
    profile = {
        "system": {"os": _platform.system(), "python": _platform.python_version()},
        "cpu": cpu, "memory": mem, "chips": chips, "limits": limits,
        "hardware": {
            "platform": chips["platform"],
            "chip_type": chips["device_kind"],
            "num_chips": chips["num_chips"],
            "num_hosts": chips["num_hosts"],
            "hbm_gb_per_chip": chips.get("hbm_gb_per_chip", 0.0),
            "peak_bf16_tflops": limits["peak_bf16_tflops"],
            "hbm_bw_gbps": limits["hbm_bw_gbps"],
        },
    }

    console = Console()
    table = Table(title="Hardware Profile")
    table.add_column("Component")
    table.add_column("Details")
    table.add_row("Platform", f"{chips['platform']} ({chips['device_kind']})")
    table.add_row("Chips", f"{chips['num_chips']} on {chips['num_hosts']} host(s)")
    table.add_row("CPU", f"{cpu['model']} ({cpu['cores_logical']} threads)")
    table.add_row("Host memory", f"{mem['total_gb']:.1f} GB")
    if "hbm_gb_per_chip" in chips:
        table.add_row("HBM / chip", f"{chips['hbm_gb_per_chip']:.1f} GB")
    table.add_row("Peak bf16", f"{limits['peak_bf16_tflops']:.1f} TFLOPs/chip "
                               f"({limits['source']})")
    console.print(table)

    if emit_path:
        p = Path(emit_path)
        if p.suffix == ".json":
            import json
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(json.dumps(profile, indent=2))
        else:
            dump_toml(profile, p)
        click.echo(f"Profile written to {p}")


@app.command()
@click.option("--matmul-size", default=4096, show_default=True)
@click.option("--mem-size-mb", default=256, show_default=True)
def benchmark(matmul_size: int, mem_size_mb: int):
    """Measure achieved matmul TFLOPs and HBM bandwidth (real, not assumed).

    Parity: reference hw.py:284-345 (numpy memory + torch matmul) — but on
    the JAX backend so the numbers are the chips', not the host's.
    """
    import time as _time

    import jax
    import jax.numpy as jnp

    # Methodology (see BASELINE.md; not re-measured on a directly
    # attached chip):
    # - R ops chained inside ONE jit (per-dispatch overhead is 5-9 ms);
    # - successive CALLS must be data-DEPENDENT (x = f(x, ...)) — identical
    #   independent calls have been observed completing impossibly fast
    #   (result reuse), inflating rates past the datasheet peak;
    # - the fence fetches a reduction over the result; its own round-trip
    #   cost is measured on a ready value and subtracted;
    # - chained elementwise passes would fuse to ONE memory pass, so the
    #   bandwidth chain transposes between passes.

    def fence(x):
        return float(jnp.sum(jnp.abs(x.astype(jnp.float32))))

    def timed_chain(step, x0, calls):
        x = step(x0)
        fence(x)                                  # compile step + fence
        t0 = _time.perf_counter()
        fence(x)
        fence_cost = _time.perf_counter() - t0    # pure round trip
        samples = []
        for _ in range(3):
            t0 = _time.perf_counter()
            for _ in range(calls):
                x = step(x)
            fence(x)
            raw = _time.perf_counter() - t0
            samples.append(max(raw - fence_cost, 0.25 * raw) / calls)
        samples.sort()
        spread = (samples[-1] - samples[0]) / samples[1]
        return samples[1], spread                 # median, rel spread

    R = 32
    n = matmul_size
    a = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.bfloat16)

    @jax.jit
    def mm_chain(x):
        for _ in range(R):
            # rescale so bf16 magnitudes stay bounded across the chain
            x = (x @ b * 0.01).astype(jnp.bfloat16)
        return x

    sec, mm_spread = timed_chain(mm_chain, a, calls=10)
    tflops = R * 2 * n**3 / sec / 1e12

    rows = 4096
    elems = (mem_size_mb * 1024 * 1024 // 4 // rows) * rows
    x0 = jnp.ones((rows, elems // rows), jnp.float32)

    @jax.jit
    def stream_chain(v):
        for _ in range(R // 2):
            v = v.T * 1.0000001
            v = v.T + 1e-7
        return v

    sec, bw_spread = timed_chain(stream_chain, x0, calls=10)
    # read + write per element per pass
    bw = R * 2 * elems * 4 / sec / 1e9

    backend = jax.default_backend()
    limits = _limits(_chip_info()) if backend == "tpu" else None
    click.echo(f"backend={backend}")
    click.echo(f"matmul {n}x{n}x{n} bf16: {tflops:.2f} TFLOPs "
               f"(±{mm_spread * 100:.0f}%)")
    click.echo(f"memory bandwidth ({mem_size_mb} MB stream): {bw:.1f} GB/s "
               f"(±{bw_spread * 100:.0f}%)")
    if limits and limits["source"] == "datasheet":
        click.echo(f"datasheet peaks: {limits['peak_bf16_tflops']:.0f} "
                   f"TFLOPs, {limits['hbm_bw_gbps']:.0f} GB/s — measured "
                   "numbers beyond these indicate timing noise; "
                   "prefer `llmctl plan verify` "
                   "(whole-step timing) for calibration")
