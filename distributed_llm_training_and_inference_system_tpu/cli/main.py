"""llmctl — CLI entry point.

Parity: reference llmctl/cli/main.py:19-56 registers 13 subcommand modules
on a Typer app with global options (backend/launcher/nodes/mixed-precision/
seed/deterministic/otlp-endpoint, main.py:59-139). This build uses click
(typer is not in the environment) and — unlike the reference, which parses
the global options and drops them (SURVEY §5.6) — stores them in the click
context for subcommands to consume.

Subcommand modules are registered lazily so `llmctl --help` stays fast and
config-only commands never import jax.
"""

from __future__ import annotations

import importlib
import logging
import os

import click

from .. import __version__


from ..utils.platform import enable_compile_cache as _enable_compile_cache

# before any command imports jax: the cache directory travels through the
# environment, so spawned children (train launcher, fleet workers) share it
_enable_compile_cache()

# command name -> module under .commands (each defines a click group/command
# named `app`). Mirrors the reference's registration table (main.py:44-56).
_COMMANDS = {
    "init": "init",
    "hw": "hw",
    "plan": "plan",
    "train": "train",
    "eval": "eval_cmd",
    "export": "export",
    "serve": "serve",
    "fleet": "fleet",
    "bench": "bench",
    "trace": "trace",
    "replay": "replay",
    "tune": "tune",
    "health": "health",
    "admin": "admin",
}


class _LazyGroup(click.Group):
    def list_commands(self, ctx):
        import importlib.util
        return [n for n, m in _COMMANDS.items()
                if importlib.util.find_spec(f"{__package__}.commands.{m}") is not None]

    def get_command(self, ctx, name):
        if name not in _COMMANDS:
            return None
        try:
            mod = importlib.import_module(
                f".commands.{_COMMANDS[name]}", package=__package__)
        except ModuleNotFoundError as e:
            raise click.ClickException(
                f"command {name!r} failed to load: {e}") from e
        return mod.app


@click.command(cls=_LazyGroup, name="llmctl")
@click.version_option(__version__, prog_name="llmctl")
@click.option("--backend", default="xla", show_default=True,
              help="Communication backend (xla collectives over ICI/DCN).")
@click.option("--launcher", default="local", show_default=True,
              type=click.Choice(["local", "slurm", "mpi", "k8s", "gke"]),
              help="Multi-host launcher.")
@click.option("--nodes", default=1, show_default=True, help="Number of hosts.")
@click.option("--chips-per-node", "--gpus-per-node", "chips_per_node",
              default=None, type=int, help="Chips per host (auto-detected).")
@click.option("--mixed-precision", default="bf16", show_default=True,
              type=click.Choice(["bf16", "fp32", "no"]))
@click.option("--seed", default=42, show_default=True, type=int)
@click.option("--deterministic", is_flag=True, default=False,
              help="Bit-deterministic mode (fixed PRNG keys + deterministic XLA ops).")
@click.option("--log-level", default="INFO", show_default=True)
@click.option("--otlp-endpoint", default=None,
              help="Accepted and ignored: nothing is exported over OTLP.")
@click.option("--platform", default=None, type=click.Choice(["tpu", "cpu"]),
              help="Force the JAX platform (cpu = host simulation).")
@click.option("--fake-devices", default=None, type=int,
              help="With --platform cpu: simulate N devices "
                   "(XLA host-platform device count).")
@click.pass_context
def main(ctx, **global_opts):
    """llmctl — TPU-native distributed LLM training and inference control."""
    ctx.ensure_object(dict)
    ctx.obj.update(global_opts)
    logging.basicConfig(
        level=global_opts["log_level"].upper(),
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    if global_opts.get("otlp_endpoint"):
        logging.getLogger("llmctl").warning(
            "--otlp-endpoint is not supported and is ignored: metrics leave "
            "through --prometheus-port, /v1/stats and `llmctl trace`")
    if global_opts.get("fake_devices"):
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{global_opts['fake_devices']}").strip()
    if global_opts.get("platform"):
        # backends are created lazily, so the live config wins over
        # whatever JAX_PLATFORMS said at import
        import jax
        jax.config.update("jax_platforms", global_opts["platform"])


if __name__ == "__main__":
    main()
