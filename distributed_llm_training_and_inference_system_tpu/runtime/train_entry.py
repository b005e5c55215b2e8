"""Per-host training entrypoint (reference runtime/train_script.py:96-162).

Run by every launcher as ``python -m ...runtime.train_entry --config f.toml
[overrides]``. Initialises jax.distributed when the launcher provided a
coordinator (multi-host), builds the engine, trains. Config precedence is
file < env (LLMCTL_*) < CLI flags via config.loader.
"""

from __future__ import annotations

import argparse
import logging
import os
import sys

from ..utils.platform import device_line, enable_compile_cache


def maybe_init_distributed() -> bool:
    """Join the jax.distributed rendezvous when the launcher provided one.

    Env contract (written by runtime/launcher.py:_train_env, exported by
    the SLURM script / k8s manifest / mpirun -x): LLMCTL_COORDINATOR is
    host:port of process 0, LLMCTL_NUM_HOSTS the world size,
    LLMCTL_HOST_ID this process's id (falls back to the OpenMPI rank).
    This is the TPU-native equivalent of the reference's MASTER_ADDR
    TCP rendezvous (reference llmctl/runtime/launcher.py:73-79), and —
    unlike the reference's, which no test ever spawns — it is exercised
    by a REAL two-process test (tests/test_runtime.py::
    test_two_process_rendezvous_psum_and_checkpoint).

    Returns True when distributed init ran."""
    coord = os.environ.get("LLMCTL_COORDINATOR")
    if not coord:
        return False
    import jax
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(os.environ["LLMCTL_NUM_HOSTS"]),
        process_id=int(os.environ.get(
            "LLMCTL_HOST_ID",
            os.environ.get("OMPI_COMM_WORLD_RANK", "0"))))
    return True


def parse_overrides(pairs: list[str]) -> dict:
    """--set section.field=value overrides."""
    out: dict = {}
    for p in pairs:
        key, _, val = p.partition("=")
        section, _, field_ = key.partition(".")
        from ..config.loader import _coerce
        out.setdefault(section, {})[field_] = _coerce(val)
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser("llmctl-train-entry")
    ap.add_argument("--config", default=None, help="run config TOML/JSON")
    ap.add_argument("--model", default=None, help="model template name")
    ap.add_argument("--max-steps", type=int, default=None)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--set", action="append", default=[], metavar="SEC.KEY=V",
                    help="config override, repeatable")
    args = ap.parse_args(argv)

    logging.basicConfig(
        level=os.environ.get("LLMCTL_LOG_LEVEL", "INFO"),
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    cache_dir = enable_compile_cache()
    # multi-host rendezvous (set by runtime/launcher.py)
    maybe_init_distributed()
    log = logging.getLogger("llmctl.train")
    log.info("%s | compile cache %s", device_line(), cache_dir)

    from ..config.loader import load_run_config
    overrides = parse_overrides(args.set)
    if args.max_steps is not None:
        overrides.setdefault("training", {})["max_steps"] = args.max_steps
    cfg = load_run_config(args.config, cli_overrides=overrides)
    if args.model:
        from ..config.presets import get_model_config
        cfg.model = get_model_config(args.model)

    from ..metrics.observability import engine_observer
    from .engine import TrainingEngine
    engine = TrainingEngine(cfg, observer=engine_observer())
    try:
        final = engine.train(resume=not args.no_resume)
    finally:
        engine.close()
    log.info("finished: %s", final)
    return 0


if __name__ == "__main__":
    sys.exit(main())
