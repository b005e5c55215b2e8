"""TrainingEngine: the orchestration loop tying every layer together.

Parity: reference TrainingEngine (engine.py:72-414) — but where that engine
wraps HF/Accelerate and leaves observability unwired, checkpoints cosmetic,
and data dummy (SURVEY §2.4.3/4, §5.5), this one drives the native stack:

    config -> mesh/ShardedTrainer -> io dataset -> jitted SPMD step loop
           -> metrics (wired), sharded async checkpoints (real), eval

One engine instance runs per HOST (single-controller JAX), not per device —
the reference's per-GPU rank processes (launcher.py:97-105) have no analog.
"""

from __future__ import annotations

import logging
import time
from pathlib import Path
from typing import Callable, Optional

import jax
import numpy as np

from ..config.schema import RunConfig
from ..io.checkpoint import CheckpointManager
from ..io.data import make_dataset
from ..metrics.spans import STARTUP
from ..models.gpt import flops_per_token
from ..parallel.api import ShardedTrainer
from ..parallel.mesh import infer_data_parallel
from ..utils import platform
from ..utils.platform import chip_peaks

logger = logging.getLogger("llmctl.engine")


class TrainingEngine:
    def __init__(self, cfg: RunConfig, devices: Optional[list] = None,
                 observer: Optional[Callable[[str, dict], None]] = None):
        """*observer(event, payload)* receives 'train_step'/'eval'/'save'
        events — the hook metrics/observability.py plugs into (closing the
        reference's unwired-metrics gap, SURVEY §5.5)."""
        self.cfg = cfg
        if cfg.model.is_diffusion:
            raise ValueError(
                f"{cfg.model.name} generates by diffusion over blocks: "
                "llmctl train is refused (there is no masked-diffusion loss "
                "here, and the next-token loss under a causal mask would "
                "train another model)")
        if cfg.model.is_looped:
            raise ValueError(
                f"{cfg.model.name} walks its stack {cfg.model.num_passes} "
                "times (total_ut_steps): llmctl train is refused (the "
                "published objective weighs every pass's loss by the exit "
                "distribution and adds an entropy term, which is not here; "
                "the next-token loss of the last pass alone would train "
                "another model)")
        devices = devices if devices is not None else platform.devices()
        self.par = infer_data_parallel(cfg.parallel, len(devices))
        self._start_step = 0
        attn_impl = cfg.training.attn_impl
        if cfg.model.has_window and (self.par.sequence_parallel > 1
                                     or attn_impl not in ("auto", "xla")):
            raise ValueError(
                f"{cfg.model.name} has window layers (sliding_window "
                f"{cfg.model.sliding_window}): llmctl train runs them under "
                "the XLA mask alone (attn_impl xla or auto, "
                "sequence_parallel 1): the flash kernel, ring and ulysses "
                "attention mask causally and have no window term; "
                "ROADMAP B3")
        if attn_impl == "auto":
            if cfg.model.has_window:
                attn_impl = "xla"         # the one route with the window
            elif self.par.sequence_parallel > 1:
                # ring vs ulysses by the planner's priced selection rule
                # (measured per-scheme efficiencies when `tune sp` has
                # calibrated this chip; analytic FLOPs/comm model otherwise)
                from ..parallel.planner import choose_sp_scheme
                attn_impl, _ = choose_sp_scheme(
                    cfg.model, self.par.sequence_parallel,
                    cfg.data.max_length, self.par.micro_batch_size,
                    hw=cfg.hardware)
            elif devices and devices[0].platform == "tpu":
                attn_impl = "flash"       # the Pallas kernel, compiled
            else:
                attn_impl = "xla"         # interpret-mode flash is too slow
        self.attn_impl = attn_impl
        self.trainer = ShardedTrainer(cfg.model, cfg.optimizer, self.par,
                                      devices=devices, attn_impl=self.attn_impl)
        self.observer = observer or (lambda event, payload: None)

        host_id, num_hosts = jax.process_index(), jax.process_count()
        per_host_batch = (self.par.global_batch_size // num_hosts)
        # tokenizer, index and (with workers) the first batches' prefetch
        with STARTUP.phase("llmctl.startup.data"):
            self.train_data = make_dataset(
                cfg.data.train, per_host_batch, cfg.data.max_length,
                cfg.model.vocab_size, seed=cfg.data.seed, host_id=host_id,
                num_hosts=num_hosts, pack=cfg.data.pack_sequences,
                num_workers=cfg.data.num_workers,
                prefetch=cfg.data.prefetch_factor)
            self.val_data = make_dataset(
                cfg.data.val, per_host_batch, cfg.data.max_length,
                cfg.model.vocab_size, seed=cfg.data.seed + 1,
                host_id=host_id, num_hosts=num_hosts,
                pack=cfg.data.pack_sequences)
        self.ckpt = CheckpointManager(
            cfg.checkpoint.path, keep_latest=cfg.checkpoint.keep_latest,
            async_save=cfg.checkpoint.async_save)
        self._flops_per_token = flops_per_token(cfg.model, cfg.data.max_length)
        # MFU is measured against the published peak of the device this
        # engine actually runs on (None on the CPU: no MFU is logged there;
        # an accelerator missing from the table raises)
        peaks = chip_peaks(devices[0].platform, devices[0].device_kind)
        self._peak_flops = peaks and peaks["peak_bf16_tflops"] * 1e12

    # -- lifecycle -----------------------------------------------------------

    def initialize(self, resume: bool = True) -> int:
        """Init or restore state. Returns the starting step. Idempotent:
        train() reuses an already-initialised state instead of re-restoring
        (so `--no-resume` + train() stays fresh)."""
        self.trainer.init_state(seed=self.cfg.training.seed)
        self._start_step = 0
        if resume and self.ckpt.latest_step() is not None:
            state, extra = self.ckpt.restore(
                target=self.trainer.state,
                shardings=self.trainer._state_shardings)
            self.trainer.state = state
            if "train_data" in extra:
                self.train_data.load_state_dict(extra["train_data"])
            start = int(extra.get("step", self.ckpt.latest_step()))
            logger.info("resumed from checkpoint step %d (params + optimizer "
                        "+ data cursor)", start)
            self._start_step = start
            return start
        return 0

    def close(self) -> None:
        """Release dataset resources: remote-URI datasets hold a download
        thread pool and (by default) a tmp cache dir holding a full copy
        of every fetched shard — without this, each run leaks both
        (round-3 review). Idempotent; the engine is not reusable after."""
        for ds in (self.train_data, self.val_data):
            if hasattr(ds, "close"):
                try:
                    ds.close()
                except Exception:
                    logger.exception("dataset close failed")

    def save(self, step: int) -> None:
        self.ckpt.save(step, self.trainer.state, extra={
            "step": step,
            "train_data": self.train_data.state_dict(),
            "config": {"model": self.cfg.model.name},
        })
        self.observer("save", {"step": step})

    # -- loops ----------------------------------------------------------------

    def train(self, max_steps: Optional[int] = None, resume: bool = True) -> dict:
        t_cfg = self.cfg.training
        max_steps = max_steps or t_cfg.max_steps
        if self.trainer.state is None:
            start = self.initialize(resume=resume)
        else:
            start = self._start_step
        chips = self.trainer.mesh.size
        window_t0, window_tokens = time.perf_counter(), 0.0
        last_metrics: dict = {}
        last_saved: Optional[int] = None

        if t_cfg.profile:
            jax.profiler.start_trace(t_cfg.profile_dir)

        for step in range(start, max_steps):
            batch = next(self.train_data)
            metrics = self.trainer.step(batch)
            window_tokens += float(batch["tokens"].size) * jax.process_count()
            if STARTUP.ready_t is None:
                # the first finished step ends start-up: one fetch, once
                jax.block_until_ready(metrics["loss"])
                STARTUP.ready()
                logger.info(STARTUP.summary())

            if (step + 1) % t_cfg.log_interval == 0 or step + 1 == max_steps:
                # block only at log boundaries: keeps the device queue full
                loss = float(metrics["loss"])
                dt = time.perf_counter() - window_t0
                tokens_per_sec = window_tokens / dt
                last_metrics = {
                    "step": step + 1, "loss": loss,
                    "grad_norm": float(metrics["grad_norm"]),
                    "lr": float(metrics["lr"]),
                    "tokens_per_sec": tokens_per_sec,
                    "tokens_per_sec_per_chip": tokens_per_sec / chips,
                }
                mfu_text = ""
                if self._peak_flops:
                    mfu = (tokens_per_sec * self._flops_per_token
                           / (chips * self._peak_flops))
                    last_metrics["mfu"] = mfu
                    mfu_text = f" | mfu {100 * mfu:.1f}%"
                self.observer("train_step", last_metrics)
                logger.info(
                    "step %d | loss %.4f | grad %.3f | lr %.2e | "
                    "%.0f tok/s (%.0f/chip)%s",
                    step + 1, loss, last_metrics["grad_norm"],
                    last_metrics["lr"], tokens_per_sec,
                    tokens_per_sec / chips, mfu_text)
                window_t0, window_tokens = time.perf_counter(), 0.0

            if (step + 1) % t_cfg.eval_interval == 0 and step + 1 < max_steps:
                ev = self.evaluate()
                self.observer("eval", ev)
                logger.info("eval @ %d | loss %.4f | ppl %.2f",
                            step + 1, ev["loss"], ev["perplexity"])
                window_t0, window_tokens = time.perf_counter(), 0.0

            if (step + 1) % self.cfg.checkpoint.interval_steps == 0:
                self.save(step + 1)
                last_saved = step + 1

        if t_cfg.profile:
            jax.profiler.stop_trace()
        # don't re-save a step the interval already covered: the duplicate
        # save re-creates step_N.tmp AFTER other hosts wrote their done
        # markers and exited, so host 0 waits the full commit deadline for
        # markers that will never come (found by the two-process test)
        if last_saved != max_steps:
            self.save(max_steps)
        self.ckpt.wait()
        self._write_manifest(start, max_steps, last_metrics)
        return last_metrics

    def _write_manifest(self, start_step: int, end_step: int,
                        final_metrics: dict) -> None:
        """Record everything needed to re-run this training deterministically
        — the basis of `llmctl replay` (the reference's replay is a stub and
        its seed is plumbed but never applied, SURVEY §5.2)."""
        import json
        manifest = {
            "run_id": f"{self.cfg.model.name}-s{self.cfg.training.seed}"
                      f"-{start_step}to{end_step}",
            "config": self.cfg.to_dict(),
            "seed": self.cfg.training.seed,
            "data_seed": self.cfg.data.seed,
            "start_step": start_step,
            "end_step": end_step,
            "num_hosts": jax.process_count(),
            "num_devices": self.trainer.mesh.size,
            "final_metrics": {k: v for k, v in final_metrics.items()
                              if isinstance(v, (int, float))},
        }
        if jax.process_index() == 0:
            path = Path(self.cfg.checkpoint.path) / "run_manifest.json"
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text(json.dumps(manifest, indent=2))

    def evaluate(self, num_batches: Optional[int] = None) -> dict:
        num_batches = num_batches or self.cfg.training.eval_steps
        losses, counts = [], []
        for _ in range(num_batches):
            out = self.trainer.evaluate(next(self.val_data))
            losses.append(float(out["loss"]))
            counts.append(float(out["tokens"]))
        total = float(np.sum(counts))
        loss = float(np.sum([l * c for l, c in zip(losses, counts)])) / max(total, 1)
        return {"loss": loss, "perplexity": float(np.exp(min(loss, 30.0))),
                "tokens": total}


STARTUP.imported()      # an entry module: llmctl.startup.import ends here
