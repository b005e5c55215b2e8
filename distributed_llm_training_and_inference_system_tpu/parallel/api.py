"""High-level SPMD training setup: mesh + shardings + jitted step in one call.

This is the executable replacement for the reference's launch chain
(train.py:16 -> launcher.py:94 -> torchrun -> engine.py:103: one process per
GPU, NCCL rendezvous, DDP wrap). Here one Python process per host builds a
mesh, places params/optimizer state by the sharding rules, and jits the
train step; XLA inserts every collective.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, Optional

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config.schema import ModelConfig, OptimizerConfig, ParallelConfig
from ..exec.train_step import TrainState, make_eval_step, make_train_step
from ..metrics.spans import STARTUP
from ..models import gpt
from .mesh import build_mesh
from .sharding import batch_specs, param_specs, use_mesh
from .zero import opt_state_specs


def state_specs(model_cfg: ModelConfig, tx, mesh: Mesh,
                zero_stage: int = 0) -> tuple[Any, Any]:
    """(TrainState spec pytree, abstract TrainState) without materialising
    any arrays (jax.eval_shape)."""
    abstract_params = jax.eval_shape(
        lambda: gpt.init(model_cfg, jax.random.PRNGKey(0)))
    p_specs = param_specs(abstract_params, mesh)
    abstract_opt = jax.eval_shape(tx.init, abstract_params)
    o_specs = opt_state_specs(abstract_opt, abstract_params, p_specs, mesh,
                              zero_stage)
    specs = TrainState(step=P(), params=p_specs, opt_state=o_specs)
    abstract = TrainState(step=jax.ShapeDtypeStruct((), "int32"),
                          params=abstract_params, opt_state=abstract_opt)
    return specs, abstract


def _to_shardings(spec_tree: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


class ShardedTrainer:
    """Owns mesh, sharded TrainState, and the compiled SPMD train step."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        opt_cfg: OptimizerConfig,
        par_cfg: ParallelConfig,
        devices: Optional[list] = None,
        attn_impl: str = "xla",
    ):
        self.model_cfg = model_cfg
        self.par_cfg = par_cfg
        self.mesh = build_mesh(par_cfg, devices)
        self.pipelined = par_cfg.pipeline_parallel > 1
        custom_loss = custom_grad = None
        if self.pipelined:
            if par_cfg.pipeline_schedule == "1f1b" and not model_cfg.is_moe:
                from .pipeline import make_pipeline_grad_fn
                custom_grad = make_pipeline_grad_fn(model_cfg, par_cfg,
                                                    attn_impl)
            else:
                # MoE needs the autodiff (GPipe) schedule for its aux-loss
                # gradient path
                from .pipeline import make_pipeline_loss_fn
                custom_loss = make_pipeline_loss_fn(model_cfg, par_cfg,
                                                    attn_impl)
        step_fn, tx, schedule = make_train_step(
            model_cfg, opt_cfg, par_cfg, attn_impl=attn_impl,
            loss_fn=custom_loss, grad_fn=custom_grad)
        self.tx, self.schedule = tx, schedule
        self._specs, self._abstract = state_specs(
            model_cfg, tx, self.mesh, par_cfg.zero_stage)
        self._state_shardings = _to_shardings(self._specs, self.mesh)

        self.train_step = jax.jit(
            step_fn,
            in_shardings=(self._state_shardings, None),
            out_shardings=(self._state_shardings, None),
            donate_argnums=(0,),
        )
        self.eval_step = jax.jit(make_eval_step(
            model_cfg,
            attn_impl if attn_impl not in ("ring", "ulysses") else "xla"))
        if self.pipelined:
            from .pipeline import pipeline_batch_specs
            self._batch_spec_fn = functools.partial(pipeline_batch_specs,
                                                    mesh=self.mesh)
        else:
            self._batch_spec_fn = functools.partial(batch_specs, mesh=self.mesh)
        self.state: Optional[TrainState] = None
        self._steps_dispatched = 0
        # programs whose first call (trace, lowering, compile) has been
        # made under its llmctl.startup.program span
        self._first_called: set[str] = set()

    def _first_call(self, name: str):
        """The ``llmctl.startup.program`` span of a program's first call; a
        later call gets a context that does nothing."""
        if name in self._first_called:
            return contextlib.nullcontext()
        self._first_called.add(name)
        return STARTUP.program(name)

    # -- state ---------------------------------------------------------------

    def init_state(self, seed: int = 0) -> TrainState:
        """Initialise params directly INTO their shards (each device
        materialises only its slice — no host-RAM staging of a 7B pytree,
        unlike reference engine.py:119-140 which loads the whole model per
        rank)."""
        def make():
            params = gpt.init(self.model_cfg, jax.random.PRNGKey(seed))
            return TrainState.create(params, self.tx)

        # ``make`` closes over the seed, so every seed is a new program
        with STARTUP.phase("llmctl.startup.params"), \
                STARTUP.program("init_state"), use_mesh(self.mesh):
            self.state = jax.jit(make, out_shardings=self._state_shardings)()
        return self.state

    def shard_batch(self, batch: Any) -> Any:
        if self.pipelined and batch["tokens"].ndim == 2:
            from .pipeline import reshape_batch_for_pipeline
            batch = reshape_batch_for_pipeline(
                batch, self.par_cfg.num_microbatches)
        shardings = _to_shardings(self._batch_spec_fn(batch), self.mesh)
        if jax.process_count() > 1:
            # each host holds a disjoint stripe of the global batch
            # (io/data.py host striping) — assemble the global array from
            # per-process local shards
            return jax.tree_util.tree_map(
                lambda x, s: jax.make_array_from_process_local_data(s, x),
                batch, shardings)
        return jax.device_put(batch, shardings)

    def step(self, batch: Any):
        assert self.state is not None, "call init_state() first"
        # host spans on the profiler's clock (`llmctl trace summarize` reads
        # them); step_num is the trainer's own count of dispatched steps
        self._steps_dispatched += 1
        with StepTraceAnnotation("llmctl.train.step",
                                 step_num=self._steps_dispatched), \
                use_mesh(self.mesh):
            with TraceAnnotation("llmctl.train.shard_batch"):
                batch = self.shard_batch(batch)
            if "train_step" not in self._first_called:
                self._note_loss_backward(batch["tokens"].shape)
            with TraceAnnotation("llmctl.train.dispatch"), \
                    self._first_call("train_step"):
                self.state, metrics = self.train_step(self.state, batch)
        return metrics

    def _note_loss_backward(self, tokens_shape: tuple[int, ...]) -> None:
        """The start-up note ``chunked_loss_bwd``, once, beside the
        ``train_step`` program's span: which axis the loss's backward walks
        in the step about to be traced, in how many slices of what width,
        and the bytes its loop carries and holds (``models/loss.py``; the
        step is built with that module's default chunk). A micro-batch is
        ``[mb, S]`` of the pipeline's ``[M, mb, S]``, else a
        ``gradient_accumulation_steps``-th of ``[B, S]``."""
        from ..models.loss import chunked_loss_backward_plan
        *_, rows, seq = tokens_shape
        if not self.pipelined:
            rows //= max(self.par_cfg.gradient_accumulation_steps, 1)
        STARTUP.note("chunked_loss_bwd", **chunked_loss_backward_plan(
            rows, seq, self.model_cfg.hidden_size,
            self.model_cfg.vocab_size)._asdict())

    def lower_step(self, batch: Any):
        """``train_step`` lowered for SHAPES alone: the state as
        ``ShapeDtypeStruct``s on its shardings and *batch* (arrays or
        shapes, ``[B, S]``) on the batch's. Nothing is placed or run, so
        the mesh may be made of DESCRIBED devices (``jax.experimental
        .topologies``): ``.compile()`` then says what the chip's compiler
        would, its ``as_text()`` which collectives GSPMD chose
        (``comms.hlo.collectives``), its ``memory_analysis()`` what the
        step needs."""
        def shapes(tree, shardings):
            return jax.tree_util.tree_map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=s),
                tree, shardings)
        state = shapes(self._abstract, self._state_shardings)
        batch = shapes(batch, _to_shardings(self._batch_spec_fn(batch),
                                            self.mesh))
        with use_mesh(self.mesh):
            return self.train_step.lower(state, batch)

    def evaluate(self, batch: Any):
        assert self.state is not None, "call init_state() first"
        with use_mesh(self.mesh):
            # eval always runs the plain (non-pipelined) forward on [B, S]
            shardings = _to_shardings(batch_specs(batch, self.mesh), self.mesh)
            with self._first_call("eval_step"):
                return self.eval_step(self.state.params,
                                      jax.device_put(batch, shardings))

    # -- introspection -------------------------------------------------------

    def param_count(self) -> int:
        from ..utils.tree import param_count
        return param_count(self._abstract.params)

    def describe_shardings(self) -> dict[str, str]:
        from ..utils.tree import flatten_with_paths
        return {path: str(spec) for (path, _), spec in zip(
            flatten_with_paths(self._abstract.params),
            jax.tree_util.tree_leaves(self._specs.params,
                                      is_leaf=lambda x: isinstance(x, P)))}
