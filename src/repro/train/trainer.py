"""Trainer: the paper's m-Synchronous SGD as a first-class training policy.

Every step:
  1. the straggler model (Assumption 2.2/3.1 instance) draws per-worker
     compute times in one vectorized call and the aggregation strategy
     (:mod:`repro.core.strategies`; ``sync`` / ``msync`` / ``auto_m`` /
     ``deadline`` — or a legacy :class:`~repro.core.sync_engine.SyncPolicy`)
     resolves the participation mask;
  2. the mask is folded into per-example loss weights
     (:func:`participation_example_weights`) so the ordinary data-parallel
     all-reduce computes exactly the Algorithm 3 estimator;
  3. simulated wall-clock advances by the m-th order statistic of the drawn
     times — loss curves are reported against *time*, like the paper's
     figures.

Works on CPU (smoke scale) and, unchanged, on a data-parallel mesh: the
jitted step is shape-identical; only `ctx` changes. With a mesh,
parameters and optimizer state are replicated and each batch (and its
participation weights) is split over the data axes.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Any, Dict, Iterator, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..core.strategies import (AggregationStrategy, make_strategy)
from ..core.sync_engine import (SimulatedStraggler, SyncPolicy, SyncMode,
                                participation_example_weights)
from ..core.time_models import TimeModel
from ..models import Model, build_model
from ..optim.optimizers import Optimizer
from ..sharding.specs import ShardCtx

__all__ = ["TrainState", "Trainer", "TrainHistory"]


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: int = 0


@dataclasses.dataclass
class TrainHistory:
    steps: list = dataclasses.field(default_factory=list)
    sim_seconds: list = dataclasses.field(default_factory=list)
    losses: list = dataclasses.field(default_factory=list)
    m_used: list = dataclasses.field(default_factory=list)
    wall_seconds: list = dataclasses.field(default_factory=list)


class Trainer:
    def __init__(self, model: Model, optimizer: Optimizer, *,
                 n_workers: int = 8,
                 sync_policy: Optional[SyncPolicy] = None,
                 strategy: Optional[Union[str, AggregationStrategy]] = None,
                 time_model: Optional[TimeModel] = None,
                 ctx: Optional[ShardCtx] = None,
                 remat: bool = False, seed: int = 0,
                 impl: str = "ref", grad_delay: int = 0) -> None:
        """``strategy`` is any mesh-capable aggregation strategy (an
        :class:`~repro.core.strategies.AggregationStrategy` instance or a
        ``STRATEGIES`` registry name); ``sync_policy`` is the deprecated
        enum-based spelling of the same thing and must not be combined
        with it.

        ``grad_delay=d > 0`` runs the SPMD-realizable form of
        Asynchronous SGD (Algorithm 2): the gradient is computed at the
        parameters from ``d`` steps ago and applied to the current ones —
        the pipelined/delayed-gradient schedule a synchronous pod can
        actually execute (Stich & Karimireddy 2020). Incompatible with an
        m-sync policy (the paper's point is you don't need both)."""
        self.model = model
        self.optimizer = optimizer
        self.n_workers = n_workers
        self.ctx = ctx or ShardCtx.null()
        self.remat = remat
        self.impl = impl
        if strategy is not None and sync_policy is not None:
            raise ValueError("pass either strategy= or sync_policy=, "
                             "not both")
        if isinstance(strategy, str):
            strategy = make_strategy(strategy)
        if strategy is None:
            strategy = (sync_policy or SyncPolicy(SyncMode.FULL)) \
                .to_strategy()
        self.strategy = strategy
        self.straggler = (SimulatedStraggler(time_model, strategy,
                                             seed=seed)
                          if time_model is not None else None)
        self.grad_delay = grad_delay
        if grad_delay and strategy.name != "sync":
            raise ValueError("grad_delay is an asynchronous-baseline mode; "
                             "combine with the full-sync strategy only")
        self._param_fifo: deque = deque()   # delayed-gradient params, O(1) popleft
        self._seed = seed
        self._step_fn = None

    # -------------------------------------------------------------- init
    def init_state(self, key=None) -> TrainState:
        """Fresh parameters and optimizer state. With a mesh in ``ctx``
        both are replicated over it (data parallelism: the batch is
        what gets split)."""
        key = jax.random.key(self._seed) if key is None else key
        params = self.model.init_params(key)
        opt_state = self.optimizer.init(params)
        if self.ctx.mesh is not None:
            if self.ctx.model_axis is not None:
                raise NotImplementedError(
                    "Trainer places data-parallel meshes only: pass a "
                    "ShardCtx with model_axis=None")
            params, opt_state = jax.device_put((params, opt_state),
                                               self.ctx.sharding())
        return TrainState(params, opt_state, 0)

    # -------------------------------------------------------------- step
    def _build_step(self):
        model, opt = self.model, self.optimizer
        ctx, remat, impl = self.ctx, self.remat, self.impl

        def step_fn(params, opt_state, batch, example_weights, step,
                    grad_params):
            # grad_params=None => synchronous (gradient at current params);
            # passing params twice would alias a donated buffer.
            gp = params if grad_params is None else grad_params

            def loss_fn(p):
                return model.loss(p, batch, ctx, remat=remat, impl=impl,
                                  example_weights=example_weights)
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(gp)
            new_params, new_opt = opt.update(grads, opt_state, params, step)
            # per-step gradient variance proxy for AUTO_M's sigma estimate
            gsq = sum(jnp.sum(g.astype(jnp.float32) ** 2)
                      for g in jax.tree.leaves(grads))
            metrics = dict(metrics, loss=loss, grad_sq=gsq)
            return new_params, new_opt, metrics

        # grad_delay keeps old params alive in the FIFO — donating them
        # would be use-after-free; donate only the optimizer state then.
        donate = (1,) if self.grad_delay else (0, 1)
        return jax.jit(step_fn, donate_argnums=donate)

    @property
    def step_program(self):
        """The jitted step program (built on first use)."""
        if self._step_fn is None:
            self._step_fn = self._build_step()
        return self._step_fn

    def step_inputs(self, state: TrainState, batch: Dict[str, Any]):
        """``(args, m, step_seconds)``: the step program's arguments for
        ``batch`` — this round's participation weights drawn, the batch
        split over the mesh's data axes when ``ctx`` has a mesh — and
        the round's participant count and simulated duration."""
        B = batch["tokens"].shape[0]
        with telemetry.span("repro.train.participation"):
            if self.straggler is not None:
                mask, m, dur = self.straggler.step()
                weights = participation_example_weights(
                    jnp.asarray(mask), self.n_workers, B)
            else:
                m, dur = self.n_workers, 0.0
                weights = None
        with telemetry.span("repro.train.put"):
            if self.ctx.mesh is not None:
                split = self.ctx.sharding(self.ctx.dp)
                batch = jax.device_put(dict(batch), split)
                if weights is not None:
                    weights = jax.device_put(weights, split)
            else:
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
            if self.grad_delay:
                self._param_fifo.append(state.params)
                grad_params = self._param_fifo[0]
                if len(self._param_fifo) > self.grad_delay:
                    self._param_fifo.popleft()
            else:
                grad_params = None
            args = (state.params, state.opt_state, batch, weights,
                    jnp.asarray(state.step, jnp.int32), grad_params)
        return args, m, dur

    def step(self, state: TrainState, batch: Dict[str, Any]):
        args, m, dur = self.step_inputs(state, batch)
        with telemetry.span("repro.train.dispatch"):
            params, opt_state, metrics = self.step_program(*args)
        return (TrainState(params, opt_state, state.step + 1),
                metrics, m, dur)

    # -------------------------------------------------------------- run
    def run(self, state: TrainState, batches: Iterator[Dict[str, Any]],
            num_steps: int, log_every: int = 10,
            history: Optional[TrainHistory] = None) -> TrainHistory:
        hist = history or TrainHistory()
        sim_t = hist.sim_seconds[-1] if hist.sim_seconds else 0.0
        wall0 = time.perf_counter()
        for i in range(num_steps):
            with telemetry.step_span("repro.train.step", state.step):
                with telemetry.span("repro.train.feed"):
                    batch = next(batches)
                state, metrics, m, dur = self.step(state, batch)
                sim_t += dur
                if self.straggler is not None:
                    # feed measured variance proxy into AUTO_M's estimator
                    with telemetry.sync("repro.train.sync"):
                        self.straggler.estimator.update_sigma2(
                            float(metrics["grad_sq"]))
                if state.step % log_every == 0 or i == num_steps - 1:
                    with telemetry.sync("repro.train.log"):
                        hist.steps.append(state.step)
                        hist.sim_seconds.append(sim_t)
                        hist.losses.append(float(metrics["loss"]))
                        hist.m_used.append(m)
                        hist.wall_seconds.append(time.perf_counter() - wall0)
        self.final_state = state
        return hist
