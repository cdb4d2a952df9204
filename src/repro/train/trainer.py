"""Training loop: step factory, metrics, fault-tolerance hooks.

``make_train_step`` returns a pure (params, opt_state, batch) -> (params,
opt_state, metrics) suitable for jit with shardings; the :class:`Trainer`
drives it with checkpointing (layout-aware, via repro.checkpoint), straggler
tracking and failure-recovery hooks.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.spans import span
from ..distributed import sharding as shd
from ..models.model import LM
from .optimizer import OptimizerConfig, adamw_init, adamw_update

__all__ = ["make_train_step", "make_train_step_reduce_once",
           "make_eval_step", "Trainer", "TrainState"]


def make_train_step(model: LM, opt_cfg: OptimizerConfig,
                    grad_accum: int = 1) -> Callable:
    """Returns (params, opt_state, batch) -> (params, opt_state, metrics).

    ``grad_accum`` > 1 scans over microbatches, accumulating f32 grads —
    the activation working set shrinks by the accumulation factor (the
    standard large-model memory lever; see EXPERIMENTS.md §Perf).
    """
    def grads_of(params, batch):
        def loss_fn(p):
            loss, metrics = model.loss(p, batch)
            return loss, metrics
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return loss, metrics, grads

    def train_step(params, opt_state, batch):
        if grad_accum == 1:
            loss, metrics, grads = grads_of(params, batch)
        else:
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape(grad_accum, x.shape[0] // grad_accum,
                                    *x.shape[1:]), batch)
            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def body(carry, mb):
                gsum, lsum = carry
                loss, metrics, grads = grads_of(params, mb)
                gsum = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), gsum, grads)
                return (gsum, lsum + loss), metrics

            (grads, lsum), metrics = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree_util.tree_map(lambda g: g / grad_accum, grads)
            loss = lsum / grad_accum
            metrics = jax.tree_util.tree_map(lambda m: jnp.mean(m), metrics)
        new_params, new_opt, opt_metrics = adamw_update(
            opt_cfg, grads, opt_state, params)
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return new_params, new_opt, metrics
    return train_step


def make_train_step_reduce_once(model: LM, opt_cfg: OptimizerConfig,
                                grad_accum: int, mesh,
                                rules=None) -> Callable:
    """Beyond-paper perf variant: the data-parallel axes run *manually*
    (shard_map) so microbatch gradients accumulate locally and cross-device
    reduction happens ONCE per step instead of once per microbatch — the
    model axis stays on GSPMD (auto).  Cuts gradient collective bytes by
    the accumulation factor (see EXPERIMENTS.md §Perf).
    """
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    auto = frozenset(mesh.axis_names) - set(dp_axes)
    rules = rules or shd.DEFAULT_RULES
    ndp = 1
    for a in dp_axes:
        ndp *= mesh.shape[a]

    def local_grads(params, batch):
        def loss_fn(p):
            return model.loss(p, batch)
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return loss, metrics, grads

    def body(params, opt_state, batch):
        # inside shard_map: dp axes are manual; constraints must not name
        # them, the model axis is still GSPMD-auto
        with shd.use_sharding(mesh, rules, manual=frozenset(dp_axes)):
            if grad_accum == 1:
                loss, metrics, grads = local_grads(params, batch)
            else:
                micro = jax.tree_util.tree_map(
                    lambda x: x.reshape(grad_accum,
                                        x.shape[0] // grad_accum,
                                        *x.shape[1:]), batch)
                g0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)

                def mb(carry, b):
                    gsum, lsum = carry
                    loss, metrics, grads = local_grads(params, b)
                    gsum = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(jnp.float32), gsum, grads)
                    return (gsum, lsum + loss), metrics

                (grads, lsum), metrics = jax.lax.scan(
                    mb, (g0, jnp.zeros((), jnp.float32)), micro)
                grads = jax.tree_util.tree_map(lambda g: g / grad_accum,
                                               grads)
                loss = lsum / grad_accum
                metrics = jax.tree_util.tree_map(jnp.mean, metrics)
            # THE one reduction per step
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g, dp_axes) / ndp, grads)
            loss = jax.lax.psum(loss, dp_axes) / ndp
            metrics = jax.tree_util.tree_map(
                lambda m: jax.lax.psum(m, dp_axes) / ndp, metrics)
            new_params, new_opt, opt_metrics = adamw_update(
                opt_cfg, grads, opt_state, params)
            return new_params, new_opt, dict(metrics, loss=loss,
                                             **opt_metrics)

    batch_spec = P(dp_axes if len(dp_axes) > 1 else dp_axes[0])
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), batch_spec),
        out_specs=(P(), P(), P()),
        check_vma=False,
        axis_names=set(dp_axes))


def make_eval_step(model: LM) -> Callable:
    def eval_step(params, batch):
        loss, metrics = model.loss(params, batch)
        return dict(metrics, loss=loss)
    return eval_step


@dataclasses.dataclass
class TrainState:
    step: int = 0
    step_times: list = dataclasses.field(default_factory=list)


class Trainer:
    """Single-controller training driver with fault-tolerance hooks.

    * checkpoints every ``ckpt_every`` steps through a layout-aware
      CheckpointManager (sync or async/staged);
    * records per-step wall times; ``straggler_report`` flags outliers
      (on real pods: per-host step contributions via collected metrics);
    * ``resume()`` restores the latest checkpoint (possibly onto a different
      mesh — elastic restart).
    """

    def __init__(self, model: LM, opt_cfg: OptimizerConfig,
                 data_iter, ckpt_manager=None, ckpt_every: int = 100,
                 straggler_factor: float = 2.0):
        self.model = model
        self.opt_cfg = opt_cfg
        self.data = data_iter
        self.ckpt = ckpt_manager
        self.ckpt_every = ckpt_every
        self.straggler_factor = straggler_factor
        self.state = TrainState()
        self._step_fn = jax.jit(make_train_step(model, opt_cfg),
                                donate_argnums=(0, 1))

    def init(self, rng):
        params = self.model.init(rng)
        return params, adamw_init(params)

    def resume(self, params_template=None):
        if self.ckpt is None:
            raise RuntimeError("no checkpoint manager configured")
        step, params = self.ckpt.restore_latest()
        self.state.step = step
        return params

    def run(self, params, opt_state, num_steps: int,
            log_every: int = 10, log_fn=print):
        history = []
        for _ in range(num_steps):
            with span("repro.train.batch"):
                batch = next(self.data)
            t0 = time.perf_counter()
            with span("repro.train.step"):
                params, opt_state, metrics = self._step_fn(params, opt_state,
                                                           batch)
                jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            self.state.step += 1
            self.state.step_times.append(dt)
            metrics = {k: float(v) for k, v in metrics.items()}
            metrics["step_seconds"] = dt
            history.append((self.state.step, metrics))
            if log_every and self.state.step % log_every == 0:
                log_fn(f"step {self.state.step}: "
                       f"loss={metrics['loss']:.4f} "
                       f"grad_norm={metrics['grad_norm']:.3f} "
                       f"({dt*1e3:.0f} ms)")
            if self.ckpt is not None and \
                    self.state.step % self.ckpt_every == 0:
                self.ckpt.save(self.state.step, params)
        return params, opt_state, history

    def straggler_report(self) -> dict:
        """Step-time outlier detection (the per-step analogue of node-level
        straggler mitigation: on a pod, the same EMA test runs per host on
        collected per-host timings and flags hosts for data reassignment)."""
        ts = np.asarray(self.state.step_times[1:])   # drop compile step
        if ts.size < 3:
            return {"stragglers": [], "median": None}
        med = float(np.median(ts))
        out = [int(i + 1) for i, t in enumerate(ts)
               if t > self.straggler_factor * med]
        return {"stragglers": out, "median": med,
                "worst": float(ts.max()), "mean": float(ts.mean())}
