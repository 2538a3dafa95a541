"""Fault-tolerant, elastic trainer.

Production loop (DESIGN.md §6):
  * jit train_step with explicit param/opt/batch shardings;
  * self-scheduled shard ingestion (repro.data) feeds fixed-shape batches;
  * async sharded checkpoints every ``ckpt_every`` steps, auto-resume;
  * elastic re-mesh: on (simulated or real) device loss, commit a sync
    checkpoint, rebuild the mesh from the survivors, re-shard via
    device_put, and continue — the training-loop analogue of the paper's
    manager re-queueing a dead worker's tasks;
  * straggler watchdog: per-step wall time EWMA; steps slower than
    ``straggler_factor`` x EWMA are counted and reported (on real fleets
    this feeds the next elastic epoch's exclusion list).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Any, Iterator, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distribution.sharding import (
    batch_shardings, opt_state_shardings, param_shardings)
from repro.launch import steps as step_lib
from repro.models import model as M
from repro.train import checkpoint as ckpt_lib
from repro.train.optimizer import OptimizerConfig, init_opt_state
from repro.train.schedules import get_schedule


@dataclasses.dataclass
class TrainerConfig:
    workdir: str
    total_steps: int = 100
    ckpt_every: int = 20
    keep_ckpts: int = 3
    log_every: int = 10
    schedule: str = "cosine"
    peak_lr: float = 3e-4
    warmup_steps: int = 10
    straggler_factor: float = 3.0
    remat: bool = True


class Trainer:
    def __init__(self, cfg: ArchConfig, opt_cfg: OptimizerConfig,
                 tcfg: TrainerConfig, mesh: Optional[Mesh] = None,
                 seed: int = 0):
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.tcfg = tcfg
        self.mesh = mesh or Mesh(np.array(jax.devices()[:1]), ("data",))
        self.seed = seed
        self.step = 0
        self.metrics_log: list[dict] = []
        self.straggler_steps = 0
        self._ewma: Optional[float] = None
        os.makedirs(tcfg.workdir, exist_ok=True)
        self.ckpt_dir = os.path.join(tcfg.workdir, "ckpt")
        os.makedirs(self.ckpt_dir, exist_ok=True)
        self.async_ckpt = ckpt_lib.AsyncCheckpointer(
            self.ckpt_dir, keep=tcfg.keep_ckpts)
        self.schedule = get_schedule(
            tcfg.schedule, peak=tcfg.peak_lr,
            warmup_steps=tcfg.warmup_steps, total_steps=tcfg.total_steps)
        self._build(restore=True)

    # -- construction / restore -------------------------------------------

    def _build(self, restore: bool) -> None:
        cfg, mesh = self.cfg, self.mesh
        self.psh = param_shardings(step_lib.param_specs(cfg), mesh)
        ospecs = jax.eval_shape(functools.partial(
            init_opt_state, cfg=self.opt_cfg), step_lib.param_specs(cfg))
        self.osh = opt_state_shardings(
            ospecs, step_lib.param_specs(cfg), self.psh, mesh)

        restored = None
        if restore:
            template = {"params": step_lib.param_specs(cfg),
                        "opt": ospecs}
            restored, step = ckpt_lib.restore_latest(
                self.ckpt_dir, template,
                {"params": self.psh, "opt": self.osh})
            if restored is not None:
                self.step = step + 1
        if restored is not None:
            self.params = restored["params"]
            self.opt_state = restored["opt"]
        else:
            with jax.set_mesh(mesh):
                self.params = jax.jit(
                    functools.partial(M.init_params, cfg),
                    out_shardings=self.psh)(jax.random.key(self.seed))
                self.opt_state = jax.jit(
                    functools.partial(init_opt_state, cfg=self.opt_cfg),
                    out_shardings=self.osh)(self.params)

        def train_step(params, opt_state, batch, step):
            lr = self.schedule(step)
            loss, grads = jax.value_and_grad(
                lambda p: M.loss_fn(cfg, p, batch,
                                    remat=self.tcfg.remat))(params)
            from repro.train.optimizer import apply_updates
            params, opt_state, metrics = apply_updates(
                params, grads, opt_state, self.opt_cfg, lr=lr)
            metrics.update(loss=loss, lr=lr)
            return params, opt_state, metrics

        self._jit_step = jax.jit(
            train_step,
            in_shardings=(self.psh, self.osh, None, None),
            out_shardings=(self.psh, self.osh, None),
            donate_argnums=(0, 1))

    # -- elastic re-mesh -----------------------------------------------------

    def remesh(self, new_mesh: Mesh) -> None:
        """Survivor re-mesh: sync-commit, rebuild, re-shard, continue."""
        self.async_ckpt.wait()
        ckpt_lib.save(self.ckpt_dir, self.step,
                      {"params": self.params, "opt": self.opt_state},
                      keep=self.tcfg.keep_ckpts)
        self.step += 1           # restored checkpoint resumes after itself
        self.mesh = new_mesh
        self._build(restore=True)

    # -- loop ------------------------------------------------------------------

    def run(self, batches: Iterator[dict[str, np.ndarray]],
            n_steps: Optional[int] = None) -> list[dict]:
        n_steps = n_steps or self.tcfg.total_steps
        bsh = None
        target = self.step + n_steps
        with jax.set_mesh(self.mesh):
            for batch in batches:
                if self.step >= target:
                    break
                if bsh is None:
                    bsh = batch_shardings(self.mesh, jax.tree_util.tree_map(
                        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        batch))
                dev_batch = jax.device_put(batch, bsh)
                t0 = time.monotonic()
                self.params, self.opt_state, metrics = self._jit_step(
                    self.params, self.opt_state, dev_batch, self.step)
                loss = float(metrics["loss"])
                dt = time.monotonic() - t0
                # straggler watchdog
                if self._ewma is not None and \
                        dt > self.tcfg.straggler_factor * self._ewma:
                    self.straggler_steps += 1
                self._ewma = dt if self._ewma is None else \
                    0.9 * self._ewma + 0.1 * dt
                rec = {"step": self.step, "loss": loss,
                       "grad_norm": float(metrics["grad_norm"]),
                       "lr": float(metrics["lr"]), "sec": dt}
                self.metrics_log.append(rec)
                if self.step % self.tcfg.log_every == 0:
                    print(f"step {self.step:5d} loss {loss:.4f} "
                          f"gnorm {rec['grad_norm']:.3f} {dt*1e3:.0f}ms",
                          flush=True)
                if self.tcfg.ckpt_every and \
                        self.step % self.tcfg.ckpt_every == 0 and \
                        self.step > 0:
                    self.async_ckpt.save_async(
                        self.step,
                        {"params": self.params, "opt": self.opt_state})
                self.step += 1
        return self.metrics_log

    def close(self) -> None:
        self.async_ckpt.close()
