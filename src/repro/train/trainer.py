"""Trainer: the end-to-end HPC-stage driver.

Composes: sharding plan -> param init -> pjit'd train step -> data
pipeline (prefetching) -> async checkpointing -> fault recovery. Designed
to run as a gang-scheduled Compute-Unit on a Pilot (examples/train_e2e.py)
or standalone (launch/train.py).

Fault tolerance: ``run`` checkpoints every ``ckpt_every`` steps; on a
device loss the caller shrinks the pilot, rebuilds the trainer on the
surviving mesh and ``restore()``s — the per-leaf checkpoint layout
reshards onto the new topology automatically.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro import compat
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.data.pipeline import TokenPipeline
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.sharding import Plan
from repro.spans import span
from repro.train.step import make_train_state, make_train_step


class Trainer:
    def __init__(self, cfg: ModelConfig, mesh: Mesh, *,
                 global_batch: int = 8, seq: int = 128,
                 hyper: adamw.Hyper = adamw.Hyper(lr=1e-3),
                 n_microbatches: int = 1, ckpt_dir: Optional[str] = None,
                 ckpt_every: int = 50, seed: int = 0,
                 warmup_steps: int = 10, total_steps: int = 1000):
        compat.require_auto_axes(mesh, "Trainer")
        self.cfg = cfg
        self.mesh = mesh
        self.plan = Plan.for_mesh(mesh)
        self.global_batch = global_batch
        self.seq = seq
        self.seed = seed
        self.ckpt_every = ckpt_every
        self.ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None

        params_shapes = jax.eval_shape(
            lambda: transformer.init_params(cfg, jax.random.key(seed)))
        self.pspec = self.plan.param_specs(params_shapes)
        self.sspec = {"params": self.pspec,
                      "opt": {"m": self.pspec, "v": self.pspec}, "step": P()}
        self.state_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), self.sspec,
            is_leaf=lambda x: isinstance(x, P))

        from repro.optim import schedule as sched
        step_fn = make_train_step(cfg, hyper=hyper,
                                  n_microbatches=n_microbatches,
                                  act_spec=self.plan.act_spec(),
                                  moe_groups=self.plan.dp_size,
                                  lr_schedule=lambda s: sched.warmup_cosine(
                                      s, warmup=warmup_steps, total=total_steps))
        self._step = jax.jit(step_fn, donate_argnums=(0,))
        self.state: Any = None
        self.pipeline = TokenPipeline(cfg, batch=global_batch, seq=seq,
                                      seed=seed)
        self.history: List[Dict[str, float]] = []

    # -------------------------------------------------------------- state
    def init_state(self) -> None:
        with jax.set_mesh(self.mesh):
            init = jax.jit(
                lambda k: make_train_state(
                    self.cfg, transformer.init_params(self.cfg, k)),
                out_shardings=self.state_shardings)
            self.state = init(jax.random.key(self.seed))

    def restore(self) -> int:
        """Restore latest checkpoint onto the *current* mesh. Returns step."""
        assert self.ckpt is not None
        target = jax.eval_shape(
            lambda: make_train_state(
                self.cfg, transformer.init_params(self.cfg, jax.random.key(0))))
        self.state = self.ckpt.restore(target, shardings=self.state_shardings)
        return int(jax.device_get(self.state["step"]))

    # ---------------------------------------------------------------- run
    def run(self, n_steps: int, *, start_step: Optional[int] = None,
            log_every: int = 10, inject_failure_at: Optional[int] = None
            ) -> List[Dict[str, float]]:
        with span("trainer.run", steps=n_steps):
            with span("trainer.resume"):
                if self.state is None:
                    if (self.ckpt is not None
                            and self.ckpt.latest_step() is not None):
                        self.restore()
                    else:
                        self.init_state()
                step0 = (start_step if start_step is not None
                         else int(jax.device_get(self.state["step"])))
                self.pipeline.start(from_step=step0)
            finished = False
            try:
                with jax.set_mesh(self.mesh):
                    self._steps(step0, n_steps, log_every, inject_failure_at)
                finished = True
            finally:
                with span("trainer.stop"):
                    self.pipeline.stop()
                    if self.ckpt is not None:
                        # publish in-flight saves even on failure
                        self.ckpt.wait()
                        if finished:
                            self.ckpt.save(self.state, n_steps, blocking=True)
        return self.history

    def _steps(self, step0: int, n_steps: int, log_every: int,
               inject_failure_at: Optional[int]) -> None:
        batches = iter(self.pipeline)
        for i in range(step0, n_steps):
            with span("trainer.batch", step=i):
                batch = next(batches, None)
            if batch is None:
                return
            if inject_failure_at is not None and i == inject_failure_at:
                raise RuntimeError("injected node failure")
            t0 = time.monotonic()
            with span("trainer.dispatch", step=i):
                self.state, metrics = self._step(self.state, batch)
            with span("trainer.sync", step=i):
                metrics = {k: float(jax.device_get(v))
                           for k, v in metrics.items()}
            metrics["step"] = i
            metrics["step_s"] = time.monotonic() - t0
            self.history.append(metrics)
            if log_every and (i % log_every == 0 or i == n_steps - 1):
                print(f"step {i:5d} loss {metrics['loss']:.4f} "
                      f"gnorm {metrics['grad_norm']:.3f} "
                      f"({metrics['step_s']*1e3:.0f} ms)")
            if (self.ckpt is not None and self.ckpt_every
                    and (i + 1) % self.ckpt_every == 0):
                self.ckpt.save(self.state, i + 1)
