"""Distributed train step: grad-accumulation microbatching + AdamW.

The step is a pure function (state, batch) -> (state, metrics) designed
for ``jax.jit`` with planner-derived in/out shardings and donated state.
Microbatching is a ``lax.scan`` over batch slices with f32 gradient
accumulation, which bounds stored activations to one microbatch (plus the
per-layer remat checkpoints) — required to fit the larger assigned
architectures into 16 GB/chip HBM.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import transformer
from repro.models.config import ModelConfig
from repro.optim import adamw, schedule
from repro.roofline.terms import V5E

TrainState = Dict[str, Any]


def make_train_state(cfg: ModelConfig, params: Any,
                     moment_dtype=jnp.float32) -> TrainState:
    return {"params": params, "opt": adamw.init(params, moment_dtype),
            "step": jnp.zeros((), jnp.int32)}


def microbatch_count(cfg: ModelConfig, global_batch: int, seq: int,
                     n_devices: int, hbm_bytes: float = V5E.hbm_bytes) -> int:
    """Pick a grad-accumulation factor so stored activations fit HBM.

    Per-layer remat stores one (mb, S, D) residual per layer; target that
    plus the optimizer footprint at ~60% of HBM.
    """
    layers = cfg.n_layers + cfg.n_encoder_layers
    bytes_per_mb = layers * seq * cfg.d_model * 2  # bf16 residuals, per sample
    # batch is sharded over the dp axes; assume dp covers all of n_devices/tp
    dp = max(1, n_devices // 16)
    local_batch = max(1, global_batch // dp)
    budget = 0.4 * hbm_bytes
    mb = 1
    while local_batch // mb > 1 and (local_batch // mb) * bytes_per_mb > budget:
        mb *= 2
    return min(mb, local_batch)


def make_train_step(cfg: ModelConfig, *, hyper: adamw.Hyper = adamw.Hyper(),
                    n_microbatches: int = 1, remat: bool = True,
                    act_spec=None, lr_schedule=None,
                    aux_coef: Optional[float] = None, moe_groups: int = 1,
                    moe_ep_axis=None, accum_dtype=jnp.float32,
                    remat_policy=None, save_spec=None):
    """Build the (state, batch) -> (state, metrics) step function.

    ``aux_coef`` defaults to the config's ``moe_aux_coef``.  The metrics of
    a config with experts also carry its MoE counters (``moe_pairs``,
    ``moe_dropped``), summed over layers and microbatches."""
    lr_schedule = lr_schedule or (lambda s: schedule.warmup_cosine(s))

    def loss_of(params, mb):
        return transformer.loss_and_stats(cfg, params, mb, aux_coef=aux_coef,
                                          remat=remat, act_spec=act_spec,
                                          moe_groups=moe_groups,
                                          moe_ep_axis=moe_ep_axis,
                                          remat_policy=remat_policy,
                                          save_spec=save_spec)

    def grads_of(params, batch):
        if n_microbatches == 1:
            (l, stats), g = jax.value_and_grad(loss_of, has_aux=True)(
                params, batch)
            return l, stats, g

        def split(x):
            b = x.shape[0]
            assert b % n_microbatches == 0, (b, n_microbatches)
            return x.reshape(n_microbatches, b // n_microbatches, *x.shape[1:])

        mbs = jax.tree.map(split, batch)
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)

        def acc(carry, mb):
            tot_l, tot_g = carry
            (l, stats), g = jax.value_and_grad(loss_of, has_aux=True)(
                params, mb)
            tot_g = jax.tree.map(lambda a, b: a + b.astype(accum_dtype), tot_g, g)
            return (tot_l + l, tot_g), stats

        (l, g), stats = jax.lax.scan(acc, (jnp.zeros(()), g0), mbs)
        inv = 1.0 / n_microbatches
        stats = {k: v.sum() for k, v in stats.items()}
        return l * inv, stats, jax.tree.map(lambda x: x * inv, g)

    def train_step(state: TrainState, batch: Dict[str, jax.Array],
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        loss, stats, grads = grads_of(state["params"], batch)
        lr_scale = lr_schedule(state["step"])
        new_p, new_opt, om = adamw.update(state["params"], grads, state["opt"],
                                          state["step"], hyper, lr_scale)
        new_state = {"params": new_p, "opt": new_opt, "step": state["step"] + 1}
        metrics = {"loss": loss, "lr_scale": jnp.asarray(lr_scale), **om,
                   **stats}
        return new_state, metrics

    return train_step
