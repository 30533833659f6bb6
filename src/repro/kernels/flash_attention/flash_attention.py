"""Pallas TPU kernel: flash attention (forward) with block skipping.

TPU adaptation of FlashAttention: the CUDA version stages K/V tiles in
shared memory with warp-level softmax reductions; here each (batch*head,
q-block) grid cell iterates KV blocks as the minor grid dimension with
the running (m, l, acc) state in VMEM scratch, and the QK^T / PV matmuls
on the MXU. Causal / sliding-window masks skip fully-masked KV blocks via
``pl.when`` predication — on TPU the skipped block's DMA + MXU work is
elided (this is what removes the 2x causal slack the jnp fallback pays;
see EXPERIMENTS.md §Perf).

Grid: (B*H, S_q/bq, S_k/bk), kv-minor. Blocks:
  q   (bq, hd)   revisited across kv blocks
  k,v (bk, hd)
  o   (bq, hd)   written on the last kv block
Scratch: m, l (bq,), acc (bq, hd) — f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            bq: int, bk: int, scale: float, causal: bool, window: int,
            n_kv: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q_start = qi * bq
    k_start = kj * bk

    # block-level skip: fully-masked KV blocks do no work at all
    live = True
    if causal:
        live = k_start <= q_start + bq - 1
    if window:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live if not isinstance(live, bool) else live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = jnp.ones((bq, bk), bool)
        if causal:
            ok &= qp >= kp
        if window:
            ok &= (qp - kp) < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + p.sum(axis=1)
        acc_s[...] = acc_s[...] * corr[:, None] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(kj == n_kv - 1)
    def _finish():
        o_ref[...] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)[:, None]
                      ).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           bq: int = 512, bk: int = 512,
                           interpret: bool) -> jax.Array:
    """q,k,v: (BH, S, hd) flattened batch*heads -> (BH, S, hd)."""
    BH, S_q, hd = q.shape
    S_k = k.shape[1]
    assert S_q % bq == 0 and S_k % bk == 0, (S_q, S_k, bq, bk)
    n_kv = S_k // bk
    grid = (BH, S_q // bq, n_kv)
    kern = functools.partial(_kernel, bq=bq, bk=bk, scale=hd ** -0.5,
                             causal=causal, window=window, n_kv=n_kv)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max m
            pltpu.VMEM((bq,), jnp.float32),      # running denom l
            pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
