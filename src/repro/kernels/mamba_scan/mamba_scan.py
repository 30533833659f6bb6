"""Pallas TPU kernel: chunked Mamba selective scan.

TPU adaptation of the CUDA selective-scan kernel: the GPU version
parallelizes over (batch, d_inner) threads with a sequential time loop in
registers; on TPU we tile d_inner (VPU lanes) and walk the sequence in
chunks as the minor grid dimension, carrying h in VMEM scratch. Inside a
chunk the recurrence runs as an unrolled VPU loop over time — wide in
(di_block, st), sequential in t — matching the VREG-friendly layout.

Grid: (B, di/bdi, S/bs), seq-minor. Blocks:
  a, b (bs, bdi, st)   [per batch]
  C    (bs, st)
  y    (bs, bdi)       output
Scratch: h (bdi, st) f32 carried across seq blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref, h_s, *,
            bs: int, n_seq: int):
    sj = pl.program_id(2)

    @pl.when(sj == 0)
    def _init():
        h_s[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)   # (bs, bdi, st)
    b = b_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)   # (bs, st)

    h = h_s[...]
    ys = []
    for t in range(bs):                  # sequential in time, wide in (di, st)
        h = a[t] * h + b[t]
        ys.append(jnp.sum(h * c[t][None, :], axis=1))  # (bdi,)
    y_ref[...] = jnp.stack(ys).astype(y_ref.dtype)
    h_s[...] = h

    @pl.when(sj == n_seq - 1)
    def _finish():
        hout_ref[...] = h_s[...]


def mamba_scan_pallas(a: jax.Array, b: jax.Array, C: jax.Array,
                      h0: jax.Array, *, bdi: int = 256, bs: int = 16,
                      interpret: bool):
    """a,b: (B,S,di,st); C: (B,S,st); h0: (B,di,st) -> (y (B,S,di), h_last)."""
    B, S, di, st = a.shape
    assert S % bs == 0 and di % bdi == 0, (S, di, bs, bdi)
    n_seq = S // bs
    grid = (B, di // bdi, n_seq)
    kern = functools.partial(_kernel, bs=bs, n_seq=n_seq)
    y, h_last = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bs, bdi, st), lambda bi, di_, sj: (bi, sj, di_, 0)),
            pl.BlockSpec((None, bs, bdi, st), lambda bi, di_, sj: (bi, sj, di_, 0)),
            pl.BlockSpec((None, bs, st), lambda bi, di_, sj: (bi, sj, 0)),
            pl.BlockSpec((None, bdi, st), lambda bi, di_, sj: (bi, di_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bs, bdi), lambda bi, di_, sj: (bi, sj, di_)),
            pl.BlockSpec((None, bdi, st), lambda bi, di_, sj: (bi, di_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, di), jnp.float32),
            jax.ShapeDtypeStruct((B, di, st), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bdi, st), jnp.float32)],
        interpret=interpret,
    )(a, b, C, h0)
    return y, h_last
