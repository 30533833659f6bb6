"""Pallas TPU kernel: blocked K-Means assignment (distance + argmin).

TPU adaptation of the classic GPU distance kernel: instead of one thread
per point with shared-memory centroid staging, we tile (points x
centroids) into VMEM blocks and drive the MXU with the
``-2 * P @ C^T`` matmul form (d is the contraction dim); the running
(min-dist, argmin) pair lives in the revisited output block while the
centroid grid dimension iterates sequentially.

Grid: (n/bn, k/bk), k-minor. Block shapes:
  points   (bn, d)     — revisited across the k dimension (stays in VMEM)
  centroids(bk, d)
  out_min  (bn,)       — accumulator, initialized at j == 0
  out_idx  (bn,)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(p_ref, c_ref, idx_ref, min_ref, *, bk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        min_ref[...] = jnp.full_like(min_ref, jnp.inf)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    p = p_ref[...].astype(jnp.float32)                 # (bn, d)
    c = c_ref[...].astype(jnp.float32)                 # (bk, d)
    # ||p - c||^2 = ||p||^2 - 2 p.c + ||c||^2 ; ||p||^2 constant per row
    scores = -2.0 * jnp.dot(p, c.T, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    scores = scores + jnp.sum(c * c, axis=1)[None, :]  # (bn, bk)
    local_min = jnp.min(scores, axis=1)
    local_arg = jnp.argmin(scores, axis=1).astype(jnp.int32) + j * bk

    running = min_ref[...]
    better = local_min < running
    min_ref[...] = jnp.where(better, local_min, running)
    idx_ref[...] = jnp.where(better, local_arg, idx_ref[...])


def assign_pallas(points: jax.Array, centroids: jax.Array, *,
                  bn: int = 1024, bk: int = 512, interpret: bool):
    """points (n,d) f32, centroids (k,d) f32 -> (idx (n,) i32, partial min).

    Returned min excludes the ||p||^2 term (constant per point) — ops.py
    adds it back so callers see true squared distances.
    """
    n, d = points.shape
    k = centroids.shape[0]
    assert n % bn == 0 and k % bk == 0, (n, k, bn, bk)
    grid = (n // bn, k // bk)
    idx, mind = pl.pallas_call(
        functools.partial(_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn,), lambda i, j: (i,)),
            pl.BlockSpec((bn,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
        ],
        interpret=interpret,
    )(points, centroids)
    return idx, mind
