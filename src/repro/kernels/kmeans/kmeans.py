"""Pallas TPU kernel: K-Means assignment (nearest centroid and its distance).

The points come as ``d`` coordinate planes of ``(rows, 128)``, point ``i``
at ``[:, i // 128, i % 128]``, so each coordinate of 1,024 points fills one
(8, 128) vreg. At the paper's d = 3 a matmul would use 3 of the MXU's 128
contraction rows, so each distance is taken on the VPU as the reference
does, ``sum_d (p_d - c_jd)^2`` in f32, against centroid coordinates read as
scalars from SMEM. Within a block of points the kernel walks chunks of
``CHUNK_ROWS`` rows; per chunk the running (min, argmin) pair stays in
vregs while the block's centroids, unrolled whole, stream past it, and a
strict ``<`` keeps the first minimal index, as ``jnp.argmin`` does. More
than ``MAX_BK`` centroids take more blocks along the grid's k axis.

Grid: (cdiv(rows, br), k / bk), k-minor. Blocks:
  planes    (d, br, 128)  VMEM, revisited across the k dimension
  centroids (cstride,)    SMEM, one block of ``bk`` centroids coordinate-
                          major, padded to whole 1,024-word tiles
  idx, mind (br * 128,)   1-D; carry the running pair across k blocks
The last block of points may be ragged: its extra outputs are never read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
CHUNK_ROWS = 32     # rows of points whose running pair stays in vregs
MAX_BK = 128        # a block's centroids are unrolled whole: cap the code
SMEM_TILE = 1024    # words: the tile of a 1-D operand
TILE_POINTS = 8 * LANES
# points and centroids a block of the lane-dense assignment kernel
BLOCK_POINTS = 65536
BLOCK_CENTROIDS = 64


def _kernel(c_ref, p_ref, idx_ref, min_ref, *, d: int, bk: int,
            carried: bool):
    j = pl.program_id(1)
    br = p_ref.shape[1]

    def chunk(r, carry):
        r0 = pl.multiple_of(r * CHUNK_ROWS, CHUNK_ROWS)
        rows = pl.ds(r0, CHUNK_ROWS)
        flat = pl.ds(pl.multiple_of(r0 * LANES, CHUNK_ROWS * LANES),
                     CHUNK_ROWS * LANES)
        shape = (CHUNK_ROWS, LANES)
        p = [p_ref[a, rows, :] for a in range(d)]
        best = jnp.full(shape, jnp.inf, jnp.float32)
        arg = jnp.zeros(shape, jnp.int32)
        if carried:     # the pair so far, from the earlier centroid blocks
            best = jnp.where(j == 0, best, min_ref[flat].reshape(shape))
            arg = jnp.where(j == 0, arg, idx_ref[flat].reshape(shape))
        for c in range(bk):
            dist = jnp.square(p[0] - c_ref[c])
            for a in range(1, d):
                dist = dist + jnp.square(p[a] - c_ref[a * bk + c])
            better = dist < best
            best = jnp.where(better, dist, best)
            arg = jnp.where(better, j * bk + c, arg)
        min_ref[flat] = best.reshape(-1)
        idx_ref[flat] = arg.reshape(-1)
        return carry

    jax.lax.fori_loop(0, br // CHUNK_ROWS, chunk, 0)


def centroid_stride(d: int, bk: int) -> int:
    """Words of SMEM one block of centroids takes: a 1-D operand is tiled
    in 1,024 words, and a block must be whole tiles."""
    return -(-d * bk // SMEM_TILE) * SMEM_TILE


def assign_pallas(planes: jax.Array, centroids: jax.Array, *,
                  br: int, bk: int, interpret: bool):
    """planes (d, rows, 128) f32, centroids (k/bk * cstride,) f32, each block
    of ``bk`` laid out ``[coord][centroid]`` -> (idx (rows*128,) i32,
    squared distance (rows*128,) f32)."""
    d, rows, _ = planes.shape
    cstride = centroid_stride(d, bk)
    assert br % CHUNK_ROWS == 0 and centroids.shape[0] % cstride == 0
    assert bk <= MAX_BK, bk
    n, kb = rows * LANES, centroids.shape[0] // cstride
    return pl.pallas_call(
        functools.partial(_kernel, d=d, bk=bk, carried=kb > 1),
        grid=(pl.cdiv(rows, br), kb),
        in_specs=[
            pl.BlockSpec((cstride,), lambda i, j: (j,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((d, br, LANES), lambda i, j: (0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br * LANES,), lambda i, j: (i,)),
            pl.BlockSpec((br * LANES,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(centroids, planes)
