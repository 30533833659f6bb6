"""Public wrapper for the K-Means assignment kernel (autotuned blocks)."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import autotune, pallas_on_platform
from . import kmeans as kernel

_BLOCK_POINTS = kernel.CHUNK_ROWS * kernel.LANES   # bn is a multiple of this


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("bn", "bk"))
def _assign(points, centroids, bn: int, bk: int):
    n, d = points.shape
    k = centroids.shape[0]
    # whole rows of 128 points, and at least one (8, 128) tile: XLA tiles a
    # shorter 1-D output more finely than the kernel's blocks
    n128 = max(_round_up(n, kernel.LANES), kernel.TILE_POINTS)
    kp = _round_up(k, bk)
    # the one relayout: coordinate planes of (rows, 128) points
    planes = jnp.pad(points.astype(jnp.float32).T, ((0, 0), (0, n128 - n)))
    planes = planes.reshape(d, n128 // kernel.LANES, kernel.LANES)
    # padded centroids never win: their distance is inf, and ties keep the
    # lower index
    c = jnp.pad(centroids.astype(jnp.float32), ((0, kp - k), (0, 0)),
                constant_values=jnp.inf)
    c = c.reshape(kp // bk, bk, d).transpose(0, 2, 1).reshape(kp // bk, -1)
    c = jnp.pad(c, ((0, 0), (0, kernel.centroid_stride(d, bk) - d * bk)))
    idx, mind = pallas_on_platform(kernel.assign_pallas, planes, c.reshape(-1),
                                   br=bn // kernel.LANES, bk=bk)
    return (idx, mind) if n128 == n else (idx[:n], mind[:n])


def resolve_blocks(n: int, k: int, d: int, dtype,
                   bn: Optional[int], bk: Optional[int]):
    """Block sizes for assignment: explicit args win, else the autotune
    registry, else ``autotune.DEFAULTS``. ``bn`` (points a block) is
    rounded to a multiple of the kernel's chunk and capped to the padded n;
    ``bk`` (centroids a block) is capped to k and to ``kernel.MAX_BK``."""
    if bn is None or bk is None:
        tuned = autotune.lookup("kmeans", {"n": n, "k": k, "d": d}, dtype) \
            or autotune.DEFAULTS["kmeans"]
        bn = bn if bn is not None else tuned["bn"]
        bk = bk if bk is not None else tuned["bk"]
    bn = min(_round_up(bn, _BLOCK_POINTS), _round_up(n, _BLOCK_POINTS))
    return bn, min(bk, k, kernel.MAX_BK)


def assign(points: jax.Array, centroids: jax.Array, *,
           bn: Optional[int] = None,
           bk: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """Nearest centroid of each point and the squared distance to it:
    ``(idx (n,) int32, dist (n,) f32)``, as ``ref.assign`` gives them."""
    n, d = points.shape
    k = centroids.shape[0]
    bn, bk = resolve_blocks(n, k, d, points.dtype, bn, bk)
    return _assign(points, centroids, bn, bk)
