"""Public wrapper for the K-Means assignment kernel (autotuned blocks)."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import autotune, pallas_on_platform
from . import kmeans as kernel

_PAD_VALUE = 1e8  # padded centroids land far away from every point


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("bn", "bk"))
def _assign(points, centroids, bn: int, bk: int):
    n, d = points.shape
    k = centroids.shape[0]
    np_, kp = _round_up(n, bn), _round_up(k, bk)
    p = jnp.pad(points.astype(jnp.float32), ((0, np_ - n), (0, 0)))
    c = jnp.pad(centroids.astype(jnp.float32), ((0, kp - k), (0, 0)),
                constant_values=_PAD_VALUE)
    idx, partial_min = pallas_on_platform(kernel.assign_pallas, p, c,
                                          bn=bn, bk=bk)
    mind = partial_min + jnp.sum(points.astype(jnp.float32) ** 2, axis=1) \
        if np_ == n else (partial_min[:n]
                          + jnp.sum(points.astype(jnp.float32) ** 2, axis=1))
    return idx[:n], mind


def resolve_blocks(n: int, k: int, d: int, dtype,
                   bn: Optional[int], bk: Optional[int]):
    """Block sizes for assignment: explicit args win, else the autotune
    registry, else the legacy 1024/512 (capped to the padded extents)."""
    if bn is None or bk is None:
        tuned = autotune.lookup("kmeans", {"n": n, "k": k, "d": d}, dtype) \
            or autotune.DEFAULTS["kmeans"]
        bn = bn if bn is not None else tuned["bn"]
        bk = bk if bk is not None else tuned["bk"]
    return min(bn, _round_up(n, 8)), min(bk, _round_up(k, 8))


def assign(points: jax.Array, centroids: jax.Array, *,
           bn: Optional[int] = None,
           bk: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """Nearest-centroid assignment via the Pallas kernel (padded + jit)."""
    n, d = points.shape
    k = centroids.shape[0]
    bn, bk = resolve_blocks(n, k, d, points.dtype, bn, bk)
    return _assign(points, centroids, bn, bk)
