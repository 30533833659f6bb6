"""Pure-jnp oracle for the K-Means assignment kernel."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def assign(points: jax.Array, centroids: jax.Array
           ) -> Tuple[jax.Array, jax.Array]:
    """points: (n, d), centroids: (k, d) ->
    (nearest centroid id (n,) int32, squared distance to it (n,) f32)."""
    p = points.astype(jnp.float32)
    c = centroids.astype(jnp.float32)
    d2 = (jnp.sum(p * p, axis=1, keepdims=True)
          - 2.0 * jnp.dot(p, c.T, precision=jax.lax.Precision.HIGHEST)
          + jnp.sum(c * c, axis=1)[None, :])          # (n, k)
    idx = jnp.argmin(d2, axis=1).astype(jnp.int32)
    return idx, jnp.take_along_axis(d2, idx[:, None], axis=1)[:, 0]
