"""Plain float32 reference of K-Means as the paper runs it (Lloyd's
iterations: assign each point to its nearest centroid, then move each
centroid to the mean of its points; a centroid with no points stays).

The initial centroids are ``k`` distinct points drawn by
``jax.random.choice`` from the fit's seed.  Distances are the plain
``sum((p - c)**2)`` in f32, taken in blocks of rows so the (n, k) matrix
never exists whole; sums and counts accumulate in f32 per block and in
f64 across blocks.  Returns the centroids after the last iteration and
the cost (sum of squared distances) of the last assignment.

``bf16=True`` is the control: distances in the ``|p|^2 - 2 p.c + |c|^2``
form with the cross term's operands rounded to bfloat16 (one MXU pass,
the TPU's default precision).
"""
from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

BLOCK_ROWS = 1 << 20


@functools.lru_cache(maxsize=None)
def _block_fn(bf16: bool):
    import jax
    import jax.numpy as jnp

    def block(p, c, valid):
        if bf16:
            cross = jnp.dot(p.astype(jnp.bfloat16), c.astype(jnp.bfloat16).T,
                            preferred_element_type=jnp.float32)
            d2 = (jnp.sum(p * p, 1)[:, None] - 2.0 * cross
                  + jnp.sum(c * c, 1)[None, :])
        else:
            d2 = jnp.sum(jnp.square(p[:, None, :] - c[None, :, :]), axis=-1)
        a = jnp.argmin(d2, axis=1)
        mind = jnp.take_along_axis(d2, a[:, None], 1)[:, 0] * valid
        k = c.shape[0]
        w = valid[:, None]
        sums = jax.ops.segment_sum(p * w, a, num_segments=k)
        counts = jax.ops.segment_sum(valid, a, num_segments=k)
        return sums, counts, jnp.sum(mind)
    return jax.jit(block)


def fit(points, k: int, iters: int, init_seed: int, *,
        bf16: bool = False) -> Tuple[np.ndarray, float]:
    """(centroids (k, d) f64, cost) of ``iters`` Lloyd iterations."""
    import jax
    import jax.numpy as jnp
    n = points.shape[0]
    idx = jax.random.choice(jax.random.key(init_seed), n, (k,), replace=False)
    c = np.asarray(points[idx], np.float64)
    block = _block_fn(bf16)
    cost = 0.0
    for _ in range(iters):
        sums = np.zeros_like(c)
        counts = np.zeros(k)
        cost = 0.0
        cj = jnp.asarray(c, jnp.float32)
        for lo in range(0, n, BLOCK_ROWS):
            p = points[lo:lo + BLOCK_ROWS]
            valid = jnp.ones((p.shape[0],), jnp.float32)
            if p.shape[0] < BLOCK_ROWS:       # one shape for every block
                pad = BLOCK_ROWS - p.shape[0]
                p = jnp.pad(p, ((0, pad), (0, 0)))
                valid = jnp.pad(valid, (0, pad))
            s, cnt, co = block(p, cj, valid)
            sums += np.asarray(s, np.float64)
            counts += np.asarray(cnt, np.float64)
            cost += float(co)
        c = np.where(counts[:, None] > 0,
                     sums / np.maximum(counts[:, None], 1.0), c)
    return c, cost
