"""K-Means on the analytics engine — the paper's evaluation workload (Fig 6).

Each iteration is one MapReduce round, exactly as the paper's Hadoop
implementation: map = assign points to nearest centroid + emit partial
(sum, count) per cluster; shuffle/reduce = aggregate partials; driver =
recompute centroids. The distance/assignment hot-spot runs through the
Pallas kernel (kernels/kmeans) when enabled, else the jnp reference.

The paper's three scenarios (points x clusters, constant product):
10,000 x 5,000 / 100,000 x 500 / 1,000,000 x 50, d=3, 2 iterations.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.spans import span

from .draw import choice_distinct
from .engine import AnalyticsEngine

PAPER_SCENARIOS = {
    "10k_points_5k_clusters": (10_000, 5_000),
    "100k_points_500_clusters": (100_000, 500),
    "1m_points_50_clusters": (1_000_000, 50),
}
PAPER_DIM = 3
PAPER_ITERS = 2


def assign_partials(points: jax.Array, centroids: jax.Array, *,
                    use_kernel: bool = False) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Map phase: per-block partial (sums, counts, sq-dist cost)."""
    if use_kernel:
        from repro.kernels.kmeans import ops
        assign, mind = ops.assign(points, centroids)
    else:
        from repro.kernels.kmeans import ref
        assign, mind = ref.assign(points, centroids)
    k = centroids.shape[0]
    onehot = jax.nn.one_hot(assign, k, dtype=points.dtype)        # (n, k)
    # f32 sums: the TPU's default matmul precision would round the points
    # to bf16 before they move the centroids
    sums = jnp.einsum("nk,nd->kd", onehot, points,
                      precision=jax.lax.Precision.HIGHEST)
    counts = onehot.sum(axis=0)
    return sums, counts, jnp.sum(mind)


def kmeans_fit(engine: AnalyticsEngine, name: str, k: int, *,
               iters: int = PAPER_ITERS, data_path: str = "local",
               use_kernel: bool = False, seed: int = 0,
               ) -> Tuple[jax.Array, float]:
    """Run K-Means over a registered dataset. Returns (centroids, cost).

    data_path='local'  — compute on resident shards (RP-YARN / local disk)
    data_path='global' — force a full redistribution first, each iteration
                         (RP / Lustre): same math, measured data movement.
    """
    with span("kmeans.init"):
        pts = engine.get(name)
        n, d = pts.shape
        idx = choice_distinct(jax.random.key(seed), n, k)
        centroids = pts[idx]

    cost = jnp.inf
    map_fn = functools.partial(assign_partials, use_kernel=use_kernel)
    for _ in range(iters):
        if data_path == "global":
            engine.global_reshard(name)
        sums, counts, cost = engine.map_reduce(
            map_fn, name, extra_args=(centroids,),
            cache_key=("kmeans_assign", use_kernel))
        with span("kmeans.update"):
            centroids = jnp.where(counts[:, None] > 0,
                                  sums / jnp.maximum(counts[:, None], 1.0),
                                  centroids)
    with span("kmeans.cost"):
        cost = float(cost)
    return centroids, cost


def make_dataset(n: int, d: int = PAPER_DIM, *, n_clusters: int = 8,
                 seed: int = 0) -> jnp.ndarray:
    """Synthetic mixture-of-Gaussians points (paper uses synthetic data)."""
    key = jax.random.key(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    centers = jax.random.uniform(k1, (n_clusters, d), minval=-5.0, maxval=5.0)
    which = jax.random.randint(k2, (n,), 0, n_clusters)
    noise = jax.random.normal(k3, (n, d)) * 0.3
    return centers[which] + noise
