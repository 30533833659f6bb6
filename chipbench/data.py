"""Inputs the benchmark makes from ``--seed``: keys, points, token batches.

The points are a copy of the program's ``analytics.kmeans.make_dataset``
(a seeded mixture of Gaussians in [-5, 5]^d, sigma 0.3) and the token
streams a copy of ``data.pipeline.TokenPipeline``'s ``sequence``
distribution, kept here so that the yardstick does not move with the
program.  The program receives only what these functions make.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np


def key(seed: int, *salt: int):
    """A JAX key from a seed of any size and optional salt words."""
    import jax
    k = jax.random.key(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0x7FFFFFFF)
    for s in salt:
        k = jax.random.fold_in(k, s & 0x7FFFFFFF)
    return k


def _mixture(k, n: int, d: int, n_clusters: int):
    import jax
    k1, k2, k3 = jax.random.split(k, 3)
    centers = jax.random.uniform(k1, (n_clusters, d), minval=-5.0, maxval=5.0)
    which = jax.random.randint(k2, (n,), 0, n_clusters)
    noise = jax.random.normal(k3, (n, d)) * 0.3
    return centers[which] + noise


@functools.lru_cache(maxsize=None)
def _mixture_fn(n: int, d: int, n_clusters: int, sharding):
    import jax
    return jax.jit(functools.partial(_mixture, n=n, d=d, n_clusters=n_clusters),
                   out_shardings=sharding)


def mixture(k, n: int, d: int, n_clusters: int, sharding=None):
    """(n, d) f32 points on the device, in one jitted call."""
    return _mixture_fn(n, d, n_clusters, sharding)(k)


def tokens_at(seed: int, step: int, batch: int, seq: int,
              vocab: int) -> Dict[str, np.ndarray]:
    """One training batch, a pure function of (seed, step): rows are
    arithmetic token streams (start, stride) with distinct starts, so
    every row differs and the loss can fall below ln(vocab)."""
    rng = np.random.default_rng((seed, step))
    start = rng.choice(vocab, size=(batch, 1), replace=False)
    stride = rng.integers(1, 4, (batch, 1))
    t = np.arange(seq + 1)[None, :]
    toks = ((start + stride * t) % vocab).astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:],
            "mask": np.ones((batch, seq), np.float32)}


class TokenFeed:
    """The Trainer's feed (its ``pipeline``): ``tokens_at`` batches, put
    on the device as they are drawn.  Steps continue where ``start``
    says, so set-up and window draw one stream."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int,
                 sharding=None):
        self.seed, self.batch, self.seq, self.vocab = seed, batch, seq, vocab
        self.sharding = sharding
        self.step = 0

    def batch_at(self, step: int) -> Dict[str, Any]:
        import jax
        b = tokens_at(self.seed, step, self.batch, self.seq, self.vocab)
        return {k: jax.device_put(v, self.sharding) for k, v in b.items()}

    def start(self, from_step: int = 0) -> "TokenFeed":
        self.step = from_step
        return self

    def stop(self) -> None:
        pass

    def __iter__(self) -> "TokenFeed":
        return self

    def __next__(self) -> Dict[str, Any]:
        b = self.batch_at(self.step)
        self.step += 1
        return b
