"""K distinct indices of ``range(n)``, drawn as ``jax.random.choice(key, n,
(k,), replace=False)`` draws them, without permuting all ``n``.

jax takes that sample as ``permutation(key, n)[:k]``. Its ``_shuffle``
permutes ``arange(n)`` by ``R = ceil(3 ln n / ln(2**32 - 1))`` stable sorts;
round ``r`` splits ``key, sub = split(key)`` and sorts by the keys
``K_r = bits(sub, (n,), uint32)``. After round ``r`` position ``i`` holds
what sat before it at the position whose pair ``(K_r[p], p)`` has rank
``i``. So the element at final position ``i`` is found by walking the
ranks back through the rounds: ``ranks = arange(k)``, then for ``r = R ...
1``, ``ranks = position_of_rank(K_r, ranks)``; after round 1 a position is
its element of ``arange(n)``. The draw is the same, bit for bit, for k
rank lookups in place of R sorts of ``n``.

``position_of_rank`` is a radix select on the 32-bit key, 4 bits a pass:
each pass counts, for every query, the keys that share its prefix by
their next digit, and the query's rank picks the digit. After eight
passes each query knows its key and its rank among the equal keys, which
the position breaks as the stable sort does: the lowest position first.

``tests/test_kmeans_draw.py`` pins the draw to the installed jax: a jax
whose ``_shuffle`` draws otherwise fails there.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

DIGIT_BITS = 4
BINS = 1 << DIGIT_BITS


def shuffle_rounds(n: int) -> int:
    """Sorts ``jax.random.permutation`` makes of ``n`` elements (jax's
    ``_shuffle``: exponent 3 over 32-bit keys)."""
    return int(np.ceil(3 * np.log(max(1, n))
                       / np.log(np.iinfo(np.uint32).max)))


def _counts_onehot(keys, prefixes, shift: int):
    """(q, 16) counts on the MXU: a one-hot product of the keys whose bits
    above the digit equal each query's prefix with the keys' digit. 0/1 in
    int8, summed exactly in int32."""
    digit = (keys >> shift) & (BINS - 1)
    onehot = (digit[None, :] == jnp.arange(BINS, dtype=jnp.uint32)[:, None])
    if prefixes is None:
        match = jnp.ones((1, keys.shape[0]), jnp.int8)
    else:
        match = ((keys >> (shift + DIGIT_BITS))[None, :]
                 == prefixes[:, None]).astype(jnp.int8)
    return jnp.einsum("qn,bn->qb", match, onehot.astype(jnp.int8),
                      preferred_element_type=jnp.int32)


def _counts_scatter(keys, prefixes, shift: int):
    """The same counts by one scatter-add of every key into its query's bin:
    the form for backends without a matrix unit."""
    digit = ((keys >> shift) & (BINS - 1)).astype(jnp.int32)
    if prefixes is None:
        return jnp.bincount(digit, length=BINS)[None, :]
    q = prefixes.shape[0]
    sorted_p = jnp.sort(prefixes)
    hi = keys >> (shift + DIGIT_BITS)
    slot = jnp.minimum(jnp.searchsorted(sorted_p, hi), q - 1)
    hit = sorted_p[slot] == hi
    bins = jnp.where(hit, slot * BINS + digit, q * BINS)
    counts = jnp.bincount(bins, length=q * BINS + 1)[:-1].reshape(q, BINS)
    return counts[jnp.searchsorted(sorted_p, prefixes)]


def _digit_counts(keys, prefixes, shift: int):
    """(q, 16) int32: for each query, the keys whose bits above the digit at
    ``shift`` equal its prefix, by that digit; one row shared by all
    queries where ``prefixes`` is None (the top digit)."""
    args = (keys,) if prefixes is None else (keys, prefixes)

    def branch(form):
        return lambda keys, prefixes=None: form(keys, prefixes, shift)

    return jax.lax.platform_dependent(*args, cpu=branch(_counts_scatter),
                                      default=branch(_counts_onehot))


def _lowest(keys, value, after=None):
    """For each query, the lowest position (past ``after``, if given) whose
    key is its ``value``. Where the call is lowered for a TPU, a min over
    positions (an ``argmax`` of the hits took three times as long on a v5e);
    on the CPU the first True, where XLA would lay out the (q, n) positions
    in memory."""
    def form(first_true: bool):
        def lowest(keys, value, *after):
            pos = jnp.arange(keys.shape[0], dtype=jnp.int32)[None, :]
            hit = keys[None, :] == value[:, None]
            if after:
                hit &= pos > after[0][:, None]
            if first_true:
                return jnp.argmax(hit, axis=1).astype(jnp.int32)
            return jnp.min(jnp.where(hit, pos, keys.shape[0]), axis=1)
        return lowest

    args = (keys, value) if after is None else (keys, value, after)
    return jax.lax.platform_dependent(*args, cpu=form(True),
                                      default=form(False))


def position_of_rank(keys: jax.Array, ranks: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
    """For each of ``ranks`` (int32, each below ``keys.size``), the position
    ``p`` whose pair ``(keys[p], p)`` has that rank: ``argsort(keys,
    stable=True)[ranks]``, without ordering ``keys`` (uint32). Also returns
    how many queries shared their key with another position, and so needed
    the position to break the tie (an int32 scalar)."""
    value = jnp.zeros(ranks.shape, jnp.uint32)
    rest = ranks
    for shift in range(32 - DIGIT_BITS, -1, -DIGIT_BITS):
        counts = _digit_counts(keys, None if shift == 32 - DIGIT_BITS
                               else value >> (shift + DIGIT_BITS), shift)
        below = jnp.cumsum(counts, axis=1) - counts
        counts = jnp.broadcast_to(counts, (ranks.shape[0], BINS))
        below = jnp.broadcast_to(below, counts.shape)
        digit = jnp.sum(below <= rest[:, None], axis=1) - 1
        rest = rest - jnp.take_along_axis(below, digit[:, None], 1)[:, 0]
        value = value | (digit.astype(jnp.uint32) << shift)
    same = jnp.take_along_axis(counts, digit[:, None], 1)[:, 0]
    ties = jnp.sum(same > 1, dtype=jnp.int32)

    at = _lowest(keys, value)

    def step(carry):
        # the next position of each query's key, for those not there yet
        at, left = carry
        return (jnp.where(left > 0, _lowest(keys, value, at), at),
                jnp.maximum(left - 1, 0))

    at, _ = jax.lax.while_loop(lambda c: jnp.any(c[1] > 0), step, (at, rest))
    return at, ties


@functools.partial(jax.jit, static_argnums=(1, 2))
def choice_distinct(key: jax.Array, n: int, k: int) -> jax.Array:
    """``jax.random.choice(key, n, (k,), replace=False)`` (int32), by rank
    selection through the rounds of jax's permutation."""
    if not 0 < k <= n:
        raise ValueError(f"cannot draw {k} distinct indices of {n}")
    round_keys = []
    for _ in range(shuffle_rounds(n)):
        key, sub = jax.random.split(key)
        bits = jax.random.bits(sub, (n,), jnp.uint32)
        # made once, read by every pass (XLA would remake them in each)
        round_keys.append(jax.lax.optimization_barrier(bits))
    ranks = jnp.arange(k, dtype=jnp.int32)
    for keys in reversed(round_keys):
        ranks, _ = position_of_rank(keys, ranks)
    return ranks
