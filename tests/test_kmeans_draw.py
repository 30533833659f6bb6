"""K-Means' initial draw by rank selection (``repro.analytics.draw``) is
``jax.random.choice(key, n, (k,), replace=False)`` of the installed jax,
index for index: a jax whose permutation draws otherwise fails here."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analytics import draw

BLOCK = 11_184_128     # one 128 MB HDFS block of d=3 f32 points
SEEDS = (0, 7, 2 ** 31 + 12345)
DRAWS = [(n, k, s) for n in (1, 50, 1_000, 65_537, 1_000_000)
         for k in (1, 50) if k <= n for s in SEEDS]
DRAWS += [(BLOCK, k, SEEDS[-1]) for k in (1, 50)]


def test_rounds_cover_one_two_and_three_sorts():
    assert [draw.shuffle_rounds(n) for n in (1, 50, 1_000, 65_537,
                                             1_000_000, BLOCK)] == \
        [0, 1, 1, 2, 2, 3]


@pytest.mark.parametrize("n,k,seed", DRAWS)
def test_draw_is_jax_random_choice(n, k, seed):
    key = jax.random.key(seed)
    want = jax.random.choice(key, n, (k,), replace=False)
    got = draw.choice_distinct(key, n, k)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _tied_keys(n, seed):
    """Keys from 0-15: most positions share their key with others."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 16, n).astype(np.uint32)
    ranks = rng.permutation(n)[:min(n, 50)].astype(np.int32)
    return keys, ranks


@pytest.mark.parametrize("form", ["cpu", "tpu"])
@pytest.mark.parametrize("n", [17, 33, 1_000, 4_099])
def test_position_of_rank_is_stable_argsort(monkeypatch, form, n):
    """Each platform's forms of the digit counts and of the lowest position,
    through the whole selection: the TPU's run here by lowering every call
    as the TPU would."""
    if form == "tpu":
        real = jax.lax.platform_dependent
        monkeypatch.setattr(jax.lax, "platform_dependent",
                            lambda *a, cpu, default: real(*a, cpu=default,
                                                          default=default))
    keys, ranks = _tied_keys(n, n)
    pos, ties = draw.position_of_rank(jnp.asarray(keys), jnp.asarray(ranks))
    np.testing.assert_array_equal(np.asarray(pos),
                                  np.argsort(keys, kind="stable")[ranks])
    assert int(ties) > 0


TOP = 32 - draw.DIGIT_BITS      # the shift of the top digit


@pytest.mark.parametrize("shift", sorted({TOP, 16, draw.DIGIT_BITS, 0}))
def test_digit_count_forms_agree(shift):
    """The MXU's one-hot product and the scatter count the same keys, with
    prefixes shared by several queries and prefixes no key has."""
    rng = np.random.default_rng(shift)
    keys = jnp.asarray(np.concatenate([
        rng.integers(0, 2 ** 32, 3_000, dtype=np.uint64),
        rng.integers(0, 2 ** 32, 8, dtype=np.uint64).repeat(40)]
    ).astype(np.uint32))
    prefixes = None
    if shift < TOP:
        picks = rng.choice(keys.shape[0], 40)
        prefixes = jnp.concatenate([
            keys[picks] >> (shift + draw.DIGIT_BITS),
            jnp.asarray([0, 2 ** (TOP - shift) - 1], jnp.uint32)])
    onehot = draw._counts_onehot(keys, prefixes, shift)
    np.testing.assert_array_equal(
        np.asarray(onehot), np.asarray(draw._counts_scatter(keys, prefixes,
                                                            shift)))
    if prefixes is not None:      # every key counts for its own prefix
        assert int(onehot[:40].sum(axis=1).min()) >= 1
