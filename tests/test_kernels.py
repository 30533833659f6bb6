"""K-Means assignment kernel: shape/dtype sweeps of the Pallas kernel
(interpreted on the CPU) against the pure-jnp oracle, and the blocks the
wrapper picks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # suite degrades to skips without it
from hypothesis import given, settings, strategies as st

from repro.kernels.kmeans import ops as km_ops, ref as km_ref


# ------------------------------------------------------------------ kmeans
def _kmeans_case(n, k, d, blocks=None, tie=False, id=None):
    return pytest.param(n, k, d, blocks, tie, id=id or f"{n}-{k}-{d}")


@pytest.mark.parametrize("n,k,d,blocks,tie", [
    _kmeans_case(64, 8, 3), _kmeans_case(256, 16, 3),
    _kmeans_case(1000, 37, 3), _kmeans_case(128, 5, 8),
    _kmeans_case(512, 50, 16),
    # n neither a multiple of 128 nor of the block: a ragged last block
    _kmeans_case(9000, 37, 3, blocks=(4096, 64), id="9000-37-3-ragged"),
    # n smaller than one block
    _kmeans_case(300, 20, 3, blocks=(8192, 64), id="300-20-3-small"),
    # k over five centroid blocks, the last one padded
    _kmeans_case(2000, 150, 3, blocks=(4096, 32), id="2000-150-3-kblocks"),
    # equal centroids, within a block and across blocks: the lower wins
    _kmeans_case(1000, 12, 3, blocks=(4096, 4), tie=True,
                 id="1000-12-3-ties"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kmeans_assign_sweep(n, k, d, blocks, tie, dtype):
    rng = np.random.default_rng(n + k)
    c = rng.normal(size=(k, d))
    if tie:
        c[7], c[9] = c[2], c[1]
        p = c[rng.integers(0, k, n)] + 0.05 * rng.normal(size=(n, d))
    else:
        p = rng.normal(size=(n, d))
    p, c = jnp.asarray(p, dtype), jnp.asarray(c, dtype)
    bn, bk = blocks or (None, None)
    ik, dk = km_ops.assign(p, c, bn=bn, bk=bk)
    ir, dr = km_ref.assign(p, c)
    assert ik.shape == dk.shape == (n,)
    # ties can differ by index but not by distance
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=1e-3)
    same = np.mean(np.asarray(ik) == np.asarray(ir))
    assert same > 0.99, f"assignment mismatch rate {1-same:.3f}"
    if tie:
        ik = np.asarray(ik)
        assert (ik == 2).any() and (ik == 1).any()
        assert not np.isin(ik, [7, 9]).any()
        np.testing.assert_array_equal(ik, np.asarray(ir))


@settings(max_examples=10, deadline=None)
@given(n=st.integers(9, 400), k=st.integers(2, 60), d=st.integers(2, 12),
       seed=st.integers(0, 2**31))
def test_kmeans_assign_property(n, k, d, seed):
    rng = np.random.default_rng(seed)
    p = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
    ik, dk = km_ops.assign(p, c)
    ir, dr = km_ref.assign(p, c)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr), rtol=1e-4,
                               atol=1e-4)
    assert (np.asarray(dk) >= -1e-4).all()  # squared distances


@pytest.mark.parametrize("n,k,bn,bk,blocks", [
    # one 128 MB HDFS block of d=3 points (the K-Means cell)
    (11_184_128, 50, None, None, (65536, 50)),
    # the coupled cell's 1M-point frame
    (1_000_000, 50, None, None, (65536, 50)),
    # fewer points than a block: bn is capped to n padded to a chunk
    (300, 20, None, None, (4096, 20)),
    # more centroids than a block holds
    (1_000_000, 150, None, None, (65536, 64)),
    # an explicit bn that is no multiple of a chunk is rounded up
    (1_000_000, 50, 5000, None, (8192, 50)),
    # an explicit bk above what the kernel unrolls is capped
    (1_000_000, 300, None, 200, (65536, 128)),
], ids=["block-cell", "coupled-frame", "small-n", "k150", "bn-rounded",
        "bk-capped"])
def test_kmeans_resolve_blocks(n, k, bn, bk, blocks):
    assert km_ops.resolve_blocks(n, k, bn, bk) == blocks
