"""Per-kernel shape/dtype sweeps: pallas (interpreted on the CPU) vs
pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # suite degrades to skips without it
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.kmeans import ops as km_ops, ref as km_ref
from repro.kernels.mamba_scan import ops as ms_ops, ref as ms_ref


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


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,S,H,hd", [(1, 128, 2, 32), (2, 256, 4, 64),
                                      (1, 512, 1, 128)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(B, S, H, hd, causal, window):
    rng = np.random.default_rng(S + hd)
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.normal(size=(B, S, H, hd)).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.normal(size=(B, S, H, hd)).astype(np.float32))
    out = fa_ops.attention(q, k, v, causal=causal, window=window, bq=64, bk=64)
    exp = fa_ref.attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 128, 2, 64)), jnp.bfloat16) * 0.3
    k = jnp.asarray(rng.normal(size=(2, 128, 2, 64)), jnp.bfloat16) * 0.3
    v = jnp.asarray(rng.normal(size=(2, 128, 2, 64)), jnp.bfloat16)
    out = fa_ops.attention(q, k, v, bq=64, bk=64)
    exp = fa_ref.attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), rtol=5e-2, atol=5e-2)


# -------------------------------------------------------------- mamba scan
@pytest.mark.parametrize("B,S,di,st_", [(1, 32, 8, 4), (2, 64, 16, 8),
                                        (1, 128, 32, 16)])
def test_mamba_scan_sweep(B, S, di, st_):
    rng = np.random.default_rng(S + di)
    # decays in (0, 1) like exp(dt * A) with A < 0
    a = jnp.asarray(rng.uniform(0.7, 0.999, size=(B, S, di, st_)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(B, S, di, st_)).astype(np.float32)) * 0.1
    C = jnp.asarray(rng.normal(size=(B, S, st_)).astype(np.float32))
    h0 = jnp.asarray(rng.normal(size=(B, di, st_)).astype(np.float32)) * 0.1
    y, h_last = ms_ops.scan(a, b, C, h0, bdi=min(8, di), bs=min(16, S))
    y_ref, h_ref = ms_ref.scan(a, b, C, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(h_ref),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(B=st.integers(1, 3), nseq=st.integers(1, 6), di=st.integers(1, 4),
       st_=st.sampled_from([2, 4, 8]), seed=st.integers(0, 2**31))
def test_mamba_scan_property(B, nseq, di, st_, seed):
    """Chunked kernel == sequential recurrence for arbitrary chunking."""
    S = nseq * 8
    di_ = di * 8
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.uniform(0.5, 1.0, size=(B, S, di_, st_)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(B, S, di_, st_)).astype(np.float32)) * 0.2
    C = jnp.asarray(rng.normal(size=(B, S, st_)).astype(np.float32))
    h0 = jnp.zeros((B, di_, st_), jnp.float32)
    y, h_last = ms_ops.scan(a, b, C, h0, bdi=8, bs=8)
    y_ref, h_ref = ms_ref.scan(a, b, C, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(h_ref),
                               rtol=1e-4, atol=1e-4)
