"""The Mamba layer's chunked selective scan against the sequential
recurrence it stands for."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # suite degrades to skips without it
from hypothesis import given, settings, strategies as st

from repro.models.layers.mamba import selective_scan


def reference(a, b, C, h0):
    """Sequential recurrence, one step at a time:
        h_t = a_t * h_{t-1} + b_t         (elementwise over (di, st))
        y_t = sum_st h_t * C_t            (readout over the state dim)
    a,b: (B,S,di,st); C: (B,S,st); h0: (B,di,st) ->
    (y (B,S,di) f32, h_last (B,di,st))."""
    def step(h, xs):
        a_t, b_t, c_t = xs
        h = a_t * h + b_t
        y = jnp.einsum("bin,bn->bi", h, c_t)
        return h, y

    xs = (a.swapaxes(0, 1), b.swapaxes(0, 1), C.swapaxes(0, 1))
    h_last, ys = jax.lax.scan(step, h0, xs)
    return ys.swapaxes(0, 1), h_last


def _check(a, b, C, h0, chunk):
    y, h_last = selective_scan(a, b, C, h0, chunk)
    y_ref, h_ref = reference(a, b, C, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(h_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,S,di,st_", [(1, 32, 8, 4), (2, 64, 16, 8),
                                        (1, 128, 32, 16)])
def test_selective_scan_sweep(B, S, di, st_):
    rng = np.random.default_rng(S + di)
    # decays in (0, 1) like exp(dt * A) with A < 0
    a = jnp.asarray(rng.uniform(0.7, 0.999, size=(B, S, di, st_)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(B, S, di, st_)).astype(np.float32)) * 0.1
    C = jnp.asarray(rng.normal(size=(B, S, st_)).astype(np.float32))
    h0 = jnp.asarray(rng.normal(size=(B, di, st_)).astype(np.float32)) * 0.1
    _check(a, b, C, h0, chunk=16)


@settings(max_examples=8, deadline=None)
@given(B=st.integers(1, 3), nseq=st.integers(2, 6), di=st.integers(1, 4),
       st_=st.sampled_from([2, 4, 8]), seed=st.integers(0, 2**31))
def test_selective_scan_property(B, nseq, di, st_, seed):
    """Chunked scan == sequential recurrence, over several chunks."""
    S = nseq * 8
    di_ = di * 8
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.uniform(0.5, 1.0, size=(B, S, di_, st_)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(B, S, di_, st_)).astype(np.float32)) * 0.2
    C = jnp.asarray(rng.normal(size=(B, S, st_)).astype(np.float32))
    h0 = jnp.zeros((B, di_, st_), jnp.float32)
    _check(a, b, C, h0, chunk=8)
