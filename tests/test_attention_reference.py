"""The unfused attention paths against a plain reference: ``sdpa`` and
``chunked_sdpa`` serve every window, padded prefill, cross-attention and
CPU lowering, so each mask they take is checked here."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.layers import attention

NEG_INF = -1e30


def reference(q, k, v, *, causal=True, window=0, k_valid=None):
    """q,k,v: (B, S, H, hd) -> (B, S, H, hd). Softmax in f32."""
    hd = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits * (hd ** -0.5)
    S_q, S_k = q.shape[1], k.shape[1]
    qp = jnp.arange(S_q)
    kp = jnp.arange(S_k)
    ok = jnp.ones((S_q, S_k), bool)
    if causal:
        ok &= qp[:, None] >= kp[None, :]
    if window:
        ok &= (qp[:, None] - kp[None, :]) < window
    if k_valid is not None:
        ok &= k_valid[None, :]
    logits = jnp.where(ok, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def _chunked(q, k, v, q_pos, k_pos, **kw):
    # several blocks each way, so the online softmax carries across them
    return attention.chunked_sdpa(q, k, v, q_pos, k_pos, q_chunk=64,
                                  kv_chunk=64, **kw)


FNS = {"sdpa": attention.sdpa, "chunked_sdpa": _chunked}

# causal; sliding window; bidirectional; causal over a left-padded prompt
# (bucketed serving prefill), whose first S/8 keys are invalid
MASKS = {"causal": dict(causal=True), "window64": dict(causal=True, window=64),
         "full": dict(causal=False), "leftpad": dict(causal=True, pad=True)}


def _qkv(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=shape), dtype) * 0.3
    k = jnp.asarray(rng.normal(size=shape), dtype) * 0.3
    v = jnp.asarray(rng.normal(size=shape), dtype)
    return q, k, v


@pytest.mark.parametrize("fn", sorted(FNS))
@pytest.mark.parametrize("mask", list(MASKS))
@pytest.mark.parametrize("B,S,H,hd", [(1, 128, 2, 32), (2, 256, 4, 64),
                                      (1, 512, 1, 128)])
def test_unfused_attention_matches_reference(fn, mask, B, S, H, hd):
    q, k, v = _qkv((B, S, H, hd), jnp.float32, S + hd)
    kw = dict(MASKS[mask])
    rows = slice(None)
    if kw.pop("pad", False):
        kw["k_valid"] = jnp.arange(S) >= S // 8
        rows = slice(S // 8, None)      # rows before it see no valid key
    pos = jnp.arange(S)
    out = FNS[fn](q, k, v, pos, pos, **kw)
    exp = reference(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out)[:, rows],
                               np.asarray(exp)[:, rows],
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("fn", sorted(FNS))
def test_unfused_attention_bf16(fn):
    q, k, v = _qkv((2, 128, 2, 64), jnp.bfloat16, 0)
    pos = jnp.arange(128)
    out = FNS[fn](q, k, v, pos, pos, causal=True)
    exp = reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=5e-2, atol=5e-2)
