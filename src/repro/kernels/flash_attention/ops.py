"""Public wrapper for the flash-attention kernel (autotuned block sizes)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import autotune, pallas_on_platform
from . import flash_attention as kernel


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def _attention(q, k, v, causal: bool, window: int, bq: int, bk: int):
    B, S_q, H, hd = q.shape

    def flat(x):
        return x.swapaxes(1, 2).reshape(B * H, x.shape[1], hd)

    out = pallas_on_platform(
        kernel.flash_attention_pallas, flat(q), flat(k), flat(v),
        causal=causal, window=window, bq=bq, bk=bk)
    return out.reshape(B, H, S_q, hd).swapaxes(1, 2)


def resolve_blocks(S_q: int, S_k: int, hd: int, dtype,
                   bq: Optional[int], bk: Optional[int]):
    """Block sizes for attention: explicit args win, else the autotune
    registry, else the legacy 256/256 — always snapped to divisors of
    the sequence lengths so any S is legal."""
    if bq is None or bk is None:
        tuned = autotune.lookup(
            "flash_attention", {"S_q": S_q, "S_k": S_k, "hd": hd}, dtype) \
            or autotune.DEFAULTS["flash_attention"]
        bq = bq if bq is not None else tuned["bq"]
        bk = bk if bk is not None else tuned["bk"]
    return autotune.snap_block(S_q, bq), autotune.snap_block(S_k, bk)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: int = 0,
              bq: Optional[int] = None,
              bk: Optional[int] = None) -> jax.Array:
    """q,k,v: (B, S, H, hd) -> (B, S, H, hd). GQA callers repeat KV first."""
    _, S_q, _, hd = q.shape
    S_k = k.shape[1]
    bq, bk = resolve_blocks(S_q, S_k, hd, q.dtype, bq, bk)
    return _attention(q, k, v, causal, window, bq, bk)
