"""Public wrapper for the Mamba selective-scan kernel (autotuned blocks)."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import autotune, pallas_on_platform
from . import mamba_scan as kernel


@functools.partial(jax.jit, static_argnames=("bdi", "bs"))
def _scan(a, b, C, h0, bdi: int, bs: int):
    return pallas_on_platform(kernel.mamba_scan_pallas, a, b, C, h0,
                              bdi=bdi, bs=bs)


def resolve_blocks(S: int, di: int, st: int, dtype,
                   bdi: Optional[int], bs: Optional[int]):
    """Block sizes for the scan: explicit args win, else the autotune
    registry, else the legacy 512/16 — snapped to divisors of d_inner
    and the sequence length so any shape is legal."""
    if bdi is None or bs is None:
        tuned = autotune.lookup(
            "mamba_scan", {"S": S, "di": di, "st": st}, dtype) \
            or autotune.DEFAULTS["mamba_scan"]
        bdi = bdi if bdi is not None else tuned["bdi"]
        bs = bs if bs is not None else tuned["bs"]
    return autotune.snap_block(di, bdi), autotune.snap_block(S, bs)


def scan(a: jax.Array, b: jax.Array, C: jax.Array, h0: jax.Array, *,
         bdi: Optional[int] = None,
         bs: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """Chunked selective scan. a,b: (B,S,di,st); C: (B,S,st); h0: (B,di,st)."""
    _, S, di, st = a.shape
    bdi, bs = resolve_blocks(S, di, st, a.dtype, bdi, bs)
    return _scan(a, b, C, h0, bdi, bs)
