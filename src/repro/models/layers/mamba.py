"""Mamba-1 selective SSM block.

TPU adaptation notes (see DESIGN.md): the CUDA reference implements the
selective scan as a fused kernel over (batch, d_inner) with shared-memory
staging. On TPU we (a) shard d_inner over the `model` mesh axis — scan
channels are independent, so the recurrence needs **zero** collectives —
and (b) run a chunked scan: `lax.scan` over sequence chunks carrying the
(B, d_inner, d_state) state, with an associative scan *inside* each chunk.
This bounds live memory to one chunk while keeping VPU-parallel work wide.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .common import normal_init

Params = Dict[str, Any]

SCAN_CHUNK = 256


def init_mamba(cfg, key) -> Params:
    d, di, st = cfg.d_model, cfg.ssm_d_inner, cfg.ssm_d_state
    dr, dc = cfg.ssm_dt_rank_, cfg.ssm_d_conv
    dt = cfg.param_dtype
    ks = jax.random.split(key, 6)
    A = jnp.broadcast_to(jnp.arange(1, st + 1, dtype=jnp.float32), (di, st))
    return {
        "in_proj": normal_init(ks[0], (d, 2 * di), dt, d ** -0.5),
        "conv_w": normal_init(ks[1], (dc, di), dt, dc ** -0.5),
        "conv_b": jnp.zeros((di,), dt),
        "x_proj": normal_init(ks[2], (di, dr + 2 * st), dt, di ** -0.5),
        "dt_proj": normal_init(ks[3], (dr, di), dt, dr ** -0.5),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((di,), 0.01, jnp.float32))),
        "A_log": jnp.log(A),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": normal_init(ks[4], (di, d), dt, di ** -0.5),
    }


def _ssm_inputs(cfg, p: Params, x1: jax.Array):
    """x1: (B, S, di) post-conv -> per-step decay a and input b, readout C."""
    st = cfg.ssm_d_state
    dr = cfg.ssm_dt_rank_
    proj = jnp.einsum("bsi,ir->bsr", x1, p["x_proj"])
    dt_raw, Bc, Cc = jnp.split(proj, [dr, dr + st], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,ri->bsi", dt_raw, p["dt_proj"]).astype(jnp.float32)
        + p["dt_bias"]
    )  # (B,S,di) f32
    A = -jnp.exp(p["A_log"])  # (di, st)
    a = jnp.exp(dt[..., None] * A)                                     # (B,S,di,st)
    b = (dt * x1.astype(jnp.float32))[..., None] * Bc.astype(jnp.float32)[:, :, None, :]
    return a, b, Cc


def _chunk_scan(a: jax.Array, b: jax.Array, h0: jax.Array):
    """Within-chunk associative scan. a,b: (B,C,di,st); h0: (B,di,st)."""
    def op(l, r):
        (a1, b1), (a2, b2) = l, r
        return a1 * a2, a2 * b1 + b2

    A_cum, B_cum = jax.lax.associative_scan(op, (a, b), axis=1)
    h = A_cum * h0[:, None] + B_cum                                    # (B,C,di,st)
    return h, h[:, -1]


def selective_scan(a: jax.Array, b: jax.Array, C: jax.Array, h0: jax.Array,
                   chunk: int) -> Tuple[jax.Array, jax.Array]:
    """Chunked selective scan: ``lax.scan`` over chunks of ``chunk`` steps
    carrying the state, an associative scan inside each chunk.
    a, b: (B,S,di,st); C: (B,S,st); h0: (B,di,st) ->
    (y (B,S,di) f32, h_last (B,di,st)), where
    ``h_t = a_t * h_{t-1} + b_t`` and ``y_t = sum_st h_t * C_t``."""
    B, S, di, st = a.shape
    nc = S // chunk
    assert S % chunk == 0, (S, chunk)

    a_c = a.reshape(B, nc, chunk, di, st).swapaxes(0, 1)
    b_c = b.reshape(B, nc, chunk, di, st).swapaxes(0, 1)

    def step(h, ab):
        h_all, h_last = _chunk_scan(ab[0], ab[1], h)
        return h_last, h_all

    h_last, h_all = jax.lax.scan(step, h0, (a_c, b_c))
    h_all = h_all.swapaxes(0, 1).reshape(B, S, di, st)
    return jnp.einsum("bsin,bsn->bsi", h_all, C.astype(jnp.float32)), h_last


def _causal_conv(p: Params, x1: jax.Array) -> jax.Array:
    """Depthwise causal conv1d as a sum of shifted copies (kernel is tiny)."""
    dc = p["conv_w"].shape[0]
    out = x1 * p["conv_w"][dc - 1]
    for i in range(1, dc):
        shifted = jnp.pad(x1[:, :-i], ((0, 0), (i, 0), (0, 0)))
        out = out + shifted * p["conv_w"][dc - 1 - i]
    return out + p["conv_b"]


def mamba_forward(cfg, p: Params, x: jax.Array,
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence Mamba (train/prefill). Returns (out, decode cache)."""
    B, S, _ = x.shape
    di, st, dc = cfg.ssm_d_inner, cfg.ssm_d_state, cfg.ssm_d_conv
    xz = jnp.einsum("bsd,di->bsi", x, p["in_proj"])
    x1, z = jnp.split(xz, 2, axis=-1)
    x1_pre = x1
    x1 = jax.nn.silu(_causal_conv(p, x1).astype(jnp.float32)).astype(x.dtype)

    a, b, Cc = _ssm_inputs(cfg, p, x1)
    h0 = jnp.zeros((B, di, st), jnp.float32)
    y, h_last = selective_scan(a, b, Cc, h0, min(SCAN_CHUNK, S))
    y = y + p["D"] * x1.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = jnp.einsum("bsi,id->bsd", y, p["out_proj"])

    cache = {
        "conv": x1_pre[:, S - (dc - 1):, :] if S >= dc - 1 else
                jnp.pad(x1_pre, ((0, 0), (dc - 1 - S, 0), (0, 0))),
        "h": h_last,
    }
    return out, cache


def mamba_decode(cfg, p: Params, x: jax.Array, cache: Dict[str, jax.Array],
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Single-token Mamba step. x: (B,1,d); cache: conv (B,dc-1,di), h (B,di,st)."""
    B = x.shape[0]
    dc = cfg.ssm_d_conv
    xz = jnp.einsum("bsd,di->bsi", x, p["in_proj"])
    x1, z = jnp.split(xz, 2, axis=-1)                                  # (B,1,di)

    window = jnp.concatenate([cache["conv"], x1], axis=1)              # (B,dc,di)
    conv_out = jnp.einsum("bci,ci->bi", window, p["conv_w"]) + p["conv_b"]
    x1c = jax.nn.silu(conv_out.astype(jnp.float32)).astype(x.dtype)[:, None, :]

    a, b, Cc = _ssm_inputs(cfg, p, x1c)                                # (B,1,di,st)
    h = a[:, 0] * cache["h"] + b[:, 0]                                 # (B,di,st)
    y = jnp.einsum("bin,bn->bi", h, Cc[:, 0].astype(jnp.float32))
    y = y + p["D"] * x1c[:, 0].astype(jnp.float32)
    y = (y * jax.nn.silu(z[:, 0].astype(jnp.float32))).astype(x.dtype)
    out = jnp.einsum("bi,id->bd", y, p["out_proj"])[:, None, :]
    return out, {"conv": window[:, 1:], "h": h}
