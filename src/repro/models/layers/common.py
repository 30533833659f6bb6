"""Shared model primitives: norms, RoPE, SwiGLU MLP, embeddings."""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


def uniform_init(key, shape, dtype, scale: float):
    return jax.random.uniform(key, shape, jnp.float32, -scale, scale).astype(dtype)


def normal_init(key, shape, dtype, stddev: float):
    return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)


# ---------------------------------------------------------------- RMSNorm
def init_rmsnorm(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    # variance accumulates in f32 inside the reduce; x itself is never
    # materialized as an f32 array (a full cast of the residual stream
    # makes XLA keep whole f32 copies of the scan-saved activation stacks)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True, dtype=jnp.float32)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * p["scale"].astype(x.dtype)


# ---------------------------------------------------------------- RoPE
def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """positions: (...,) int -> cos/sin of shape (..., head_dim//2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.cos(ang), jnp.sin(ang)


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention temperature factor (``yarn_get_mscale``)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s frequencies: the plain ones
    below the correction range (dims rotating more than ``beta_fast``
    times over ``original`` positions), the plain ones / ``factor`` above
    it (fewer than ``beta_slow``), blended linearly in between."""
    half = head_dim // 2
    plain = 1.0 / theta ** (np.arange(half, dtype=np.float64) * 2 / head_dim)

    def dim_of(rotations):
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                     # 1: plain frequency, 0: interpolated
    return (plain / factor * (1.0 - keep) + plain * keep).astype(np.float32)


def yarn_angles(cfg, positions: jax.Array, head_dim: int
                ) -> tuple[jax.Array, jax.Array]:
    """cos/sin at YaRN's frequencies, times its cos/sin factor
    mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    rs = cfg.yarn
    f = rs["factor"]
    inv = jnp.asarray(yarn_inv_freq(head_dim, cfg.rope_theta, f,
                                    rs["original_max_position_embeddings"],
                                    rs.get("beta_fast", 32),
                                    rs.get("beta_slow", 1)))
    m = (yarn_mscale(f, rs.get("mscale", 1))
         / yarn_mscale(f, rs.get("mscale_all_dim", 0)))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., S, n_heads, head_dim); cos/sin: (..., S, head_dim//2)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(dtype)


# ---------------------------------------------------------------- SwiGLU MLP
def init_mlp(cfg, key, d_ff: int) -> Params:
    d = cfg.d_model
    dt = cfg.param_dtype
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = d ** -0.5
    s_out = d_ff ** -0.5
    return {
        "w_gate": normal_init(k1, (d, d_ff), dt, s_in),
        "w_up": normal_init(k2, (d, d_ff), dt, s_in),
        "w_down": normal_init(k3, (d_ff, d), dt, s_out),
    }


def mlp(p: Params, x: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, p["w_gate"])
    u = jnp.einsum("...d,df->...f", x, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


# ---------------------------------------------------------------- Embedding
def init_embedding(cfg, key) -> Params:
    dt = cfg.param_dtype
    k1, k2 = jax.random.split(key)
    p = {"embed": normal_init(k1, (cfg.vocab_padded, cfg.d_model), dt, 0.02)}
    if not cfg.tie_embeddings:
        p["lm_head"] = normal_init(k2, (cfg.vocab_padded, cfg.d_model), dt, cfg.d_model ** -0.5)
    return p


def embed(p: Params, tokens: jax.Array) -> jax.Array:
    return p["embed"][tokens]


def unembed(cfg, p: Params, x: jax.Array) -> jax.Array:
    """Logits over the padded vocab; padding ids masked to -inf."""
    table = p["embed"] if cfg.tie_embeddings else p["lm_head"]
    logits = jnp.einsum("...d,vd->...v", x, table).astype(jnp.float32)
    if cfg.vocab_padded != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab_size
        logits = jnp.where(pad_mask, -1e30, logits)
    return logits


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array, mask: jax.Array) -> jax.Array:
    """Mean token-level NLL over masked positions. logits f32 (..., V)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
