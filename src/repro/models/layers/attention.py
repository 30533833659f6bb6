"""Attention layers: GQA (full / sliding-window / chunked) and MLA.

Full-sequence attention (training, prefill) takes one of two paths:

  * ``causal_attention``: the fused causal kernel (the splash attention
    kernel bundled with JAX), forward and backward, on the TPU.  It never
    writes the scores to HBM, skips the blocks the causal mask empties,
    and serves each group of query heads from its one K/V head, which is
    never repeated.
    ``gqa_forward`` and ``mla_forward`` take it where
    ``fused_attention_applies`` holds (causal, no window, no padding
    mask, self-attention, S a multiple of a block, batch and heads on one
    device) and the call is lowered for a TPU.
  * ``sdpa`` / ``chunked_sdpa`` everywhere else: on the CPU, for sliding
    windows, padded prefill (``k_valid``), cross-attention and meshes of
    more than one device.  Sequences above CHUNK_THRESHOLD use the
    chunked online-softmax form, so memory stays linear in S.

Sharding notes (see sharding/planner.py): q/o projections are sharded on
the head axis when n_heads divides the model axis; k/v projections are
replicated when n_kv_heads doesn't divide it (they are small).  The
``sdpa`` paths use the repeat-kv form so all S^2 compute is sharded on
the (repeated) head axis.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from .common import apply_rope, normal_init, rope_angles, yarn_angles, yarn_mscale

Params = Dict[str, Any]

CHUNK_THRESHOLD = 2048  # use chunked attention above this sequence length
Q_CHUNK = 1024
KV_CHUNK = 1024
NEG_INF = -1e30
FUSED_BLOCK = 512      # the fused kernel's query block (K/V: up to twice it)
FUSED_MIN_BLOCK = 128  # the least block the kernel tiles (one lane row)


# =================================================================== GQA
def init_gqa(cfg, key) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    dt = cfg.param_dtype
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    return {
        "wq": normal_init(k1, (d, h, hd), dt, s),
        "wk": normal_init(k2, (d, kv, hd), dt, s),
        "wv": normal_init(k3, (d, kv, hd), dt, s),
        "wo": normal_init(k4, (h, hd, d), dt, (h * hd) ** -0.5),
    }


def _repeat_kv(x: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, kv, hd) -> (B, S, n_heads, hd)."""
    kv = x.shape[2]
    if kv == n_heads:
        return x
    return jnp.repeat(x, n_heads // kv, axis=2)


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, causal: bool, window: int,
               k_valid: Optional[jax.Array] = None) -> jax.Array:
    """(Sq, Sk) additive f32 bias from absolute positions."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= q_pos[:, None] >= k_pos[None, :]
    if window:
        ok &= (q_pos[:, None] - k_pos[None, :]) < window
    if k_valid is not None:
        ok &= k_valid[None, :]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, q_pos: jax.Array,
         k_pos: jax.Array, *, causal: bool, window: int = 0,
         k_valid: Optional[jax.Array] = None,
         scale: Optional[float] = None) -> jax.Array:
    """Full-materialization attention. q: (B,Sq,H,hd), k/v: (B,Sk,H,hd).
    ``scale`` multiplies the scores (default hd ** -0.5)."""
    hd = q.shape[-1]
    scale = hd ** -0.5 if scale is None else scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits * scale + _mask_bias(q_pos, k_pos, causal, window, k_valid)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def chunked_sdpa(q: jax.Array, k: jax.Array, v: jax.Array, q_pos: jax.Array,
                 k_pos: jax.Array, *, causal: bool, window: int = 0,
                 k_valid: Optional[jax.Array] = None,
                 q_chunk: int = Q_CHUNK, kv_chunk: int = KV_CHUNK,
                 scale: Optional[float] = None) -> jax.Array:
    """Online-softmax chunked attention; memory O(q_chunk * kv_chunk).
    v may have a head width of its own (MLA's 128 beside q.k's 192).

    Note: block-masked (compute over all block pairs) — the fused kernel
    (``causal_attention``) skips fully-masked blocks on TPU; HLO FLOPs
    here include that causal slack (accounted in the roofline notes).
    """
    B, Sq, H, hd = q.shape
    Sk, dv = k.shape[1], v.shape[-1]
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    assert Sq % q_chunk == 0 and Sk % kv_chunk == 0, (Sq, Sk, q_chunk, kv_chunk)
    scale = hd ** -0.5 if scale is None else scale

    qc = q.reshape(B, nq, q_chunk, H, hd).swapaxes(0, 1)        # (nq,B,qc,H,hd)
    qp = q_pos.reshape(nq, q_chunk)
    kc = k.reshape(B, nk, kv_chunk, H, hd).swapaxes(0, 1)       # (nk,B,kc,H,hd)
    vc = v.reshape(B, nk, kv_chunk, H, dv).swapaxes(0, 1)
    kp = k_pos.reshape(nk, kv_chunk)
    if k_valid is None:
        kval = jnp.ones((nk, kv_chunk), bool)
    else:
        kval = k_valid.reshape(nk, kv_chunk)

    def q_step(_, q_in):
        qi, qpi = q_in

        # rematerialized: backward recomputes the (qc, kc) score block
        # instead of storing it per kv-chunk (flash-attention memory shape)
        @jax.checkpoint
        def kv_step(carry, kv_in):
            m, l, acc = carry
            ki, vi, kpi, kvi = kv_in
            logits = jnp.einsum("bqhd,bkhd->bhqk", qi, ki).astype(jnp.float32)
            logits = logits * scale + _mask_bias(qpi, kpi, causal, window, kvi)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None].swapaxes(1, 2) + jnp.einsum(
                "bhqk,bkhd->bqhd", p.astype(vi.dtype), vi
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, q_chunk, H, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kc, vc, kp, kval))
        out = acc / jnp.maximum(l, 1e-30)[..., None].swapaxes(1, 2)
        return None, out.astype(q.dtype)

    _, out = jax.lax.scan(q_step, None, (qc, qp))
    return out.swapaxes(0, 1).reshape(B, Sq, H, dv)


# ======================================================= fused causal kernel
def _fused_blocks(S: int) -> Optional[splash.BlockSizes]:
    """The fused kernel's blocks at sequence S; None where S is no
    multiple of a block.  Queries go in blocks of FUSED_BLOCK (all of S
    when shorter), keys and values in twice that where S allows, and the
    backward runs as one kernel (dq beside dk/dv), which re-reads fewer
    blocks than separate dq and dk/dv kernels.  On a TPU v5e these were
    the fastest of the blocks tried at both training cells' shapes."""
    b = min(FUSED_BLOCK, S)
    if S % b or b % FUSED_MIN_BLOCK:
        return None
    kv = 2 * b if S % (2 * b) == 0 else b
    return splash.BlockSizes(
        block_q=b, block_kv=kv, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=kv, block_kv_dkv_compute=b,
        use_fused_bwd_kernel=True)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     scale: float, interpret: bool = False) -> jax.Array:
    """Causal softmax attention through the fused kernel, forward and
    backward.  q: (B,S,H,dqk), k: (B,S,KV,dqk), v: (B,S,KV,dv) with H a
    multiple of KV -> (B,S,H,dv).

    Each KV head serves its group of H/KV query heads in the kernel's
    MQA form, so K/V are never repeated.  The scale is folded into q in
    f32 before the cast back to q's dtype; the softmax statistics and
    accumulators are f32 inside the kernel."""
    B, S, H, dqk = q.shape
    kv, dv = k.shape[2], v.shape[-1]
    g = H // kv
    kernel = splash.make_splash_mqa_single_device(
        splash.MultiHeadMask([splash.CausalMask((S, S))] * g),
        block_sizes=_fused_blocks(S), interpret=interpret)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qg = qs.reshape(B, S, kv, g, dqk).transpose(0, 2, 3, 1, 4)  # (B,kv,g,S,d)
    out = jax.vmap(jax.vmap(kernel))(qg, k.transpose(0, 2, 1, 3),
                                     v.transpose(0, 2, 1, 3))
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, dv)


def _on_one_device(x: jax.Array) -> bool:
    """No mesh axis that could split x (its batch or heads) spans more than
    one device: neither x's mesh nor the context's has such an axis, a
    shard_map's manual axes aside."""
    for mesh in (jax.typeof(x).sharding.mesh, jax.sharding.get_abstract_mesh()):
        manual = set(mesh.manual_axes)
        if any(n > 1 for a, n in mesh.shape.items() if a not in manual):
            return False
    return True


def fused_attention_applies(q: jax.Array, k: jax.Array, *, causal: bool,
                            window: int = 0,
                            k_valid: Optional[jax.Array] = None,
                            cross: bool = False) -> bool:
    """Whether full-sequence attention may take the fused kernel: causal
    self-attention over every key (no window, no padding mask), S a
    multiple of the kernel's block, batch and heads on one device.  The
    platform is decided where the call is lowered (``_attend``)."""
    S = q.shape[1]
    return (causal and not window and k_valid is None and not cross
            and k.shape[1] == S and _fused_blocks(S) is not None
            and _on_one_device(q))


def _attend(fused: bool, fallback: Callable, q, k, v, *, scale: float):
    """``causal_attention`` where the call is lowered for a TPU and
    ``fused`` holds; ``fallback(q, k, v)`` otherwise (and only that branch
    is lowered)."""
    if not fused:
        return fallback(q, k, v)
    return jax.lax.platform_dependent(
        q, k, v, tpu=functools.partial(causal_attention, scale=scale),
        default=fallback)


def gqa_forward(cfg, p: Params, x: jax.Array, positions: jax.Array, *,
                causal: bool = True, window: int = 0,
                kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
                k_valid: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence attention (train/prefill). Returns (out, kv-cache).

    kv_override supplies (k, v) already projected — used by cross-attention.
    k_valid is an (S,) bool key-validity mask: False keys (e.g. left-pad
    slots in bucketed serving prefill) are never attended.
    """
    B, S, _ = x.shape
    h = cfg.n_heads
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        cos, sin = rope_angles(positions, cfg.head_dim_, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    else:
        k, v = kv_override
    cache = {"k": k, "v": v}
    k_pos = positions if kv_override is None else jnp.arange(k.shape[1])

    def unfused(q, k, v):
        kf, vf = _repeat_kv(k, h), _repeat_kv(v, h)
        if max(S, k.shape[1]) > CHUNK_THRESHOLD:
            return chunked_sdpa(q, kf, vf, positions, k_pos, causal=causal,
                                window=window, k_valid=k_valid)
        return sdpa(q, kf, vf, positions, k_pos, causal=causal, window=window,
                    k_valid=k_valid)

    fused = fused_attention_applies(q, k, causal=causal, window=window,
                                    k_valid=k_valid,
                                    cross=kv_override is not None)
    out = _attend(fused, unfused, q, k, v, scale=cfg.head_dim_ ** -0.5)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache


def gqa_decode(cfg, p: Params, x: jax.Array, cache: Dict[str, jax.Array],
               pos: jax.Array, *, window: int = 0, cross: bool = False,
               start: Optional[jax.Array] = None,
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Single-token decode. x: (B,1,d); cache k/v: (B,Sc,kv,hd); pos: (B,).

    For sliding-window layers the cache is a ring buffer of size `window`.
    For cross-attention the cache holds encoder k/v and is not updated.
    start (B,) marks the first real cache position per row (left-pad count
    from bucketed prefill): slots below it are never attended, and RoPE
    runs at pad-relative positions (pos - start) so a padded prompt decodes
    bit-identically to its unpadded form.
    """
    B = x.shape[0]
    h = cfg.n_heads
    Sc = cache["k"].shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])

    if not cross:
        k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        rpos = pos if start is None else pos - start
        cos, sin = rope_angles(rpos[:, None], cfg.head_dim_, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k_new = apply_rope(k_new, cos, sin)
        slot = (pos % Sc).astype(jnp.int32)

        def write(buf, val, s):
            return jax.lax.dynamic_update_slice_in_dim(buf, val, s, axis=0)

        cache = {
            "k": jax.vmap(write)(cache["k"], k_new, slot),
            "v": jax.vmap(write)(cache["v"], v_new, slot),
        }

    # grouped-query form — NO repeat-kv: repeating would reshard the
    # (B, S, kv, hd) cache from sequence-sharded to head-sharded, i.e.
    # all-gather the whole KV cache across the model axis every token
    # (measured 2 x 1.07 GB/device/layer on deepseek-67b). The grouped
    # einsums contract against the sharded cache in place; only (B,kv,g)
    # softmax stats and the (B,kv,g,hd) output cross the wire.
    kv_heads = cache["k"].shape[2]
    g = h // kv_heads
    qg = q.reshape(B, kv_heads, g, cfg.head_dim_)      # (B,kv,g,hd)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, cache["k"]).astype(jnp.float32)
    logits = logits * (cfg.head_dim_ ** -0.5)
    if not cross:
        slots = jnp.arange(Sc)
        if window:
            valid = (slots[None, :] < pos[:, None]) | (pos[:, None] >= Sc)
            if start is not None:
                # absolute position held by ring-buffer slot s
                abs_pos = pos[:, None] - ((pos[:, None] - slots[None, :]) % Sc)
                valid &= abs_pos >= start[:, None]
        else:
            valid = slots[None, :] <= pos[:, None]
            if start is not None:
                valid &= slots[None, :] >= start[:, None]
        logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(cache["v"].dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", w, cache["v"])
    out = out.reshape(B, 1, h, cfg.head_dim_)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache


# =================================================================== MLA
def init_mla(cfg, key) -> Params:
    """MLA weights; with ``q_lora_rank`` 0 (DeepSeek-V2-Lite) the query is
    one projection ``w_q`` with no latent and no ``q_norm``."""
    d, h = cfg.d_model, cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vh = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    dt = cfg.param_dtype
    ks = jax.random.split(key, 6)
    p = {
        "w_dkv": normal_init(ks[2], (d, kvr + rope), dt, d ** -0.5),
        "w_uk": normal_init(ks[3], (kvr, h, nope), dt, kvr ** -0.5),
        "w_uv": normal_init(ks[4], (kvr, h, vh), dt, kvr ** -0.5),
        "wo": normal_init(ks[5], (h, vh, d), dt, (h * vh) ** -0.5),
        "kv_norm": jnp.ones((kvr,), jnp.float32),
    }
    if qr:
        p["w_dq"] = normal_init(ks[0], (d, qr), dt, d ** -0.5)
        p["w_uq"] = normal_init(ks[1], (qr, h, nope + rope), dt, qr ** -0.5)
        p["q_norm"] = jnp.ones((qr,), jnp.float32)
    else:
        p["w_q"] = normal_init(ks[0], (d, h, nope + rope), dt, d ** -0.5)
    return p


def _mla_rope(cfg, positions):
    """cos/sin of the rope dims: YaRN's when the config scales rope."""
    if cfg.rope_scaling:
        return yarn_angles(cfg, positions, cfg.qk_rope_dim)
    return rope_angles(positions, cfg.qk_rope_dim, cfg.rope_theta)


def mla_softmax_scale(cfg) -> float:
    """(nope + rope) ** -0.5, times mscale(f, mscale_all_dim) ** 2 under
    YaRN, as the published attention sets ``softmax_scale``."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    rs = cfg.yarn
    if rs and rs.get("mscale_all_dim"):
        scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _mla_q(cfg, p, x, positions):
    from .common import rmsnorm
    nope = cfg.qk_nope_dim
    if cfg.q_lora_rank:
        q_lat = rmsnorm({"scale": p["q_norm"]},
                        jnp.einsum("bsd,dr->bsr", x, p["w_dq"]), cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", q_lat, p["w_uq"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    cos, sin = _mla_rope(cfg, positions)
    q_rope = apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def _mla_latent(cfg, p, x, positions):
    from .common import rmsnorm
    kvr = cfg.kv_lora_rank
    lat = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    ckv = rmsnorm({"scale": p["kv_norm"]}, lat[..., :kvr], cfg.norm_eps)
    k_rope = lat[..., kvr:][:, :, None, :]  # single shared rope head
    cos, sin = _mla_rope(cfg, positions)
    k_rope = apply_rope(k_rope, cos, sin)[:, :, 0, :]
    return ckv, k_rope


def mla_forward(cfg, p: Params, x: jax.Array, positions: jax.Array,
                k_valid: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Train/prefill MLA with naive (expanded) K/V; latent cache returned."""
    B, S, _ = x.shape
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    ckv, k_rope = _mla_latent(cfg, p, x, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uv"])
    h = cfg.n_heads
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :], (B, S, h, cfg.qk_rope_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    scale = mla_softmax_scale(cfg)

    def unfused(q, k, v):
        attend = chunked_sdpa if S > CHUNK_THRESHOLD else sdpa
        return attend(q, k, v, positions, positions, causal=True,
                      k_valid=k_valid, scale=scale)

    fused = fused_attention_applies(q, k, causal=True, k_valid=k_valid)
    out = _attend(fused, unfused, q, k, v, scale=scale)
    cache = {"ckv": ckv, "k_rope": k_rope}
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache


def mla_decode(cfg, p: Params, x: jax.Array, cache: Dict[str, jax.Array],
               pos: jax.Array, start: Optional[jax.Array] = None,
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Weight-absorbed MLA decode: attention runs in the latent space.

    score(t) = q_nope^T W_uk ckv_t + q_rope . k_rope_t
    out      = (sum_t w_t ckv_t) W_uv

    start (B,): first real cache slot per row (see gqa_decode).
    """
    B = x.shape[0]
    Sc = cache["ckv"].shape[1]
    rpos = pos if start is None else pos - start
    q_nope, q_rope = _mla_q(cfg, p, x, rpos[:, None])
    ckv_new, k_rope_new = _mla_latent(cfg, p, x, rpos[:, None])
    slot = (pos % Sc).astype(jnp.int32)

    def write(buf, val, s):
        return jax.lax.dynamic_update_slice_in_dim(buf, val, s, axis=0)

    cache = {
        "ckv": jax.vmap(write)(cache["ckv"], ckv_new, slot),
        "k_rope": jax.vmap(write)(cache["k_rope"], k_rope_new, slot),
    }
    # absorb: q_lat (B,1,h,kvr)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"])
    logits = jnp.einsum("bshr,btr->bhst", q_lat, cache["ckv"]).astype(jnp.float32)
    logits += jnp.einsum("bshk,btk->bhst", q_rope, cache["k_rope"]).astype(jnp.float32)
    logits *= mla_softmax_scale(cfg)
    valid = jnp.arange(Sc)[None, :] <= pos[:, None]
    if start is not None:
        valid &= jnp.arange(Sc)[None, :] >= start[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    o_lat = jnp.einsum("bhst,btr->bshr", w.astype(cache["ckv"].dtype), cache["ckv"])
    out = jnp.einsum("bshr,rhk->bshk", o_lat, p["w_uv"])
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache
