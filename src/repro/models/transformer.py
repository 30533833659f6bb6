"""Unified transformer stack for the whole model zoo.

Every architecture is compiled as a sequence of **segments**: contiguous
runs of layers with identical block structure. Each segment is executed
with ``lax.scan`` over stacked per-layer parameters (small HLO, fast
compiles, natural remat boundary). Heterogeneous stacks (Hymba's
full-attention islands, DeepSeek-V2's leading dense layer) become
multiple segments instead of per-layer Python unrolling.

Block anatomy (pre-norm residual):
    x += attn(ln(x))            [if seg.attn]      (GQA or MLA)
    x += ssm(ln(x))             [if seg.ssm]       (parallel to attn for Hymba)
    x += cross_attn(ln(x), enc) [if seg.cross]
    x += ffn(ln(x))             [if seg.ffn]       (SwiGLU MLP or MoE)
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

from repro.models.config import ModelConfig
from repro.models.layers import attention as attn_lib
from repro.models.layers import common, mamba as mamba_lib, moe as moe_lib

REMAT_POLICIES = {
    None: None,
    # save the TP-collective outputs: backward skips recomputing the
    # attention/FFN output psums (4 instead of 6 residual-stream
    # collectives per layer); costs 3x saved activations per layer —
    # combine with sp=True activation sharding to stay in HBM.
    "save_tp_out": jax.checkpoint_policies.save_only_these_names("tp_out"),
}

Params = Dict[str, Any]


class Segment(NamedTuple):
    n_layers: int
    attn: Optional[str]     # 'gqa' | 'mla' | None
    ffn: Optional[str]      # 'mlp' | 'moe' | None
    ssm: bool
    window: int             # 0 = full attention
    cross: bool             # decoder cross-attention (enc-dec archs)
    causal: bool
    d_ff: int               # MLP width when ffn == 'mlp'


def build_segments(cfg: ModelConfig, *, role: str = "decoder") -> List[Segment]:
    if role == "encoder":
        return [Segment(cfg.n_encoder_layers, "gqa", "mlp", False, 0, False, False, cfg.d_ff)]
    if cfg.family == "ssm":
        return [Segment(cfg.n_layers, None, None, True, 0, False, True, 0)]
    if cfg.family == "hybrid":
        segs: List[Segment] = []
        full = set(cfg.full_attn_layers)
        i = 0
        while i < cfg.n_layers:
            w = 0 if i in full else cfg.sliding_window
            j = i
            while j < cfg.n_layers and (0 if j in full else cfg.sliding_window) == w:
                j += 1
            segs.append(Segment(j - i, "gqa", "mlp", True, w, False, True, cfg.d_ff))
            i = j
        return segs
    attn = "mla" if cfg.use_mla else "gqa"
    if cfg.family == "moe":
        segs = []
        if cfg.moe_first_k_dense:
            segs.append(Segment(cfg.moe_first_k_dense, attn, "mlp", False, 0, False, True,
                                cfg.dense_d_ff))
        segs.append(Segment(cfg.n_layers - cfg.moe_first_k_dense, attn, "moe", False, 0,
                            False, True, 0))
        return segs
    cross = cfg.is_encoder_decoder
    return [Segment(cfg.n_layers, attn, "mlp", False, 0, cross, True, cfg.d_ff)]


# ------------------------------------------------------------------ blocks
def init_block(cfg: ModelConfig, seg: Segment, key) -> Params:
    ks = jax.random.split(key, 5)
    p: Params = {"ln1": common.init_rmsnorm(cfg.d_model)}
    if seg.attn == "gqa":
        p["attn"] = attn_lib.init_gqa(cfg, ks[0])
    elif seg.attn == "mla":
        p["attn"] = attn_lib.init_mla(cfg, ks[0])
    if seg.ssm:
        p["ssm"] = mamba_lib.init_mamba(cfg, ks[1])
        if seg.attn:  # Hymba: parallel heads fused by normalized averaging
            p["ln_attn_out"] = common.init_rmsnorm(cfg.d_model)
            p["ln_ssm_out"] = common.init_rmsnorm(cfg.d_model)
    if seg.cross:
        p["cross"] = attn_lib.init_gqa(cfg, ks[2])
        p["ln_cross"] = common.init_rmsnorm(cfg.d_model)
    if seg.ffn:
        p["ln2"] = common.init_rmsnorm(cfg.d_model)
        if seg.ffn == "mlp":
            p["mlp"] = common.init_mlp(cfg, ks[3], seg.d_ff)
        else:
            p["moe"] = moe_lib.init_moe(cfg, ks[3])
    return p


def _mixer_forward(cfg, seg: Segment, p: Params, x, positions,
                   enc_kv=None, k_valid=None) -> Tuple[jax.Array, Dict[str, Any]]:
    """Token-mixing sublayer(s) on a full sequence; returns (dx, cache)."""
    h = common.rmsnorm(p["ln1"], x, cfg.norm_eps)
    cache: Dict[str, Any] = {}
    parts = []
    if seg.attn == "gqa":
        a, kv = attn_lib.gqa_forward(cfg, p["attn"], h, positions,
                                     causal=seg.causal, window=seg.window,
                                     k_valid=k_valid)
        cache.update(kv)
        parts.append(("attn", a))
    elif seg.attn == "mla":
        a, kv = attn_lib.mla_forward(cfg, p["attn"], h, positions,
                                     k_valid=k_valid)
        cache.update(kv)
        parts.append(("attn", a))
    if seg.ssm:
        s, sc = mamba_lib.mamba_forward(cfg, p["ssm"], h)
        cache.update(sc)
        parts.append(("ssm", s))
    if len(parts) == 2:  # Hymba fusion: mean of per-branch RMS-normed outputs
        a = common.rmsnorm(p["ln_attn_out"], parts[0][1], cfg.norm_eps)
        s = common.rmsnorm(p["ln_ssm_out"], parts[1][1], cfg.norm_eps)
        dx = 0.5 * (a + s)
    else:
        dx = parts[0][1]
    return dx, cache


def block_forward(cfg, seg: Segment, p: Params, x, positions, enc_out=None,
                  moe_groups: int = 1, moe_ep_axis=None, save_spec=None,
                  k_valid=None,
                  ) -> Tuple[jax.Array, Dict[str, Any], jax.Array,
                             Dict[str, jax.Array]]:
    """Full-sequence block. Returns (x, cache, moe_aux, moe counters);
    the counters are empty for a block without experts."""
    def _save(v):
        # values the save_tp_out remat policy keeps; optionally stored
        # sequence-sharded (save_spec) so 3x saved acts still fit HBM
        return checkpoint_name(_constrain(v, save_spec), "tp_out")

    aux = jnp.zeros((), jnp.float32)
    stats: Dict[str, jax.Array] = {}
    dx, cache = _mixer_forward(cfg, seg, p, x, positions, k_valid=k_valid)
    x = x + _save(dx)
    if seg.cross:
        h = common.rmsnorm(p["ln_cross"], x, cfg.norm_eps)
        k = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross"]["wv"])
        c, ckv = attn_lib.gqa_forward(cfg, p["cross"], h, positions,
                                      causal=False, kv_override=(k, v))
        cache["xk"], cache["xv"] = ckv["k"], ckv["v"]
        x = x + c
    if seg.ffn:
        h = common.rmsnorm(p["ln2"], x, cfg.norm_eps)
        if seg.ffn == "mlp":
            x = x + _save(common.mlp(p["mlp"], h))
        else:
            out, aux, stats = moe_lib.moe_forward(
                cfg, p["moe"], h, groups=moe_groups, ep_axis=moe_ep_axis)
            x = x + _save(out)
    return x, cache, aux, stats


def block_decode(cfg, seg: Segment, p: Params, x, cache: Dict[str, Any],
                 pos, moe_groups: int = 1, moe_ep_axis=None,
                 start=None) -> Tuple[jax.Array, Dict[str, Any]]:
    """Single-token block step. x: (B,1,d); pos: (B,); start: (B,) or None."""
    h = common.rmsnorm(p["ln1"], x, cfg.norm_eps)
    new_cache: Dict[str, Any] = {}
    parts = []
    if seg.attn == "gqa":
        a, kv = attn_lib.gqa_decode(cfg, p["attn"], h,
                                    {"k": cache["k"], "v": cache["v"]},
                                    pos, window=seg.window, start=start)
        new_cache.update(kv)
        parts.append(a)
    elif seg.attn == "mla":
        a, kv = attn_lib.mla_decode(cfg, p["attn"], h,
                                    {"ckv": cache["ckv"], "k_rope": cache["k_rope"]},
                                    pos, start=start)
        new_cache.update(kv)
        parts.append(a)
    if seg.ssm:
        s, sc = mamba_lib.mamba_decode(cfg, p["ssm"], h,
                                       {"conv": cache["conv"], "h": cache["h"]})
        new_cache.update(sc)
        parts.append(s)
    if len(parts) == 2:
        a = common.rmsnorm(p["ln_attn_out"], parts[0], cfg.norm_eps)
        s = common.rmsnorm(p["ln_ssm_out"], parts[1], cfg.norm_eps)
        dx = 0.5 * (a + s)
    else:
        dx = parts[0]
    x = x + dx
    if seg.cross:
        h = common.rmsnorm(p["ln_cross"], x, cfg.norm_eps)
        c, _ = attn_lib.gqa_decode(cfg, p["cross"], h,
                                   {"k": cache["xk"], "v": cache["xv"]},
                                   pos, cross=True)
        new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]
        x = x + c
    if seg.ffn:
        h = common.rmsnorm(p["ln2"], x, cfg.norm_eps)
        if seg.ffn == "mlp":
            x = x + common.mlp(p["mlp"], h)
        else:
            out, _, _ = moe_lib.moe_forward(cfg, p["moe"], h, groups=moe_groups,
                                            ep_axis=moe_ep_axis)
            x = x + out
    return x, new_cache


# ------------------------------------------------------------------ model
def init_params(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = common.init_embedding(cfg, ks[0])
    p["final_norm"] = common.init_rmsnorm(cfg.d_model)

    def stack(segs, key):
        out = []
        for i, seg in enumerate(segs):
            lkeys = jax.random.split(jax.random.fold_in(key, i), seg.n_layers)
            out.append(jax.vmap(lambda k, s=seg: init_block(cfg, s, k))(lkeys))
        return out

    p["segments"] = stack(build_segments(cfg), ks[1])
    if cfg.is_encoder_decoder:
        p["enc_segments"] = stack(build_segments(cfg, role="encoder"), ks[2])
        p["enc_final_norm"] = common.init_rmsnorm(cfg.d_model)
    return p


def _constrain(x, act_spec):
    if act_spec is not None:
        x = jax.lax.with_sharding_constraint(x, act_spec)
    return x


def _grad_dtype_guard(x):
    """Identity; backward casts the residual cotangent to x.dtype.

    Without it f32 cotangents (born at the f32 CE/softmax boundaries)
    propagate down the whole residual stream, doubling the wire bytes of
    every TP backward psum (measured on qwen2-moe: ~2x on the two largest
    all-reduces). Standard bf16-activation-grads mixed-precision policy.
    """
    dtype = x.dtype

    @jax.custom_vjp
    def ident(y):
        return y

    ident.defvjp(lambda y: (y, None), lambda _, ct: (ct.astype(dtype),))
    return ident(x)


def _run_segments(cfg, segs, seg_params, x, positions, enc_out=None, *,
                  remat: bool = True, want_cache: bool = False,
                  act_spec=None, moe_groups: int = 1, moe_ep_axis=None,
                  remat_policy=None, save_spec=None, k_valid=None):
    """Scan each segment; returns (x, per-segment stacked caches, aux sum,
    MoE counters summed over layers)."""
    caches, aux_total = [], jnp.zeros((), jnp.float32)
    stats_total: Dict[str, jax.Array] = {}
    for seg, sp in zip(segs, seg_params):
        def body(carry, lp, seg=seg):
            # barrier: stops XLA from hoisting a convert of the *stacked*
            # saved-residual buffer out of the backward loop (which would
            # materialize a whole-model f32 activation copy)
            carry = jax.lax.optimization_barrier(carry)
            carry = _grad_dtype_guard(carry)
            y, cache, aux, stats = block_forward(
                cfg, seg, lp, carry, positions, enc_out, moe_groups,
                moe_ep_axis, save_spec, k_valid)
            y = _constrain(y, act_spec)
            if not want_cache:  # keep k/v tensors out of the jaxpr for training
                cache = {}
            return y, (cache, aux, stats)

        if remat:
            body = jax.checkpoint(body, prevent_cse=False,
                                  policy=REMAT_POLICIES.get(remat_policy))
        x, (cache, aux, stats) = jax.lax.scan(body, x, sp)
        caches.append(cache)
        aux_total = aux_total + aux.sum()
        for k, v in stats.items():
            stats_total[k] = stats_total.get(k, 0.0) + v.sum()
    return x, caches, aux_total, stats_total


def embed_inputs(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
    """Token + stub-frontend embedding -> (B, S, d)."""
    x = common.embed(params, batch["tokens"])
    if cfg.frontend == "vision":
        x = jnp.concatenate([batch["patch_embeds"].astype(x.dtype), x], axis=1)
    return x


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array], *,
            remat: bool = True, act_spec=None,
            moe_groups: int = 1, moe_ep_axis=None) -> Tuple[jax.Array, jax.Array]:
    """Full forward to logits. Returns (logits, moe_aux)."""
    segs = build_segments(cfg)
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_x = batch["frame_embeds"].astype(cfg.param_dtype)
        enc_pos = jnp.arange(enc_x.shape[1])
        enc_segs = build_segments(cfg, role="encoder")
        enc_out, _, _, _ = _run_segments(cfg, enc_segs, params["enc_segments"],
                                         enc_x, enc_pos, remat=remat,
                                         act_spec=act_spec)
        enc_out = common.rmsnorm(params["enc_final_norm"], enc_out, cfg.norm_eps)
    x = embed_inputs(cfg, params, batch)
    positions = jnp.arange(x.shape[1])
    x, _, aux, _ = _run_segments(cfg, segs, params["segments"], x, positions,
                                 enc_out, remat=remat, act_spec=act_spec,
                                 moe_groups=moe_groups, moe_ep_axis=moe_ep_axis)
    x = common.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return common.unembed(cfg, params, x), aux


LOSS_CHUNK = 512  # sequence-chunked CE above this length (memory-linear)


def _hidden_states(cfg, params, batch, *, remat, act_spec, moe_groups=1,
                   moe_ep_axis=None, remat_policy=None, save_spec=None):
    """Forward to final hidden states (pre-unembed), the aux loss and the
    MoE counters."""
    segs = build_segments(cfg)
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_x = batch["frame_embeds"].astype(cfg.param_dtype)
        enc_segs = build_segments(cfg, role="encoder")
        enc_out, _, _, _ = _run_segments(cfg, enc_segs, params["enc_segments"],
                                         enc_x, jnp.arange(enc_x.shape[1]),
                                         remat=remat, act_spec=act_spec)
        enc_out = common.rmsnorm(params["enc_final_norm"], enc_out, cfg.norm_eps)
    x = embed_inputs(cfg, params, batch)
    positions = jnp.arange(x.shape[1])
    x, _, aux, stats = _run_segments(
        cfg, segs, params["segments"], x, positions, enc_out, remat=remat,
        act_spec=act_spec, moe_groups=moe_groups, moe_ep_axis=moe_ep_axis,
        remat_policy=remat_policy, save_spec=save_spec)
    return common.rmsnorm(params["final_norm"], x, cfg.norm_eps), aux, stats


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            **kw) -> jax.Array:
    """Mean next-token cross-entropy plus the weighted MoE aux loss."""
    return loss_and_stats(cfg, params, batch, **kw)[0]


def loss_and_stats(cfg: ModelConfig, params: Params,
                   batch: Dict[str, jax.Array], *,
                   aux_coef: Optional[float] = None, remat: bool = True,
                   act_spec=None, moe_groups: int = 1, moe_ep_axis=None,
                   remat_policy=None, save_spec=None
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """(loss, MoE counters summed over layers; empty without experts).
    ``aux_coef`` defaults to the config's ``moe_aux_coef``."""
    if aux_coef is None:
        aux_coef = cfg.moe_aux_coef
    x, aux, stats = _hidden_states(cfg, params, batch, remat=remat, act_spec=act_spec,
                            moe_groups=moe_groups, moe_ep_axis=moe_ep_axis,
                            remat_policy=remat_policy, save_spec=save_spec)
    labels, mask = batch["labels"], batch["mask"].astype(jnp.float32)
    if cfg.frontend == "vision":  # frontend tokens carry no LM loss
        pad = x.shape[1] - labels.shape[1]
        x = x[:, pad:]
    S = labels.shape[1]
    if S > LOSS_CHUNK and S % LOSS_CHUNK == 0:
        # chunk the unembed+CE over the sequence: the (B, S, V) f32 logits
        # tensor never materializes; backward recomputes per chunk.
        nc = S // LOSS_CHUNK

        def split(t):
            return t.reshape(t.shape[0], nc, LOSS_CHUNK, *t.shape[2:]).swapaxes(0, 1)

        @jax.checkpoint
        def chunk_nll(carry, xs):
            xc, lc, mc = xs
            logits = common.unembed(cfg, params, xc)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
            return (carry[0] + jnp.sum((logz - gold) * mc),
                    carry[1] + jnp.sum(mc)), None

        (tot, cnt), _ = jax.lax.scan(
            chunk_nll, (jnp.zeros(()), jnp.zeros(())),
            (split(x), split(labels), split(mask)))
        nll = tot / jnp.maximum(cnt, 1.0)
    else:
        logits = common.unembed(cfg, params, x)
        nll = common.softmax_cross_entropy(logits, labels, mask)
    return nll + aux_coef * aux, stats


# ------------------------------------------------------------------ serving
def init_cache(cfg: ModelConfig, seg: Segment, n_layers: int, batch: int,
               max_seq: int, enc_len: int = 0) -> Dict[str, Any]:
    """Zeroed stacked decode cache for one segment."""
    dt = cfg.param_dtype
    S = min(max_seq, seg.window) if seg.window else max_seq
    c: Dict[str, Any] = {}
    if seg.attn == "gqa":
        kv = (n_layers, batch, S, cfg.n_kv_heads, cfg.head_dim_)
        c["k"] = jnp.zeros(kv, dt)
        c["v"] = jnp.zeros(kv, dt)
    elif seg.attn == "mla":
        c["ckv"] = jnp.zeros((n_layers, batch, S, cfg.kv_lora_rank), dt)
        c["k_rope"] = jnp.zeros((n_layers, batch, S, cfg.qk_rope_dim), dt)
    if seg.ssm:
        c["conv"] = jnp.zeros((n_layers, batch, cfg.ssm_d_conv - 1, cfg.ssm_d_inner), dt)
        c["h"] = jnp.zeros((n_layers, batch, cfg.ssm_d_inner, cfg.ssm_d_state), jnp.float32)
    if seg.cross:
        kv = (n_layers, batch, enc_len, cfg.n_kv_heads, cfg.head_dim_)
        c["xk"] = jnp.zeros(kv, dt)
        c["xv"] = jnp.zeros(kv, dt)
    return c


def init_caches(cfg: ModelConfig, batch: int, max_seq: int,
                enc_len: int = 0) -> List[Dict[str, Any]]:
    return [init_cache(cfg, seg, seg.n_layers, batch, max_seq, enc_len)
            for seg in build_segments(cfg)]


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            *, moe_groups: int = 1, moe_ep_axis=None,
            positions: Optional[jax.Array] = None,
            pad_mask: Optional[jax.Array] = None,
            ) -> Tuple[List[Dict[str, Any]], jax.Array]:
    """Run the full prompt; returns (caches, last-position logits).

    For left-padded (bucketed) prompts pass ``pad_mask`` — an (S,) bool
    that is False on pad slots, so they are never attended — and
    ``positions = arange(S) - n_pad`` so real tokens keep the RoPE
    positions they would have in the unpadded prompt. Together the two
    make a padded prefill bit-identical (masked keys contribute exactly
    zero softmax weight) to the unpadded one.
    """
    segs = build_segments(cfg)
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_x = batch["frame_embeds"].astype(cfg.param_dtype)
        enc_segs = build_segments(cfg, role="encoder")
        enc_out, _, _, _ = _run_segments(cfg, enc_segs, params["enc_segments"],
                                         enc_x, jnp.arange(enc_x.shape[1]),
                                         remat=False)
        enc_out = common.rmsnorm(params["enc_final_norm"], enc_out, cfg.norm_eps)
    x = embed_inputs(cfg, params, batch)
    if positions is None:
        positions = jnp.arange(x.shape[1])
    x, caches, _, _ = _run_segments(cfg, segs, params["segments"], x, positions,
                                    enc_out, remat=False, want_cache=True,
                                    moe_groups=moe_groups,
                                    moe_ep_axis=moe_ep_axis, k_valid=pad_mask)
    x = common.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = common.unembed(cfg, params, x[:, -1:, :])
    # prefill caches for windowed segments keep only the trailing window
    out_caches = []
    for seg, cache in zip(segs, caches):
        if seg.window and cache.get("k") is not None:
            W = seg.window
            S = cache["k"].shape[2]
            if S > W:
                # roll so ring-buffer slot (pos % W) lines up with storage
                sl = {k: v[:, :, S - W:] if k in ("k", "v") else v
                      for k, v in cache.items()}
                # slot of absolute position p is (p % W): index i in the
                # trailing-window slice holds p = S - W + i  ->  roll by S % W
                sl = {k: (jnp.roll(v, S % W, axis=2) if k in ("k", "v") else v)
                      for k, v in sl.items()}
                cache = sl
        out_caches.append(cache)
    return out_caches, logits


def decode_step(cfg: ModelConfig, params: Params, caches: List[Dict[str, Any]],
                tokens: jax.Array, pos: jax.Array, *, moe_groups: int = 1,
                moe_ep_axis=None, start: Optional[jax.Array] = None,
                ) -> Tuple[List[Dict[str, Any]], jax.Array]:
    """One decode step. tokens: (B,1) int32; pos: (B,) absolute positions.

    start (B,) marks the first real (non-pad) cache slot per row; pad
    slots below it are masked out and RoPE runs pad-relative.
    """
    segs = build_segments(cfg)
    x = common.embed(params, tokens)
    new_caches = []
    for seg, sp, cache in zip(segs, params["segments"], caches):
        def body(carry, xs, seg=seg):
            lp, lc = xs
            y, nc = block_decode(cfg, seg, lp, carry, lc, pos,
                                 moe_groups=moe_groups,
                                 moe_ep_axis=moe_ep_axis, start=start)
            return y, nc
        x, nc = jax.lax.scan(body, x, (sp, cache))
        new_caches.append(nc)
    x = common.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return new_caches, common.unembed(cfg, params, x)
