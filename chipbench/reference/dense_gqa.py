"""Plain float32 reference of the dense GQA decoder's training step.

Follows the published InternLM2 / Llama block (pre-norm RMSNorm, GQA
attention with rotate-half RoPE, SwiGLU MLP, untied head, mean token
cross-entropy) and AdamW with global-norm clipping and decoupled weight
decay on every leaf, the optimizer the traffic file states.  It imports
nothing of the program: the weights come from ``chipbench.model`` with
the seed, the batches from ``chipbench.data``.

Every matmul runs at ``Precision.HIGHEST`` in f32.  ``quant="fp8"`` is
the control: every matmul operand rounded to float8_e4m3 with a
per-tensor scale, cotangents left in f32.  Layers are rematerialized one at a time and the loss is
taken in sequence chunks, so the step fits one chip beside the f32
parameters and gradients; AdamW's m and v live on the host.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import numpy as np

CE_CHUNK = 512


def _q(x, quant: Optional[str]):
    """Round to float8_e4m3 with a per-tensor scale; the cotangent passes
    through unrounded (the backward's matmuls see the rounded operands)."""
    import jax
    import jax.numpy as jnp
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(r - x)


def _mm(spec: str, a, b, quant):
    import jax
    import jax.numpy as jnp
    return jnp.einsum(spec, _q(a, quant), _q(b, quant),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, S, H, hd): rotate-half RoPE at positions 0..S-1."""
    import jax.numpy as jnp
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = np.arange(S, dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, lp, cj, quant):
    import jax
    import jax.numpy as jnp
    eps, theta = cj["rms_norm_eps"], cj["rope_theta"]
    h_n, kv_n = cj["num_attention_heads"], cj["num_key_value_heads"]
    B, S, _ = x.shape
    h = _rms(x, lp["ln1"]["scale"], eps)
    q = _rope(_mm("bsd,dhk->bshk", h, lp["attn"]["wq"], quant), theta)
    k = _rope(_mm("bsd,dhk->bshk", h, lp["attn"]["wk"], quant), theta)
    v = _mm("bsd,dhk->bshk", h, lp["attn"]["wv"], quant)
    k = jnp.repeat(k, h_n // kv_n, axis=2)
    v = jnp.repeat(v, h_n // kv_n, axis=2)
    hd = q.shape[-1]
    s = _mm("bqhd,bkhd->bhqk", q, k, quant) * hd ** -0.5
    causal = np.tril(np.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqk,bkhd->bqhd", w, v, quant)
    x = x + _mm("bshk,hkd->bsd", o, lp["attn"]["wo"], quant)
    h = _rms(x, lp["ln2"]["scale"], eps)
    g = _mm("bsd,df->bsf", h, lp["mlp"]["w_gate"], quant)
    u = _mm("bsd,df->bsf", h, lp["mlp"]["w_up"], quant)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, lp["mlp"]["w_down"],
                   quant)


def loss(params, batch, cj, quant: Optional[str] = None):
    """Mean next-token cross-entropy over the published vocabulary."""
    import jax
    import jax.numpy as jnp
    V = cj["vocab_size"]
    x = params["embed"][batch["tokens"]]
    body = jax.checkpoint(lambda c, lp: (_layer(c, lp, cj, quant), None))
    x, _ = jax.lax.scan(body, x, params["segments"][0])
    x = _rms(x, params["final_norm"]["scale"], cj["rms_norm_eps"])
    head = (params["embed"] if cj["tie_word_embeddings"]
            else params["lm_head"])[:V]
    B, S, d = x.shape
    n = S // CE_CHUNK if S % CE_CHUNK == 0 else 1
    xs = x.reshape(B, n, S // n, d).swapaxes(0, 1)
    ls = batch["labels"].reshape(B, n, S // n).swapaxes(0, 1)
    ms = batch["mask"].reshape(B, n, S // n).swapaxes(0, 1)

    @jax.checkpoint
    def chunk(xc, lc, mc):
        logits = _mm("bsd,vd->bsv", xc, head, quant)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - gold) * mc)

    tot = jnp.sum(jax.lax.map(lambda a: chunk(*a), (xs, ls, ms)))
    return tot / jnp.maximum(jnp.sum(batch["mask"]), 1.0)


def warmup_cosine(step: int, warmup: int, total: int,
                  floor: float = 0.1) -> float:
    warm = min(step / max(warmup, 1), 1.0)
    frac = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return warm * (floor + (1.0 - floor) * 0.5 * (1.0 + np.cos(np.pi * frac)))


@functools.lru_cache(maxsize=None)
def _grad_fn(cj_items, quant):
    import jax
    cj = dict(cj_items)
    return jax.jit(jax.value_and_grad(
        functools.partial(loss, cj=cj, quant=quant)))


@functools.lru_cache(maxsize=None)
def _adam_leaf():
    import jax
    import jax.numpy as jnp

    def upd(p, g, m, v, scale, lr, b1, b2, eps, wd, bc1, bc2):
        g = g * scale
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        delta = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p
        return p - lr * delta, m, v
    return jax.jit(upd, donate_argnums=(0,))


def _hashable(cj: Dict[str, Any]):
    return tuple(sorted((k, v) for k, v in cj.items()
                        if isinstance(v, (int, float, str, bool))))


def f32_params(cj: Dict[str, Any], k):
    """The seed's weights (as the program stores them) in f32."""
    import jax
    import jax.numpy as jnp
    from chipbench.model import make_params
    return jax.jit(lambda kk: jax.tree.map(
        lambda x: x.astype(jnp.float32), make_params(cj, kk)))(k)


def train_steps(cj: Dict[str, Any], k, batches: List[Dict[str, Any]],
                opt: Dict[str, Any], *, quant: Optional[str] = None,
                batch_rows: Optional[slice] = None) -> Dict[str, Any]:
    """AdamW over ``batches`` from the weights of key ``k``.

    Returns each step's loss, the per-leaf norm of the first clipped
    gradient (what the optimizer gets) and the per-leaf norm of the
    parameters' change over all the steps.  ``batch_rows`` keeps only
    those rows of every batch (a fault: part of the batch left out)."""
    import jax
    import jax.numpy as jnp
    from chipbench.model import make_leaf, path_str
    grad_fn = _grad_fn(_hashable(cj), quant)
    adam = _adam_leaf()
    flat0, treedef = jax.tree_util.tree_flatten_with_path(f32_params(cj, k))
    paths = [path_str(p) for p, _ in flat0]
    leaves = [x for _, x in flat0]
    del flat0
    m = [np.zeros(x.shape, np.float32) for x in leaves]
    v = [np.zeros(x.shape, np.float32) for x in leaves]
    losses, g_norms = [], None
    for step, batch in enumerate(batches):
        b = {name: jnp.asarray(x if batch_rows is None else x[batch_rows])
             for name, x in batch.items()}
        lval, grads = grad_fn(jax.tree_util.tree_unflatten(treedef, leaves), b)
        gl = jax.tree_util.tree_leaves(grads)
        del grads
        gnorm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in gl)))
        scale = min(1.0, opt["clip_norm"] / max(gnorm, 1e-9))
        if step == 0:
            g_norms = {pth: float(jnp.linalg.norm(g.ravel())) * scale
                       for pth, g in zip(paths, gl)}
        t = step + 1
        lr = opt["lr"] * warmup_cosine(step, opt["warmup_steps"],
                                       opt["total_steps"])
        hp = (opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
              1.0 - opt["b1"] ** t, 1.0 - opt["b2"] ** t)
        for i in range(len(leaves)):
            p, mi, vi = adam(leaves[i], gl[i], jnp.asarray(m[i]),
                             jnp.asarray(v[i]), scale, lr, *hp)
            m[i], v[i] = np.asarray(mi), np.asarray(vi)
            leaves[i], gl[i] = p, None
        losses.append(float(lval))
    d_norms = {}
    for pth, p in zip(paths, leaves):
        p0 = make_leaf(cj, k, pth).astype(jnp.float32)
        d_norms[pth] = float(jnp.linalg.norm((p - p0).ravel()))
    return {"losses": losses, "g_norms": g_norms, "d_norms": d_norms}
