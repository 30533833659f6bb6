"""Plain float32 reference of one chip's share of the DeepSeek-V2 (MLA +
MoE) training step, for the configurations ``chipbench/model_mla_moe.py``
lays out (DeepSeek-V2-Lite cut to 8 of 64 experts).

The model, as published (arXiv:2405.04434; HF ``modeling_deepseek.py``):
pre-norm RMSNorm blocks; MLA with one query projection d -> h x (nope +
rope), a latent kv projection d -> kv_lora + rope, RMSNorm on the latent,
keys and values expanded from it, one rope key shared by the heads; YaRN
rope (the blended frequencies and the cos/sin factor of
``DeepseekV2YarnRotaryEmbedding``) and the softmax scale (nope + rope) **
-0.5 x mscale(factor, mscale_all_dim) ** 2; a dense SwiGLU layer first,
then MoE layers: a softmax router over every expert, greedy top-k
(``lax.top_k``), weights not renormalised unless the config says so and
times ``routed_scaling_factor``, shared experts as one wide SwiGLU; an
untied head, mean next-token cross-entropy plus ``aux_loss_alpha`` x the
sequence-wise expert balance loss (§2.2.3: per sequence, f_i = E / (S k)
x the pairs routed to expert i, P_i = the mean router probability, the
sum of f_i P_i averaged over the batch and summed over layers).  AdamW
with global-norm clipping and decoupled weight decay on every leaf, as
``dense_gqa.py``.  It imports nothing of the program: the weights come
from ``chipbench.model_mla_moe`` with the seed, the batches from
``chipbench.data``.

Departures from the published model, each the benchmark's cut or the
program's stated rule (the configuration file lists them):

* The chip's share: only experts ``first_expert`` .. ``first_expert + n
  - 1`` exist here; what the other experts would add to a token is left
  out (the router still scores all of them and picks its top-k among
  them).  The vocabulary is the configuration's slice.
* Capacity: each held expert keeps the first ``cap`` = ceil(1.25 x T k /
  E), rounded up to 8, of its (token, expert) pairs in token order over
  the step's whole batch of T tokens and drops the rest; written plainly
  as a per-expert running count carried from sequence to sequence.
* Rope rotates halves of the stored columns (the published code first
  de-interleaves them; with random weights a fixed permutation).

Every matmul runs at ``Precision.HIGHEST`` in f32; each held expert runs
on every token of a sequence and its output is weighted by the combine
weight (zero where the token is not routed to it or was dropped).
Gradients accumulate one sequence at a time, layers are rematerialized
and attention is taken in query blocks, so the step fits one chip beside
the f32 parameters and the accumulated gradient; AdamW's m and v live on
the host.

``quant="fp8"`` is the control: every matmul operand rounded to
float8_e4m3 with a per-tensor scale.  ``fault`` plants a fault the checks
must catch: ``"renorm"`` renormalises the top-k weights, ``"no_mscale"``
leaves YaRN's mscale out of the softmax scale, ``"drop_expert"`` drops
the output of the first held expert in every MoE layer.  The limits of
the comparison (``PERF.md``) sit between the program's largest gap over
seeds and the smallest gap of these faults and of half the batch left
out.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench.reference.dense_gqa import _adam_leaf, _mm, _rms, warmup_cosine

CE_CHUNK = 512
Q_BLOCK = 512
FAULTS = (None, "renorm", "no_mscale", "drop_expert")


def capacity(tokens: int, cj: Dict[str, Any]) -> int:
    """Pairs each held expert keeps in a step of ``tokens`` tokens."""
    e, k = cj["router_experts"], cj["num_experts_per_tok"]
    cap = math.ceil(1.25 * tokens * k / e)
    return max(8, -(-cap // 8) * 8)


def _mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_tables(cj: Dict[str, Any], S: int):
    """cos, sin (S, rope/2) of YaRN at positions 0..S-1, each times the
    cos/sin factor, in float64 arithmetic."""
    rs, dim, base = cj["rope_scaling"], cj["qk_rope_head_dim"], cj["rope_theta"]
    f, orig = rs["factor"], rs["original_max_position_embeddings"]
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / f

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv_freq = inter * ramp + extra * (1.0 - ramp)
    m = _mscale(f, rs["mscale"]) / _mscale(f, rs["mscale_all_dim"])
    ang = np.arange(S)[:, None] * inv_freq[None, :]
    return ((np.cos(ang) * m).astype(np.float32),
            (np.sin(ang) * m).astype(np.float32))


def softmax_scale(cj: Dict[str, Any], fault: Optional[str] = None) -> float:
    rs = cj["rope_scaling"]
    s = (cj["qk_nope_head_dim"] + cj["qk_rope_head_dim"]) ** -0.5
    if rs.get("mscale_all_dim") and fault != "no_mscale":
        s *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def _rope(x, cos, sin):
    """x (S, ..., r): rotate-half rope; cos/sin (S, r/2)."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    c = cos.reshape(cos.shape[:1] + (1,) * (x.ndim - 2) + cos.shape[1:])
    s = sin.reshape(c.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def knobs(cj: Dict[str, Any], fault: Optional[str], tokens: int,
          n_seq: int) -> Dict[str, Any]:
    """What a fault or the batch changes, as traced values, so one
    compiled step serves the reference, every fault and half the batch:
    the top-k renormalisation (0 or 1), the softmax scale, a keep mask
    over the held experts, the step's token and sequence counts and the
    capacity."""
    import jax.numpy as jnp
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    keep = np.ones((cj["n_routed_experts"],), np.float32)
    if fault == "drop_expert":
        keep[0] = 0.0
    return {"renorm": jnp.float32(bool(cj["norm_topk_prob"])
                                  or fault == "renorm"),
            "scale": jnp.float32(softmax_scale(cj, fault)),
            "keep": jnp.asarray(keep), "n_tok": jnp.float32(tokens),
            "n_seq": jnp.float32(n_seq),
            "cap": jnp.int32(capacity(tokens, cj))}


def _attn(h, ap, cj, quant, kn, tables):
    import jax
    import jax.numpy as jnp
    nope, kvr = cj["qk_nope_head_dim"], cj["kv_lora_rank"]
    cos, sin = tables
    S = h.shape[0]
    q = _mm("sd,dhk->shk", h, ap["w_q"], quant)
    qn, qr = q[..., :nope], _rope(q[..., nope:], cos, sin)
    lat = _mm("sd,dr->sr", h, ap["w_dkv"], quant)
    ckv = _rms(lat[:, :kvr], ap["kv_norm"], cj["rms_norm_eps"])
    kr = _rope(lat[:, kvr:], cos, sin)                    # (S, rope), shared
    k_nope = _mm("sr,rhk->shk", ckv, ap["w_uk"], quant)
    v = _mm("sr,rhk->shk", ckv, ap["w_uv"], quant)
    scale = kn["scale"]
    nb = S // Q_BLOCK if S % Q_BLOCK == 0 else 1
    qb = S // nb

    @jax.checkpoint
    def block(i):
        sl = lambda t: jax.lax.dynamic_slice_in_dim(t, i * qb, qb, 0)
        s = (_mm("qhk,thk->hqt", sl(qn), k_nope, quant)
             + _mm("qhk,tk->hqt", sl(qr), kr, quant)) * scale
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        return _mm("hqt,thv->qhv", jax.nn.softmax(s, axis=-1), v, quant)

    o = jax.lax.map(block, jnp.arange(nb)).reshape(S, *v.shape[1:])
    return _mm("shv,hvd->sd", o, ap["wo"], quant)


def _swiglu(h, mp, quant):
    import jax
    g = _mm("sd,df->sf", h, mp["w_gate"], quant)
    u = _mm("sd,df->sf", h, mp["w_up"], quant)
    return _mm("sf,fd->sd", jax.nn.silu(g) * u, mp["w_down"], quant)


def _moe(h, mp, cj, quant, kn, off):
    """One sequence's MoE output, its balance loss and the pairs it routed
    to each held expert and of those the pairs kept (2, n); ``off`` counts
    the pairs routed by earlier sequences."""
    import jax
    import jax.numpy as jnp
    S = h.shape[0]
    E, k = cj["router_experts"], cj["num_experts_per_tok"]
    n, first = cj["n_routed_experts"], cj["first_expert"]
    probs = jax.nn.softmax(_mm("sd,de->se", h, mp["router"], quant), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = jnp.where(kn["renorm"] > 0,
                      top_p / jnp.sum(top_p, axis=-1, keepdims=True), top_p)
    top_p = top_p * cj["routed_scaling_factor"]
    to_e = (top_i[..., None] == first + jnp.arange(n)).astype(jnp.int32)
    flat = to_e.reshape(S * k, n)
    before = jnp.cumsum(flat, axis=0) - flat      # earlier pairs, same expert
    kept = flat * ((off[None, :] + before) < kn["cap"])
    combine = jnp.sum(kept.reshape(S, k, n) * top_p[..., None], axis=1)
    combine = combine * kn["keep"]
    g = _mm("sd,edf->esf", h, mp["w_gate"], quant)
    u = _mm("sd,edf->esf", h, mp["w_up"], quant)
    y = _mm("esf,efd->esd", jax.nn.silu(g) * u, mp["w_down"], quant)
    out = _mm("se,esd->sd", combine, y, quant) + _swiglu(h, mp["shared"], quant)
    f = jnp.zeros((E,)).at[top_i.reshape(-1)].add(1.0) * (E / (S * k))
    aux = jnp.sum(f * jnp.mean(probs, axis=0))
    return out, aux, jnp.stack([jnp.sum(flat, axis=0), jnp.sum(kept, axis=0)])


def hidden(params, seq, off, cj, quant, kn):
    """One sequence through the layers: the final normed hidden states
    (S, d), each MoE layer's balance loss (L,), and the pairs it routed to
    each held expert in each MoE layer and of those the pairs kept,
    (L, 2, n)."""
    import jax
    import jax.numpy as jnp
    eps = cj["rms_norm_eps"]
    S = seq["tokens"].shape[0]
    tables = tuple(jnp.asarray(t) for t in yarn_tables(cj, S))
    x = params["embed"][seq["tokens"]]

    def dense_layer(c, lp):
        c = c + _attn(_rms(c, lp["ln1"]["scale"], eps), lp["attn"], cj,
                      quant, kn, tables)
        return c + _swiglu(_rms(c, lp["ln2"]["scale"], eps), lp["mlp"],
                           quant), None

    def moe_layer(c, inp):
        lp, o = inp
        c = c + _attn(_rms(c, lp["ln1"]["scale"], eps), lp["attn"], cj,
                      quant, kn, tables)
        out, aux, cnt = _moe(_rms(c, lp["ln2"]["scale"], eps), lp["moe"], cj,
                             quant, kn, o)
        return c + out, (aux, cnt)

    x, _ = jax.lax.scan(jax.checkpoint(dense_layer), x, params["segments"][0])
    x, (aux, cnt) = jax.lax.scan(jax.checkpoint(moe_layer), x,
                                 (params["segments"][1], off))
    return _rms(x, params["final_norm"]["scale"], eps), aux, cnt


def _head(params, cj):
    head = params["embed"] if cj["tie_word_embeddings"] else params["lm_head"]
    return head[:cj["vocab_size"]]


def logits(params, seq, off, cj, kn, quant: Optional[str] = None):
    """One sequence's logits (S, V) over the configuration's vocabulary,
    and its pairs as ``hidden`` counts them."""
    x, _, cnt = hidden(params, seq, off, cj, quant, kn)
    return _mm("sd,vd->sv", x, _head(params, cj), quant), cnt


def seq_loss(params, seq, off, cj, quant, kn):
    """One sequence's share of the step's loss: its summed cross-entropy
    over the batch's token count plus alpha x its balance losses over the
    batch's sequence count; and its pairs as ``hidden`` counts them."""
    import jax
    import jax.numpy as jnp
    x, aux, cnt = hidden(params, seq, off, cj, quant, kn)
    head = _head(params, cj)
    S = x.shape[0]
    n = S // CE_CHUNK if S % CE_CHUNK == 0 else 1

    @jax.checkpoint
    def chunk(a):
        xc, lc, mc = a
        lg = _mm("sd,vd->sv", xc, head, quant)
        gold = jnp.take_along_axis(lg, lc[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(lg, axis=-1) - gold) * mc)

    ce = jnp.sum(jax.lax.map(chunk, (x.reshape(n, S // n, -1),
                                     seq["labels"].reshape(n, S // n),
                                     seq["mask"].reshape(n, S // n))))
    return (ce / kn["n_tok"]
            + cj["aux_loss_alpha"] * jnp.sum(aux) / kn["n_seq"]), cnt


def _hashable(cj: Dict[str, Any]):
    out = []
    for k, v in sorted(cj.items()):
        if isinstance(v, dict):
            v = _hashable(v)
        if isinstance(v, (int, float, str, bool, tuple, type(None))):
            out.append((k, v))
    return tuple(out)


def _unhash(items):
    return {k: (_unhash(v) if isinstance(v, tuple) and v and
                isinstance(v[0], tuple) else v) for k, v in items}


@functools.lru_cache(maxsize=None)
def _seq_step(cj_items, quant):
    """jit: (params, acc, seq, off, knobs) -> (loss share, pairs,
    acc + grad)."""
    import jax
    cj = _unhash(cj_items)
    vg = jax.value_and_grad(functools.partial(seq_loss, cj=cj, quant=quant),
                            has_aux=True)

    def f(params, acc, seq, off, kn):
        (l, cnt), g = vg(params, seq, off, kn=kn)
        return l, cnt, jax.tree.map(lambda a, b: a + b, acc, g)
    return jax.jit(f, donate_argnums=(1,))


def f32_params(cj: Dict[str, Any], k):
    """The seed's weights (as the program stores them) in f32."""
    import jax
    import jax.numpy as jnp
    from chipbench.model_mla_moe import make_params
    return jax.jit(lambda kk: jax.tree.map(
        lambda x: x.astype(jnp.float32), make_params(cj, kk)))(k)


def train_steps(cj: Dict[str, Any], k, batches: List[Dict[str, Any]],
                opt: Dict[str, Any], *, quant: Optional[str] = None,
                fault: Optional[str] = None,
                batch_rows: Optional[slice] = None) -> Dict[str, Any]:
    """AdamW over ``batches`` from the weights of key ``k``.

    Returns each step's loss, the norms (``model_mla_moe.host_norms``
    keys) of the first clipped gradient and of the parameters' change
    over all the steps, and each step's pairs routed to the held experts
    and kept by them (summed over layers).  ``batch_rows`` keeps only those rows of every
    batch (a fault: part of the batch left out)."""
    import jax
    import jax.numpy as jnp
    from chipbench.model_mla_moe import host_norms, make_leaf, slice_norms
    from chipbench.model import path_str
    adam = _adam_leaf()
    fn = _seq_step(_hashable(cj), quant)
    params = f32_params(cj, k)
    flat0, treedef = jax.tree_util.tree_flatten_with_path(params)
    paths = [path_str(p) for p, _ in flat0]
    leaves = [x for _, x in flat0]
    del flat0, params
    m = [np.zeros(x.shape, np.float32) for x in leaves]
    v = [np.zeros(x.shape, np.float32) for x in leaves]
    L1 = cj["num_hidden_layers"] - cj["first_k_dense_replace"]
    n = cj["n_routed_experts"]
    losses, pairs, g_norms = [], [], None
    for step, batch in enumerate(batches):
        b = {name: np.asarray(x if batch_rows is None else x[batch_rows])
             for name, x in batch.items()}
        B, S = b["tokens"].shape
        kn = knobs(cj, fault, B * S, B)
        acc = [jnp.zeros(x.shape, jnp.float32) for x in leaves]
        off = jnp.zeros((L1, n), jnp.int32)
        lval = kept = 0.0
        for r in range(B):
            seq = {name: jnp.asarray(x[r]) for name, x in b.items()}
            l, cnt, acc = fn(jax.tree_util.tree_unflatten(treedef, leaves),
                             jax.tree_util.tree_unflatten(treedef, acc),
                             seq, off, kn)
            acc = jax.tree_util.tree_leaves(acc)
            off = off + cnt[:, 0]
            kept += float(jnp.sum(cnt[:, 1]))
            lval += float(l)
        gnorm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in acc)))
        scale = min(1.0, opt["clip_norm"] / max(gnorm, 1e-9))
        if step == 0:
            g_norms = host_norms(slice_norms(
                jax.tree_util.tree_unflatten(treedef, [g * scale for g in acc])))
        t = step + 1
        lr = opt["lr"] * warmup_cosine(step, opt["warmup_steps"],
                                       opt["total_steps"])
        hp = (opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
              1.0 - opt["b1"] ** t, 1.0 - opt["b2"] ** t)
        for i in range(len(leaves)):
            p, mi, vi = adam(leaves[i], acc[i], jnp.asarray(m[i]),
                             jnp.asarray(v[i]), scale, lr, *hp)
            m[i], v[i] = np.asarray(mi), np.asarray(vi)
            leaves[i], acc[i] = p, None
        losses.append(lval)
        pairs.append({"routed": float(jnp.sum(off)), "kept": kept})
    delta = [p - make_leaf(cj, k, pth).astype(jnp.float32)
             for pth, p in zip(paths, leaves)]
    d_norms = host_norms(slice_norms(
        jax.tree_util.tree_unflatten(treedef, delta)))
    return {"losses": losses, "g_norms": g_norms, "d_norms": d_norms,
            "pairs": pairs}
