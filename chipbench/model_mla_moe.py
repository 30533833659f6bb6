"""The DeepSeek-V2 (MLA + MoE) configuration as the benchmark states it:
its parameter layout, the weights made from the seed, and the program's
ModelConfig, for a chip that holds a share of each MoE layer's experts.

The layout is the benchmark's own statement of the tree the program
trains: the leading dense layers stacked under ``segments[0]``, the MoE
layers under ``segments[1]``, the held experts' weights (n_held, d, f)
beside a router over every expert, the shared experts as one wide
SwiGLU, norm scales and the router in f32, the embedding and head padded
to a multiple of 256 rows (the program masks the padding ids' logits).
``check_layout`` compares it with the program's ``jax.eval_shape`` tree
before a run.  Weights are
drawn per leaf from the seed at the program's own init scales, in one
jitted call, with the leaf keys of ``chipbench.model``.
"""
from __future__ import annotations

from typing import Any, Dict

from chipbench.model import VOCAB_ALIGN, _is_spec, _leaf, leaf_key, path_str

EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def sizes(cj: Dict[str, Any]) -> Dict[str, int]:
    if cj.get("q_lora_rank"):
        raise ValueError("this layout has one query projection (q_lora_rank "
                         "null)")
    k0 = cj["first_k_dense_replace"]
    return {"L0": k0, "L1": cj["num_hidden_layers"] - k0,
            "d": cj["hidden_size"], "h": cj["num_attention_heads"],
            "nope": cj["qk_nope_head_dim"], "rope": cj["qk_rope_head_dim"],
            "vh": cj["v_head_dim"], "kvr": cj["kv_lora_rank"],
            "ff": cj["intermediate_size"], "fe": cj["moe_intermediate_size"],
            "fs": cj["n_shared_experts"] * cj["moe_intermediate_size"],
            "E": cj["router_experts"], "n": cj["n_routed_experts"],
            "k": cj["num_experts_per_tok"], "V": cj["vocab_size"],
            "Vp": -(-cj["vocab_size"] // VOCAB_ALIGN) * VOCAB_ALIGN}


def _attn(L: int, s: Dict[str, int], dt: str) -> Dict[str, Any]:
    d, h, kvr, vh = s["d"], s["h"], s["kvr"], s["vh"]
    return {"w_q": ((L, d, h, s["nope"] + s["rope"]), dt, d ** -0.5),
            "w_dkv": ((L, d, kvr + s["rope"]), dt, d ** -0.5),
            "w_uk": ((L, kvr, h, s["nope"]), dt, kvr ** -0.5),
            "w_uv": ((L, kvr, h, vh), dt, kvr ** -0.5),
            "wo": ((L, h, vh, d), dt, (h * vh) ** -0.5),
            "kv_norm": ((L, kvr), "float32", "ones")}


def _mlp(L: int, d: int, ff: int, dt: str) -> Dict[str, Any]:
    return {"w_gate": ((L, d, ff), dt, d ** -0.5),
            "w_up": ((L, d, ff), dt, d ** -0.5),
            "w_down": ((L, ff, d), dt, ff ** -0.5)}


def layout(cj: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf -> (shape, dtype, init): init is 'ones' or a normal's std."""
    s = sizes(cj)
    d, dt = s["d"], cj["torch_dtype"]
    L0, L1, n, fe = s["L0"], s["L1"], s["n"], s["fe"]

    def norms(L):
        return {"ln1": {"scale": ((L, d), "float32", "ones")},
                "ln2": {"scale": ((L, d), "float32", "ones")}}
    dense = dict(norms(L0), attn=_attn(L0, s, dt), mlp=_mlp(L0, d, s["ff"], dt))
    moe = dict(norms(L1), attn=_attn(L1, s, dt), moe={
        "router": ((L1, d, s["E"]), "float32", d ** -0.5),
        "w_gate": ((L1, n, d, fe), dt, d ** -0.5),
        "w_up": ((L1, n, d, fe), dt, d ** -0.5),
        "w_down": ((L1, n, fe, d), dt, fe ** -0.5),
        "shared": _mlp(L1, d, s["fs"], dt)})
    out = {"embed": ((s["Vp"], d), dt, 0.02),
           "final_norm": {"scale": ((d,), "float32", "ones")},
           "segments": [dense, moe]}
    if not cj["tie_word_embeddings"]:
        out["lm_head"] = ((s["Vp"], d), dt, d ** -0.5)
    return out


def make_params(cj: Dict[str, Any], k):
    """Every leaf of ``layout(cj)`` drawn from ``k`` (traced: call it
    inside one jit)."""
    import jax
    return jax.tree_util.tree_map_with_path(
        lambda p, spec: _leaf(leaf_key(k, p), spec), layout(cj),
        is_leaf=_is_spec)


def make_leaf(cj: Dict[str, Any], k, path: str):
    """One leaf of ``make_params`` alone, by its path string."""
    import jax
    found = {}

    def visit(p, spec):
        if path_str(p) == path:
            found["x"] = _leaf(leaf_key(k, p), spec)
        return None
    jax.tree_util.tree_map_with_path(visit, layout(cj), is_leaf=_is_spec)
    return found["x"]


def shape_tree(cj: Dict[str, Any]):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s[0], jnp.dtype(s[1])),
                        layout(cj), is_leaf=_is_spec)


def check_layout(cj: Dict[str, Any], program_shapes) -> None:
    """Raise unless the program's parameter tree is ``layout(cj)``."""
    import jax
    ours = jax.tree_util.tree_flatten_with_path(shape_tree(cj))[0]
    theirs = jax.tree_util.tree_flatten_with_path(program_shapes)[0]
    a = {path_str(p): (tuple(x.shape), str(x.dtype)) for p, x in ours}
    b = {path_str(p): (tuple(x.shape), str(x.dtype)) for p, x in theirs}
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))
        raise ValueError(f"the program's parameter tree is not the "
                         f"benchmark's layout: {diff[:6]}")


def slice_norms(tree) -> Dict[str, Any]:
    """Norm of every layer's slice of each stacked leaf, and of every
    held expert's slice of the expert leaves (``<path>/<layer>`` and
    ``<path>/<layer>/<expert>``); unstacked leaves whole.  Returns device
    scalars and vectors; ``host_norms`` makes floats of them."""
    import jax
    import jax.numpy as jnp
    out = {}
    for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        pth = path_str(p)
        lead = 0
        if pth.startswith("segments/"):
            lead = 2 if pth.rsplit("/", 1)[-1] in EXPERT_LEAVES and \
                "/moe/" in pth and "/shared/" not in pth else 1
        x = x.astype(jnp.float32)
        out[pth] = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(lead, x.ndim))))
    return out


def host_norms(norms: Dict[str, Any]) -> Dict[str, float]:
    """``slice_norms`` as {key: float}, one key per slice."""
    import numpy as np
    out = {}
    for pth, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            out[pth] = float(v)
        for idx in np.ndindex(*v.shape):
            out["/".join([pth] + [str(i) for i in idx])] = float(v[idx])
    return out


def program_config(cj: Dict[str, Any]):
    """The program's ModelConfig for this configuration file."""
    from repro.models.config import ModelConfig
    if cj.get("hidden_act") != "silu" or cj.get("attention_bias"):
        raise ValueError("the program runs SwiGLU blocks without biases")
    if cj.get("scoring_func") != "softmax" or cj.get("topk_method") != "greedy":
        raise ValueError("the program routes by greedy top-k over a softmax")
    if cj.get("moe_layer_freq") != 1 or not cj.get("seq_aux"):
        raise ValueError("the program puts experts in every layer after the "
                         "dense ones and balances them per sequence")
    s = sizes(cj)
    return ModelConfig(
        name=cj["name"], family="moe", n_layers=s["L0"] + s["L1"],
        d_model=s["d"], n_heads=s["h"], n_kv_heads=s["h"],
        head_dim=s["nope"] + s["rope"], d_ff=s["fe"], vocab_size=s["V"],
        moe_n_routed=s["E"], moe_n_shared=cj["n_shared_experts"],
        moe_top_k=s["k"], moe_d_ff=s["fe"], moe_first_k_dense=s["L0"],
        dense_d_ff=s["ff"], moe_norm_topk=bool(cj["norm_topk_prob"]),
        moe_routed_scale=float(cj["routed_scaling_factor"]),
        moe_experts_held=s["n"], moe_first_expert=int(cj["first_expert"]),
        moe_aux_coef=float(cj["aux_loss_alpha"]), moe_seq_aux=True,
        use_mla=True, q_lora_rank=0, kv_lora_rank=s["kvr"],
        qk_nope_dim=s["nope"], qk_rope_dim=s["rope"], v_head_dim=s["vh"],
        rope_scaling=tuple(sorted(cj["rope_scaling"].items())),
        dtype=cj["torch_dtype"], rope_theta=float(cj["rope_theta"]),
        norm_eps=float(cj["rms_norm_eps"]),
        tie_embeddings=bool(cj["tie_word_embeddings"]))
