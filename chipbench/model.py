"""The dense GQA configuration as the benchmark states it: its parameter
layout, the weights made from the seed, and the program's ModelConfig.

The layout is the benchmark's own statement of the tree the program
trains (stacked layers under ``segments[0]``, the embedding and head
padded to a multiple of 256 rows, norm scales in f32).  ``check_layout``
compares it with the program's ``jax.eval_shape`` tree before a run, so a
program that changes its tree stops the run instead of being compared
with something else.  Weights are drawn per leaf from the seed at the
program's own init scales, in one jitted call.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict

VOCAB_ALIGN = 256


def sizes(cj: Dict[str, Any]) -> Dict[str, int]:
    d, h = cj["hidden_size"], cj["num_attention_heads"]
    v = cj["vocab_size"]
    return {"L": cj["num_hidden_layers"], "d": d, "h": h,
            "kv": cj["num_key_value_heads"], "hd": d // h,
            "ff": cj["intermediate_size"], "V": v,
            "Vp": -(-v // VOCAB_ALIGN) * VOCAB_ALIGN}


def layout(cj: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf -> (shape, dtype, init): init is 'ones' or a normal's std."""
    s = sizes(cj)
    L, d, h, kv, hd, ff, Vp = (s[k] for k in ("L", "d", "h", "kv", "hd",
                                              "ff", "Vp"))
    dt = cj["torch_dtype"]
    layer = {
        "ln1": {"scale": ((L, d), "float32", "ones")},
        "attn": {"wq": ((L, d, h, hd), dt, d ** -0.5),
                 "wk": ((L, d, kv, hd), dt, d ** -0.5),
                 "wv": ((L, d, kv, hd), dt, d ** -0.5),
                 "wo": ((L, h, hd, d), dt, (h * hd) ** -0.5)},
        "ln2": {"scale": ((L, d), "float32", "ones")},
        "mlp": {"w_gate": ((L, d, ff), dt, d ** -0.5),
                "w_up": ((L, d, ff), dt, d ** -0.5),
                "w_down": ((L, ff, d), dt, ff ** -0.5)},
    }
    out = {"embed": ((Vp, d), dt, 0.02),
           "final_norm": {"scale": ((d,), "float32", "ones")},
           "segments": [layer]}
    if not cj["tie_word_embeddings"]:
        out["lm_head"] = ((Vp, d), dt, d ** -0.5)
    return out


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _leaf(k, spec):
    import jax
    import jax.numpy as jnp
    shape, dtype, init = spec
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    return (jax.random.normal(k, shape, jnp.float32) * init).astype(dtype)


def leaf_key(k, path):
    import jax
    return jax.random.fold_in(k, zlib.crc32(path_str(path).encode()) & 0x7FFFFFFF)


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


def program_config(cj: Dict[str, Any]):
    """The program's ModelConfig for this configuration file."""
    from repro.models.config import ModelConfig
    if cj.get("hidden_act") != "silu" or cj.get("bias"):
        raise ValueError("the program runs SwiGLU blocks without biases")
    s = sizes(cj)
    return ModelConfig(
        name=cj["name"], family="dense", n_layers=s["L"], d_model=s["d"],
        n_heads=s["h"], n_kv_heads=s["kv"], head_dim=s["hd"], d_ff=s["ff"],
        vocab_size=s["V"], dtype=cj["torch_dtype"],
        rope_theta=float(cj["rope_theta"]), norm_eps=float(cj["rms_norm_eps"]),
        tie_embeddings=bool(cj["tie_word_embeddings"]))
