"""Operations and bytes the work needs, computed from shapes alone.

Model FLOPs count what a training step requires: 6 FLOPs per parameter
per token for every parameter that enters a matmul (the embedding lookup
does not), plus causal attention's score and value products (each query
attends to its own position and the ones before it: (S + 1) / 2 keys on
average), times 3 for the backward.  Recomputation does not count.

K-Means counts the unpadded shapes: distances in the dot form (2d
FLOPs a pair for p.c, one to add |c|^2, one to compare) and bytes of one
read of the points and centroids and one write of the assignment and
its distance; an iteration adds the centroid sums and counts (one add a
coordinate and one a point).
"""
from __future__ import annotations

from typing import Any, Dict

F32 = 4


def dense_params(cj: Dict[str, Any]) -> Dict[str, int]:
    """Parameters per leaf of the published model (no vocabulary
    padding), keyed like the program's tree."""
    d, h = cj["hidden_size"], cj["num_attention_heads"]
    kv, ff = cj["num_key_value_heads"], cj["intermediate_size"]
    L, V, hd = cj["num_hidden_layers"], cj["vocab_size"], d // h
    out = {"embed": V * d, "final_norm/scale": d,
           "segments/0/ln1/scale": L * d, "segments/0/ln2/scale": L * d,
           "segments/0/attn/wq": L * d * h * hd,
           "segments/0/attn/wk": L * d * kv * hd,
           "segments/0/attn/wv": L * d * kv * hd,
           "segments/0/attn/wo": L * h * hd * d,
           "segments/0/mlp/w_gate": L * d * ff,
           "segments/0/mlp/w_up": L * d * ff,
           "segments/0/mlp/w_down": L * ff * d}
    if not cj["tie_word_embeddings"]:
        out["lm_head"] = V * d
    return out


def matmul_params(cj: Dict[str, Any]) -> int:
    p = dense_params(cj)
    n = sum(v for k, v in p.items()
            if k.startswith("segments/0/attn") or k.startswith("segments/0/mlp"))
    return n + cj["vocab_size"] * cj["hidden_size"]     # the head (tied or not)


def train_flops_per_token(cj: Dict[str, Any], seq: int) -> float:
    L, h = cj["num_hidden_layers"], cj["num_attention_heads"]
    hd = cj["hidden_size"] // h
    attn_fwd = L * 2 * 2 * h * hd * (seq + 1) / 2      # QK^T and PV
    return 6.0 * matmul_params(cj) + 3.0 * attn_fwd


def kmeans_assign(n: int, k: int, d: int) -> Dict[str, float]:
    """FLOPs and bytes one assignment pass needs."""
    return {"flops": float(n * k * (2 * d + 2)),
            "bytes": float(F32 * (n * d + k * d) + n * (4 + F32))}


def kmeans_iter_flops(n: int, k: int, d: int) -> float:
    return kmeans_assign(n, k, d)["flops"] + float(n * (d + 1))


def roofline_s(flops: float, nbytes: float, peak) -> Dict[str, Any]:
    """The least time the chip could take, and which bound sets it."""
    t_c, t_m = flops / peak.flops, nbytes / peak.hbm_bytes_per_s
    return {"s": max(t_c, t_m), "bound": "compute" if t_c >= t_m else "memory"}
