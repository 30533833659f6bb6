"""FLOPs a training step of one chip's share of an MLA + MoE model needs,
from shapes and the program's own count of the (token, expert) pairs its
held experts computed.

Per token: 6 FLOPs for every matmul parameter every token crosses (the
attention projections, the latent's key and value expansion, the dense
layer's and the shared experts' SwiGLUs, the router and the head; the
embedding lookup does not count), plus 6 per parameter of one held expert
for each pair those experts computed, plus causal attention's scores and
value products (each query attends to (S + 1) / 2 keys on average:
2 h (nope + rope) FLOPs a key for q.k and 2 h v_head for p.v) times 3 for
the backward.  Recomputation does not count.
"""
from __future__ import annotations

from typing import Any, Dict

from chipbench.model_mla_moe import sizes


def every_token_params(cj: Dict[str, Any]) -> int:
    """Matmul parameters every token crosses."""
    s = sizes(cj)
    d, h, kvr = s["d"], s["h"], s["kvr"]
    attn = (d * h * (s["nope"] + s["rope"]) + d * (kvr + s["rope"])
            + kvr * h * (s["nope"] + s["vh"]) + h * s["vh"] * d)
    dense = attn + 3 * d * s["ff"]
    moe = attn + 3 * d * s["fs"] + d * s["E"]
    return s["L0"] * dense + s["L1"] * moe + s["V"] * d


def expert_params(cj: Dict[str, Any]) -> int:
    """Parameters of one routed expert."""
    s = sizes(cj)
    return 3 * s["d"] * s["fe"]


def attn_flops_per_token(cj: Dict[str, Any], seq: int) -> float:
    s = sizes(cj)
    per_key = 2 * s["h"] * (s["nope"] + s["rope"]) + 2 * s["h"] * s["vh"]
    return (s["L0"] + s["L1"]) * per_key * (seq + 1) / 2


def train_flops(cj: Dict[str, Any], seq: int, tokens: float,
                pairs: float) -> float:
    """FLOPs of ``tokens`` training tokens whose held experts computed
    ``pairs`` (token, expert) pairs over all layers."""
    return (6.0 * (every_token_params(cj) * tokens
                   + expert_params(cj) * pairs)
            + 3.0 * attn_flops_per_token(cj, seq) * tokens)
