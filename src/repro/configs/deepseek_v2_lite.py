"""DeepSeek-V2-Lite — MLA without a query latent + MoE 64 routed top-6
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json].

27L d_model=2048 16H. MLA: one query projection d -> 16 x (128 + 64)
(``q_lora_rank`` null), kv_lora=512, qk_nope=128, qk_rope=64, v_head=128.
Layer 0 keeps a dense FFN (d_ff=10944); layers 1-26 hold 2 shared + 64
routed experts of width 1408, top-6 by softmax, weights not renormalised
(``norm_topk_prob`` false, ``routed_scaling_factor`` 1), sequence-wise
balance loss at alpha 0.001. YaRN rope: factor 40 over 4096 original
positions, beta 32/1, mscale = mscale_all_dim = 0.707, theta 10000; the
softmax scale is 192 ** -0.5 x mscale(40, 0.707) ** 2. rms_norm_eps 1e-6,
vocab 102400, untied head.

Rope layout: the published code de-interleaves the 64 rope columns of q
and k (pairs (2i, 2i+1)) before its rotate-half rope; this program rotates
halves of the columns as they are stored. With weights drawn at random
the two are the same model up to a fixed permutation of those 64 columns
of ``w_q`` and ``w_dkv``, so the permutation is not emulated; published
weights would need their rope columns permuted on loading.
"""
from repro.models.config import ModelConfig

YARN = (("type", "yarn"), ("factor", 40.0),
        ("original_max_position_embeddings", 4096), ("beta_fast", 32.0),
        ("beta_slow", 1.0), ("mscale", 0.707), ("mscale_all_dim", 0.707))

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,  # qk_nope + qk_rope (used for FLOP accounting only)
    d_ff=1408,
    vocab_size=102400,
    moe_n_routed=64,
    moe_n_shared=2,
    moe_top_k=6,
    moe_d_ff=1408,
    moe_first_k_dense=1,
    dense_d_ff=10944,
    moe_norm_topk=False,
    moe_routed_scale=1.0,
    moe_aux_coef=0.001,
    moe_seq_aux=True,
    use_mla=True,
    q_lora_rank=0,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    rope_scaling=YARN,
    rope_theta=10000.0,
    norm_eps=1e-6,
)

SMOKE_CONFIG = ModelConfig(
    name="deepseek-v2-lite-smoke",
    family="moe",
    n_layers=3,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    head_dim=24,
    d_ff=32,
    vocab_size=512,
    moe_n_routed=8,
    moe_n_shared=2,
    moe_top_k=2,
    moe_d_ff=32,
    moe_capacity_factor=16.0,  # = E_pad: provably drop-free for exact tests
    moe_first_k_dense=1,
    dense_d_ff=96,
    moe_norm_topk=False,
    moe_aux_coef=0.001,
    moe_seq_aux=True,
    use_mla=True,
    q_lora_rank=0,
    kv_lora_rank=16,
    qk_nope_dim=16,
    qk_rope_dim=8,
    v_head_dim=16,
    rope_scaling=YARN,
    norm_eps=1e-6,
    dtype="float32",
)
