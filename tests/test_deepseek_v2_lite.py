"""DeepSeek-V2-Lite's mechanisms on the CPU at smoke size, seeded: the
training forward and gradients against the plain f32 reference
(``chipbench/reference/mla_moe.py``), the held share of an expert layer
against the uncut layer, YaRN against hand-computed values, and decoding
through the latent cache against the full forward."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import transformer
from repro.models.layers import attention, common, moe

# one chip's 4 of 16 experts (ids 4-7), top-4; 2 x 64 tokens keep each
# held expert's first 40 pairs, so the capacity drops some
SMOKE_CJ = {
    "name": "lite-smoke", "hidden_act": "silu", "attention_bias": False,
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 96,
    "kv_lora_rank": 16, "moe_intermediate_size": 32, "moe_layer_freq": 1,
    "n_routed_experts": 4, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 3, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_method": "greedy", "v_head_dim": 16, "vocab_size": 512,
    "router_experts": 16, "first_expert": 4, "aux_loss_alpha": 0.001,
    "torch_dtype": "float32"}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def test_training_forward_and_gradients_match_the_reference():
    from chipbench import data, model_mla_moe
    from chipbench.model import path_str
    from chipbench.reference import mla_moe
    cj = SMOKE_CJ
    cfg = model_mla_moe.program_config(cj)
    params = jax.jit(lambda k: model_mla_moe.make_params(cj, k))(
        data.key(2 ** 33 + 11, 1))
    b = data.tokens_at(5, 0, 2, 64, cj["vocab_size"])
    B, S = b["tokens"].shape
    kn = mla_moe.knobs(cj, None, B * S, B)

    logits, _ = transformer.forward(cfg, params, b, remat=False)
    (loss, stats), grads = jax.value_and_grad(
        lambda p: transformer.loss_and_stats(cfg, p, b), has_aux=True)(params)

    n = cj["num_hidden_layers"] - cj["first_k_dense_replace"]
    off = jnp.zeros((n, cj["n_routed_experts"]), jnp.int32)
    vg = jax.value_and_grad(lambda p, seq, o: mla_moe.seq_loss(
        p, seq, o, cj, None, kn), has_aux=True)
    ref_loss, kept, ref_g = 0.0, 0.0, None
    for r in range(B):
        seq = {k: jnp.asarray(v[r]) for k, v in b.items()}
        ref_logits, _ = mla_moe.logits(params, seq, off, cj, kn)
        np.testing.assert_allclose(np.asarray(logits[r, :, :cj["vocab_size"]]),
                                   np.asarray(ref_logits), rtol=2e-4, atol=2e-4)
        (l, cnt), g = vg(params, seq, off)
        off = off + cnt[:, 0]
        kept += float(jnp.sum(cnt[:, 1]))
        ref_loss += float(l)
        ref_g = g if ref_g is None else jax.tree.map(jnp.add, ref_g, g)

    assert float(loss) == pytest.approx(ref_loss, rel=1e-5)
    routed = float(jnp.sum(off))
    assert float(stats["moe_pairs"]) == kept
    assert float(stats["moe_dropped"]) == routed - kept > 0
    flat_p = jax.tree_util.tree_flatten_with_path(grads)[0]
    flat_r = dict((path_str(p), x) for p, x in
                  jax.tree_util.tree_flatten_with_path(ref_g)[0])
    for p, g in flat_p:
        assert _rel(g, flat_r[path_str(p)]) < 1e-3, path_str(p)


def _share_cfg():
    cfg = configs.get_smoke("deepseek-v2-lite")
    # 16 experts top-4 at the published capacity factor, so some drop
    return dataclasses.replace(cfg, moe_n_routed=16, moe_top_k=4,
                               moe_capacity_factor=1.25)


def test_eight_shares_sum_to_the_uncut_layer():
    full = _share_cfg()
    p = moe.init_moe(full, jax.random.key(3))
    # a direction every token shares skews the routing past the capacity
    x = (jax.random.normal(jax.random.key(4), (2, 32, full.d_model))
         + 2.0 * jax.random.normal(jax.random.key(7), (full.d_model,)))
    out, aux, st = moe.moe_forward(full, p, x)
    shared = common.mlp(p["shared"], x)
    assert float(st["moe_dropped"]) > 0
    n = full.moe_n_routed // 8
    total, pairs, dropped = shared, 0.0, 0.0
    for r in range(8):
        cfg = dataclasses.replace(full, moe_experts_held=n,
                                  moe_first_expert=r * n)
        pr = dict(p, **{k: p[k][r * n:(r + 1) * n]
                        for k in ("w_gate", "w_up", "w_down")})
        o, a, s = moe.moe_forward(cfg, pr, x)
        assert float(a) == pytest.approx(float(aux), rel=1e-6)
        total = total + (o - shared)
        pairs += float(s["moe_pairs"])
        dropped += float(s["moe_dropped"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(out),
                               rtol=1e-5, atol=1e-5)
    assert pairs == float(st["moe_pairs"])
    assert dropped == float(st["moe_dropped"])
    assert pairs + dropped == 2 * 32 * full.moe_top_k


def test_yarn_against_hand_computed_values():
    cfg = configs.get("deepseek-v2-lite")
    # mscale(40, 0.707) = 0.1 x 0.707 x ln 40 + 1
    m = 0.1 * 0.707 * 3.6888794541139363 + 1.0
    assert common.yarn_mscale(40, 0.707) == pytest.approx(1.2608038, abs=1e-7)
    assert common.yarn_mscale(40, 0.707) == pytest.approx(m, rel=1e-12)
    assert common.yarn_mscale(1.0, 0.707) == 1.0
    assert attention.mla_softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * 1.2608038 ** 2, rel=1e-6)
    inv = common.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    plain = 10000.0 ** (-np.arange(32) / 32)
    # the correction range is dims 10-23: plain below, plain / 40 from 23
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    ramp = 6 / 13
    assert inv[16] == pytest.approx(0.01 * (1 - ramp) + 0.01 / 40 * ramp,
                                    rel=1e-6)
    cos, sin = common.yarn_angles(cfg, jnp.arange(3), 64)
    # cos/sin factor mscale(40, 0.707) / mscale(40, 0.707) = 1
    np.testing.assert_allclose(np.asarray(cos[1]), np.cos(inv), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sin[2]), np.sin(2 * inv),
                               rtol=1e-5, atol=1e-7)
    assert math.isclose(float(cos[0, 0]), 1.0)


def test_prefill_then_decode_matches_the_full_forward():
    """Query projection (no latent) and YaRN through the latent cache:
    teacher-forced decoding reproduces the parallel forward's logits."""
    cfg = configs.get_smoke("deepseek-v2-lite")
    assert cfg.q_lora_rank == 0 and cfg.yarn["factor"] == 40
    B, S, prompt, max_seq = 2, 40, 32, 48
    toks = jax.random.randint(jax.random.key(5), (B, S), 0, cfg.vocab_size)
    params = transformer.init_params(cfg, jax.random.key(6))
    assert "w_q" in params["segments"][1]["attn"]
    assert "q_norm" not in params["segments"][1]["attn"]
    full, _ = transformer.forward(cfg, params, {"tokens": toks}, remat=False)
    caches, last = transformer.prefill(cfg, params,
                                       {"tokens": toks[:, :prompt]})
    np.testing.assert_allclose(np.asarray(last[:, 0]),
                               np.asarray(full[:, prompt - 1]),
                               rtol=2e-3, atol=2e-3)
    grown = jax.eval_shape(lambda: transformer.init_caches(cfg, B, max_seq))
    caches = jax.tree.map(
        lambda c, g: jnp.pad(c, [(0, t - s) for s, t in zip(c.shape,
                                                            g.shape)]),
        caches, grown)
    step = jax.jit(lambda c, t, p: transformer.decode_step(cfg, params, c,
                                                           t, p))
    for t in range(prompt, S):
        caches, lg = step(caches, toks[:, t:t + 1],
                          jnp.full((B,), t, jnp.int32))
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=2e-3, atol=2e-3, err_msg=f"step {t}")


EP_RUN = """
import dataclasses, json
import jax, jax.numpy as jnp
from repro import compat, configs
from repro.models.layers import moe
cfg = dataclasses.replace(configs.get_smoke("deepseek-v2-lite"),
                          moe_n_routed=16, moe_top_k=4,
                          moe_capacity_factor=1.25)
p = moe.init_moe(cfg, jax.random.key(3))
x = (jax.random.normal(jax.random.key(4), (4, 32, cfg.d_model))
     + 2.0 * jax.random.normal(jax.random.key(7), (cfg.d_model,)))
out, _, st = moe.moe_forward(cfg, p, x, groups=2)
with jax.set_mesh(compat.make_mesh((2, 2), ("data", "model"))):
    ep, _, se = jax.jit(lambda p, x: moe.moe_forward(
        cfg, p, x, groups=2, ep_axis="model"))(p, x)
print("RESULT " + json.dumps({
    "err": float(jnp.max(jnp.abs(ep - out))),
    "pairs": [float(st["moe_pairs"]), float(se["moe_pairs"])],
    "dropped": [float(st["moe_dropped"]), float(se["moe_dropped"])]}))
"""


def test_expert_parallel_ranks_sum_to_the_one_device_layer():
    """The EP path (each rank's ``_held_share`` psum'd over a 2-way
    model axis, groups over a 2-way data axis) on four host devices
    equals the layer on one device, counters included."""
    import json
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", EP_RUN], env=env,
                       capture_output=True, text=True, timeout=600)
    lines = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    assert p.returncode == 0 and lines, p.stderr[-3000:]
    r = json.loads(lines[-1][len("RESULT "):])
    assert r["err"] < 1e-5, r
    assert r["pairs"][0] == r["pairs"][1] and r["dropped"][0] == r["dropped"][1]
    assert r["dropped"][0] > 0, r
