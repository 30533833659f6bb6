"""The fused causal attention kernel on the CPU: its output and gradients
(in interpret mode) against ``sdpa``, and the rule by which
``gqa_forward`` and ``mla_forward`` take it or keep the unfused path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models.layers import attention

S = 256  # shorter than the kernel's 512-row block: one block of 256


def _gqa_cfg():
    """4 query heads over 2 KV heads, head width 128."""
    return dataclasses.replace(configs.get_smoke("internlm2-1.8b"),
                               d_model=64, n_heads=4, n_kv_heads=2,
                               head_dim=128)


def _mla_cfg():
    """DeepSeek-V2-Lite's widths (q.k 192, V 128) over 4 heads, YaRN on."""
    return dataclasses.replace(configs.get_smoke("deepseek-v2-lite"),
                               qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128)


def _qkv(key, h, kv, dqk, dv):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (2, S, h, dqk), jnp.float32)
    k = jax.random.normal(ks[1], (2, S, kv, dqk), jnp.float32)
    v = jax.random.normal(ks[2], (2, S, kv, dv), jnp.float32)
    g = jax.random.normal(ks[3], (2, S, h, dv), jnp.float32)
    return q, k, v, g


@pytest.mark.parametrize("form", ["gqa", "mqa-hd64", "mla"])
def test_fused_kernel_matches_sdpa(form):
    """Output and gradients in q, k and v, in f32: GQA's 4 query heads
    over 2 KV heads (hd 128), 4 over one at hd 64 (llama3.2-1b's and
    Hymba's width), and MLA's q.k 192 / V 128 with YaRN's softmax
    scale."""
    if form == "gqa":
        h, kv, dqk, dv = 4, 2, 128, 128
        scale = dqk ** -0.5
    elif form == "mqa-hd64":
        h, kv, dqk, dv = 4, 1, 64, 64
        scale = dqk ** -0.5
    else:
        cfg = _mla_cfg()
        h, kv, dqk, dv = cfg.n_heads, cfg.n_heads, 192, 128
        scale = attention.mla_softmax_scale(cfg)
        assert scale != pytest.approx(dqk ** -0.5)  # mscale^2 is in it
    q, k, v, g = _qkv(jax.random.key(3), h, kv, dqk, dv)
    pos = jnp.arange(S)

    def fused(q, k, v):
        return attention.causal_attention(q, k, v, scale=scale,
                                          interpret=True)

    def plain(q, k, v):
        return attention.sdpa(q, attention._repeat_kv(k, h),
                              attention._repeat_kv(v, h), pos, pos,
                              causal=True, scale=scale)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * g)

    np.testing.assert_allclose(fused(q, k, v), plain(q, k, v),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(loss(fused), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(plain), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=name)


def _gqa_jaxpr(cfg, seq, **kw):
    p = attention.init_gqa(cfg, jax.random.key(0))
    x = jnp.zeros((2, seq, cfg.d_model), jnp.float32)
    pos = jnp.arange(seq)
    return str(jax.make_jaxpr(
        lambda p, x: attention.gqa_forward(cfg, p, x, pos, **kw))(p, x))


@pytest.mark.parametrize("case", [
    "fused", "k_valid", "window", "kv_override", "not_causal",
    "seq_not_a_block_multiple", "seq_below_one_block", "sharded_heads",
    "manual_mesh"])
def test_dispatch_rule(case):
    """Causal, unpadded, one-device self-attention at a multiple of the
    block takes the kernel; each other case traces only the unfused
    path.  Inside a shard_map (manual axes) each device attends its own
    shard, so the kernel applies there."""
    cfg = _gqa_cfg()
    seq, kw, mesh = S, {}, None
    if case == "k_valid":
        kw["k_valid"] = jnp.arange(S) >= 3
    elif case == "window":
        kw["window"] = 64
    elif case == "kv_override":
        kv = jnp.zeros((2, S, cfg.n_kv_heads, cfg.head_dim_), jnp.float32)
        kw["kv_override"] = (kv, kv)
    elif case == "not_causal":
        kw["causal"] = False
    elif case == "seq_not_a_block_multiple":
        seq = attention.FUSED_BLOCK + 128
    elif case == "seq_below_one_block":
        seq = 64
    elif case == "sharded_heads":
        mesh = jax.sharding.AbstractMesh((2,), ("model",))
    elif case == "manual_mesh":
        mesh = jax.sharding.AbstractMesh(
            (2,), ("model",), axis_types=(jax.sharding.AxisType.Manual,))
    if mesh is None:
        text = _gqa_jaxpr(cfg, seq, **kw)
    else:
        with jax.sharding.use_abstract_mesh(mesh):
            text = _gqa_jaxpr(cfg, seq, **kw)
    takes = case in ("fused", "manual_mesh")
    assert ("pallas_call" in text) == takes, case
    assert ("platform_index" in text) == takes, case


def test_mla_forward_takes_the_kernel_only_without_a_padding_mask():
    cfg = _mla_cfg()
    p = attention.init_mla(cfg, jax.random.key(0))
    x = jnp.zeros((2, S, cfg.d_model), jnp.float32)
    pos = jnp.arange(S)

    def jaxpr(**kw):
        return str(jax.make_jaxpr(
            lambda p, x: attention.mla_forward(cfg, p, x, pos, **kw))(p, x))

    assert "pallas_call" in jaxpr()
    assert "pallas_call" not in jaxpr(k_valid=jnp.arange(S) >= 3)


@pytest.mark.parametrize("form", ["gqa", "mla"])
def test_cpu_lowering_runs_the_unfused_path(form):
    """Lowered for the CPU, a call that takes the kernel on a TPU holds no
    kernel and gives what the unfused path gives, bit for bit (an
    all-true ``k_valid`` forces that path and adds nothing to the
    scores)."""
    cfg = _gqa_cfg() if form == "gqa" else _mla_cfg()
    fwd = attention.gqa_forward if form == "gqa" else attention.mla_forward
    init = attention.init_gqa if form == "gqa" else attention.init_mla
    p = init(cfg, jax.random.key(1))
    x = jax.random.normal(jax.random.key(2), (2, S, cfg.d_model), jnp.float32)
    pos = jnp.arange(S)

    def out(p, x, k_valid=None):
        return fwd(cfg, p, x, pos, k_valid=k_valid)[0]

    lowered = jax.jit(out).lower(p, x)
    assert "tpu_custom_call" not in lowered.as_text()
    got = lowered.compile()(p, x)
    want = jax.jit(out)(p, x, jnp.ones((S,), bool))
    np.testing.assert_array_equal(got, want)
