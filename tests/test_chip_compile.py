"""Compile every Pallas kernel for a described TPU v5e chip, at the widths
the main path runs them, with the blocks ``resolve_blocks`` picks, the
training cells' train steps, one layer deep, whose attention runs in the
fused causal kernel, and K-Means' initial draw over one block of points.

Nothing runs: the TPU compiler lowers each kernel for a chip that is
described, not attached, and refuses what the chip would refuse (VMEM
overflow, tile misalignment).  The topology is described inside a
module-scoped fixture only: the TPU library may be loaded by one process
at a time, and every pytest worker imports this file.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.analytics import draw
from repro.kernels.kmeans import kmeans as km_kernel
from repro.kernels.kmeans import ops as km_ops
from repro.models import transformer
from repro.models.layers import attention
from repro.train.step import make_train_state, make_train_step


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries written for a described chip cannot be read back without one
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    assert topo.devices[0].device_kind == "TPU v5 lite"
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _assert_kmeans_results(compiled, n):
    """The assignment kernel's custom call returns (idx s32[n], f32[n]): the
    signature by which the benchmark finds the kernel in a device trace."""
    assert re.search(rf"= \(s32\[{n}\]\{{[^}}]*\}}, f32\[{n}\]\{{[^}}]*\}}\) "
                     r"custom-call\(", compiled.as_text()), n


@pytest.mark.parametrize("n,k", [(1_000_000, 50), (1_000_000, 5_000)])
def test_kmeans_assign_compiles(one_chip, n, k):
    """The paper's 1M x 3 scenario, with 50 and 5,000 centroids."""
    d = 3
    bn, bk = km_ops.resolve_blocks(n, k)
    rows = -(-n // km_kernel.LANES)
    centroid_words = -(-k // bk) * km_kernel.centroid_stride(d, bk)
    fn = functools.partial(km_kernel.assign_pallas, br=bn // km_kernel.LANES,
                           bk=bk, interpret=False)
    compiled = _compile(fn, _spec((d, rows, km_kernel.LANES), jnp.float32,
                                  one_chip),
                        _spec((centroid_words,), jnp.float32, one_chip))
    _assert_kmeans_results(compiled, rows * km_kernel.LANES)


def test_kmeans_block_assign_compiles(one_chip):
    """One 128 MB HDFS block of d=3 points through the public wrapper: the
    points are relaid out once, as coordinate planes, and no larger."""
    n, k, d = 11_184_128, 50, 3
    compiled = _compile(lambda p, c: km_ops.assign(p, c),
                        _spec((n, d), jnp.float32, one_chip),
                        _spec((k, d), jnp.float32, one_chip))
    _assert_kmeans_results(compiled, n)
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * n * d * 4


def test_kmeans_ops_wrapper_picks_compiled_kernel(one_chip):
    """The public wrapper, lowered for the chip, takes the compiled branch
    (interpret mode is chosen per lowering platform, not by a global)."""
    compiled = _compile(lambda p, c: km_ops.assign(p, c),
                        _spec((10_240, 3), jnp.float32, one_chip),
                        _spec((5_120, 3), jnp.float32, one_chip))
    _assert_kmeans_results(compiled, 10_240)


def _sorts_of(n: int, text: str):
    return re.findall(rf"^\s*%\S+ = [^\n]*\[{n}\][^\n]* sort\(", text, re.M)


def test_kmeans_draw_sorts_no_index_array(one_chip):
    """K-Means' initial draw of 50 of one 128 MB block's 11,184,128 points:
    the rank selection counts digits as one-hot products and sorts nothing
    of the block, where jax's permutation sorts every index once a round
    (shown at 4,096, where the compile is quick)."""
    key = _spec((), jax.random.key(0).dtype, one_chip)
    lower = lambda fn: jax.jit(fn).lower(key).compile().as_text()
    n, k = 11_184_128, 50
    text = lower(lambda key: draw.choice_distinct(key, n, k))
    assert re.search(rf"s32\[{k},{draw.BINS}\]\S* convolution\(", text)
    assert not _sorts_of(n, text)
    small = 4_096
    permuted = lower(lambda key: jax.random.choice(key, small, (k,),
                                                   replace=False))
    assert len(_sorts_of(small, permuted)) == draw.shuffle_rounds(small) == 2


# the training cells' attention: InternLM2 (16 query heads over 8 KV heads,
# hd 128) at 2 x 2048, and DeepSeek-V2-Lite's MLA (16 heads, q.k 192, V 128)
# at 4 x 4096; and llama3.2-1b's (32 query heads over 8 KV heads, hd 64,
# a head half a lane row wide) at 1 x 2048
ATTN_SHAPES = {"internlm2": (2, 2048, 16, 8, 128, 128),
               "mla": (4, 4096, 16, 16, 192, 128),
               "llama3.2-1b": (1, 2048, 32, 8, 64, 64)}


@pytest.mark.parametrize("form", sorted(ATTN_SHAPES))
def test_fused_attention_compiles_forward_and_backward(one_chip, form):
    """The fused causal kernel's custom calls: the forward, and one
    backward kernel that gives dq, dk and dv."""
    B, S, H, KV, dqk, dv = ATTN_SHAPES[form]

    def loss(q, k, v):
        out = attention.causal_attention(q, k, v, scale=dqk ** -0.5)
        return jnp.sum(out.astype(jnp.float32))

    compiled = _compile(jax.grad(loss, (0, 1, 2)),
                        _spec((B, S, H, dqk), jnp.bfloat16, one_chip),
                        _spec((B, S, KV, dqk), jnp.bfloat16, one_chip),
                        _spec((B, S, KV, dv), jnp.bfloat16, one_chip))
    calls = set(re.findall(
        r"%splash_mqa_(fwd|dq|dkv)_\w+\.\d+ = .*custom-call\(",
        compiled.as_text()))
    assert calls == {"fwd", "dkv"}


def _one_layer_train_step(one_chip, arch, batch, seq) -> str:
    """HLO of one train step of ``arch`` cut to one layer, compiled for
    the chip."""
    cfg = dataclasses.replace(configs.get(arch), n_layers=1)
    state = jax.eval_shape(lambda: make_train_state(
        cfg, transformer.init_params(cfg, jax.random.key(0))))
    spec = lambda x: _spec(x.shape, x.dtype, one_chip)
    tokens = _spec((batch, seq), jnp.int32, one_chip)
    b = {"tokens": tokens, "labels": tokens,
         "mask": _spec((batch, seq), jnp.float32, one_chip)}
    return _compile(make_train_step(cfg), jax.tree.map(spec, state),
                    b).as_text()


@pytest.mark.parametrize("arch,batch,seq,scores", [
    ("internlm2-1.8b", 2, 2048, r"f32\[2,16,2048,2048\]"),
    ("deepseek-v2-lite", 4, 4096, r"\[4,16,1024,1024\]")],
    ids=["internlm2", "deepseek-v2-lite"])
def test_train_step_holds_no_score_array(one_chip, arch, batch, seq, scores):
    """The train steps of both training cells, one layer deep: attention
    runs in the fused kernel, and neither the whole score matrix nor a
    chunk's block of logits is an array of the program."""
    text = _one_layer_train_step(one_chip, arch, batch, seq)
    assert "%splash_mqa_dkv" in text
    assert not re.search(scores, text)
