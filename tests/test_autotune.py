"""Autotune registry + block-size resolution: cache hits skip re-timing,
keys discriminate backend/dtype, corrupt registries degrade to defaults,
and the ops wrappers snap autotuned/odd shapes to legal grids."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune as at


# ------------------------------------------------------------- snapping
def test_snap_block_divides():
    assert at.snap_block(1024, 256) == 256
    assert at.snap_block(384, 256) == 192     # odd seq: largest divisor
    assert at.snap_block(100, 64) == 50
    assert at.snap_block(7, 512) == 7
    assert at.snap_block(13, 4) == 1          # prime: degenerates to 1
    for n in (48, 384, 1000, 4096):
        for cap in (8, 64, 256, 2048):
            b = at.snap_block(n, cap)
            assert n % b == 0 and 1 <= b <= min(cap, n)


def test_shape_bucket_pow2_rounds():
    b1 = at.shape_bucket("flash_attention", {"S_q": 1000, "hd": 64})
    b2 = at.shape_bucket("flash_attention", {"S_q": 1024, "hd": 64})
    b3 = at.shape_bucket("flash_attention", {"S_q": 2048, "hd": 64})
    assert b1 == b2 != b3   # nearby shapes share a tuned config


# ------------------------------------------------------------- registry
def test_corrupt_registry_falls_back_to_defaults(tmp_path):
    bad = tmp_path / "autotune.json"
    bad.write_text("{not json")
    reg = at.Registry(str(bad))
    assert reg.corrupt and len(reg) == 0
    # wrong schema is also rejected
    bad.write_text(json.dumps({"k": "not-a-dict"}))
    assert at.Registry(str(bad)).corrupt


def test_missing_registry_is_empty_not_error(tmp_path):
    reg = at.Registry(str(tmp_path / "nope" / "autotune.json"))
    assert not reg.corrupt and len(reg) == 0


def test_registry_roundtrip(tmp_path):
    path = str(tmp_path / "autotune.json")
    reg = at.Registry(path)
    reg.put("k", {"config": {"bq": 128}})
    reg.save()
    assert at.Registry(path).get("k") == {"config": {"bq": 128}}


def test_key_includes_backend_and_dtype():
    k1 = at.Registry.key("flash_attention", "S1024", "cpu+interpret",
                         "float32")
    k2 = at.Registry.key("flash_attention", "S1024", "tpu", "float32")
    k3 = at.Registry.key("flash_attention", "S1024", "cpu+interpret",
                         "bfloat16")
    assert len({k1, k2, k3}) == 3


def test_lookup_respects_dtype_axis(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_REGISTRY",
                       str(tmp_path / "autotune.json"))
    reg = at.default_registry(reload=True)
    shape = {"S_q": 1024, "S_k": 1024, "hd": 64}
    key = at.Registry.key("flash_attention",
                          at.shape_bucket("flash_attention", shape),
                          at.backend_tag(), "float32")
    reg.put(key, {"config": {"bq": 512, "bk": 512}})
    assert at.lookup("flash_attention", shape, jnp.float32) == \
        {"bq": 512, "bk": 512}
    # same shape, different dtype: miss -> caller uses DEFAULTS
    assert at.lookup("flash_attention", shape, jnp.bfloat16) is None
    at.default_registry(reload=True)


# ---------------------------------------------------------- cache skips
def test_cache_hit_skips_retiming(tmp_path, monkeypatch):
    reg = at.Registry(str(tmp_path / "autotune.json"))
    calls = {"n": 0}
    real = at._time_call

    def counting(fn, reps):
        calls["n"] += 1
        return real(fn, reps)

    monkeypatch.setattr(at, "_time_call", counting)
    shape = {"n": 256, "k": 8, "d": 3}
    first = at.autotune("kmeans", shape, reps=1, registry=reg)
    assert first["trials"] > 0 and not first["cached"]
    n_after_first = calls["n"]
    assert n_after_first == first["trials"]

    second = at.autotune("kmeans", shape, reps=1, registry=reg)
    assert second["cached"] and second["trials"] == 0
    assert calls["n"] == n_after_first        # no re-timing at all
    assert second["config"] == first["config"]

    forced = at.autotune("kmeans", shape, reps=1, registry=reg, force=True)
    assert not forced["cached"] and calls["n"] > n_after_first


def test_autotune_winner_never_worse_than_default(tmp_path):
    reg = at.Registry(str(tmp_path / "autotune.json"))
    rec = at.autotune("kmeans", {"n": 256, "k": 8, "d": 3}, reps=1,
                      registry=reg)
    assert rec["speedup_vs_default"] >= 1.0 - 1e-9   # default is a candidate


# ----------------------------------------------------------- candidates
def test_candidates_respect_vmem_budget():
    for cand in at.candidates_flash(8192, 8192, 128):
        bq, bk = cand["bq"], cand["bk"]
        vmem = 4 * (3 * bq * 128 + 2 * bk * 128 + 2 * bq)
        assert vmem <= at.VMEM_BUDGET_BYTES
    # a tiny budget prunes everything big
    small = at.candidates_flash(8192, 8192, 128, budget=256 * 1024)
    assert small and all(c["bq"] <= 128 for c in small)


def test_mamba_vmem_filter_refuses_what_v5e_refuses():
    """At falcon-mamba widths (di=8192, st=16) the v5e compiler runs out of
    VMEM at bdi=512, bs=16: st pads to 128 lanes and every block is
    double-buffered.  The filter must refuse it and keep the default."""
    cands = at.candidates_mamba(2048, 8192, 16)
    assert {"bdi": 512, "bs": 16} not in cands
    assert at.DEFAULTS["mamba_scan"] in cands


def test_kmeans_candidates_land_as_resolved_and_fit_vmem():
    """Each candidate is a pair ``resolve_blocks`` keeps as it is, and the
    d coordinate planes of the largest point block push it out at d=16."""
    from repro.kernels.kmeans import ops as km
    for n, k, d in ((11_184_128, 50, 3), (100_000, 500, 16), (256, 8, 3)):
        cands = at.candidates_kmeans(n, k, d)
        assert cands
        for c in cands:
            assert km.resolve_blocks(n, k, d, None, c["bn"], c["bk"]) == \
                (c["bn"], c["bk"])
    assert {"bn": 262144, "bk": 50} in at.candidates_kmeans(11_184_128, 50, 3)
    assert all(c["bn"] < 262144
               for c in at.candidates_kmeans(11_184_128, 50, 16))


def test_candidates_snap_to_shape_divisors():
    for c in at.candidates_flash(384, 384, 64):
        assert 384 % c["bq"] == 0 and 384 % c["bk"] == 0
    for c in at.candidates_mamba(48, 24, 8):
        assert 24 % c["bdi"] == 0 and 48 % c["bs"] == 0


# --------------------------------------------------- ops wrapper consult
def test_ops_wrappers_consult_registry(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_REGISTRY",
                       str(tmp_path / "autotune.json"))
    reg = at.default_registry(reload=True)
    shape = {"S_q": 256, "S_k": 256, "hd": 8}
    key = at.Registry.key("flash_attention",
                          at.shape_bucket("flash_attention", shape),
                          at.backend_tag(), "float32")
    reg.put(key, {"config": {"bq": 64, "bk": 64}})

    from repro.kernels.flash_attention import ops as fa
    bq, bk = fa.resolve_blocks(256, 256, 8, jnp.float32, None, None)
    assert (bq, bk) == (64, 64)               # registry entry won
    bq, bk = fa.resolve_blocks(256, 256, 8, jnp.float32, 32, None)
    assert (bq, bk) == (32, 64)               # explicit arg beats registry
    at.default_registry(reload=True)


def test_ops_wrappers_default_without_registry(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_REGISTRY",
                       str(tmp_path / "empty.json"))
    at.default_registry(reload=True)
    from repro.kernels.flash_attention import ops as fa
    from repro.kernels.mamba_scan import ops as ms
    assert fa.resolve_blocks(1024, 1024, 64, jnp.float32, None, None) == \
        (256, 256)                            # legacy constants survive
    assert ms.resolve_blocks(256, 512, 16, jnp.float32, None, None) == \
        (256, 16)                             # shipped default (v5e-legal)
    at.default_registry(reload=True)


def test_stale_kmeans_entry_is_not_applied(tmp_path, monkeypatch):
    """An entry tuned for the earlier K-Means blocking, under the kernel's
    old registry name, is never applied to the lane-dense kernel."""
    monkeypatch.setenv("REPRO_AUTOTUNE_REGISTRY",
                       str(tmp_path / "autotune.json"))
    reg = at.default_registry(reload=True)
    shape = {"n": 1_000_000, "k": 50, "d": 3}
    bucket = at.shape_bucket("kmeans", shape)
    reg.put(f"kmeans|{bucket}|{at.backend_tag()}|float32",
            {"config": {"bn": 1024, "bk": 512}})
    from repro.kernels.kmeans import ops as km
    default = km.resolve_blocks(1_000_000, 50, 3, jnp.float32,
                                at.DEFAULTS["kmeans"]["bn"],
                                at.DEFAULTS["kmeans"]["bk"])
    assert km.resolve_blocks(1_000_000, 50, 3, jnp.float32, None, None) == \
        default
    reg.put(at.Registry.key("kmeans", bucket, at.backend_tag(), "float32"),
            {"config": {"bn": 16384, "bk": 32}})
    assert km.resolve_blocks(1_000_000, 50, 3, jnp.float32, None, None) == \
        (16384, 32)
    at.default_registry(reload=True)


def test_attention_odd_seq_no_crash():
    """S=384 used to trip `assert S % bq == 0`; now bq snaps to 192."""
    from repro.kernels.flash_attention import ops as fa
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 384, 2, 16)), jnp.float32) * 0.3
    out = fa.attention(q, q, q)
    assert out.shape == q.shape and bool(jnp.isfinite(out).all())


def test_mamba_odd_shapes_no_crash():
    from repro.kernels.mamba_scan import ops as ms
    B, S, di, st = 1, 48, 24, 8               # di=24 not divisible by 512
    a = jnp.full((B, S, di, st), 0.9, jnp.float32)
    b = jnp.full((B, S, di, st), 0.1, jnp.float32)
    C = jnp.ones((B, S, st), jnp.float32)
    h0 = jnp.zeros((B, di, st), jnp.float32)
    y, h = ms.scan(a, b, C, h0)
    assert y.shape == (B, S, di) and h.shape == (B, di, st)
    assert bool(jnp.isfinite(y).all())
