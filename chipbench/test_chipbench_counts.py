"""Counts from shapes, and the table of peaks."""
from __future__ import annotations

import json
import os
import types

import pytest

from chipbench import counts, harness, peaks


def _config():
    with open(os.path.join(harness.ROOT, "chipbench", "configs",
                           "internlm2-1.8b.json")) as f:
        return json.load(f)


def test_param_counts_match_the_program_at_full_width():
    import dataclasses
    import jax
    from chipbench import model
    from chipbench.model import path_str
    from repro.models import transformer
    cj = dict(_config(), num_hidden_layers=24)     # the published depth
    cfg = model.program_config(cj)
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.key(0)))
    pad_rows = cfg.vocab_padded - cfg.vocab_size
    got = {}
    for p, x in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        n = x.size
        if path_str(p) in ("embed", "lm_head"):
            n -= pad_rows * cfg.d_model
        got[path_str(p)] = n
    assert got == counts.dense_params(cj)
    assert sum(got.values()) == pytest.approx(1.889e9, rel=1e-3)
    del dataclasses


def test_train_flops_by_hand():
    cj = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
          "intermediate_size": 16, "num_hidden_layers": 3, "vocab_size": 10,
          "tie_word_embeddings": False}
    per_layer = 8 * 2 * 4 + 2 * 8 * 1 * 4 + 2 * 4 * 8 + 3 * 8 * 16
    mm = 3 * per_layer + 10 * 8
    attn = 3 * 2 * 2 * 2 * 4 * (5 + 1) / 2
    assert counts.train_flops_per_token(cj, 5) == 6 * mm + 3 * attn


def test_kmeans_counts_small_case():
    a = counts.kmeans_assign(1024, 4, 3)
    assert a["flops"] == 1024 * 4 * (2 * 3 + 2)
    assert a["bytes"] == 4 * (1024 * 3 + 4 * 3) + 1024 * 8
    assert counts.kmeans_iter_flops(1024, 4, 3) == a["flops"] + 1024 * 4
    r = counts.roofline_s(a["flops"], a["bytes"],
                          peaks.PEAKS["TPU v5 lite"])
    assert r["bound"] == "memory"
    assert r["s"] == pytest.approx(a["bytes"] / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak_for("TPU v99")
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    with pytest.raises(peaks.UnknownDevice):
        harness.device_info([dev], require_tpu=True)
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    with pytest.raises(harness.NoChip):
        harness.device_info([cpu], require_tpu=True)
