"""Mixture-of-Experts layer: top-k routing with sort-based capacity dispatch.

Design (TPU-idiomatic):
  * Router over the *logical* expert count; experts padded to a multiple
    of 16 for clean expert-parallelism over the `model` mesh axis
    (padding experts masked to -inf in the router).
  * Dispatch plan = per-group argsort by expert id -> position-in-expert
    via segment offsets; an expert keeps the first ``cap`` of its pairs in
    token order and drops the rest. `groups` = the mesh's dp-shard count,
    so all sorting is group-local. No (T, E, C) one-hot is materialized.
  * A layer holds the weights of ``cfg.moe_n_held`` experts starting at
    router id ``cfg.moe_first_expert`` (all of them unless the config
    says otherwise). ``_held_share`` computes their part of the output:
    it gathers only the rows routed to them (an (n, cap) table of token
    indices), runs a batched per-expert SwiGLU einsum and scatter-adds the
    weighted rows back. Rows bound for other experts are never gathered.
    A config that holds a share (one chip of an expert-parallel group)
    leaves out what the absent experts would add.
  * EP path (``ep_axis`` set): the held experts are divided over the
    model axis under a fully-manual ``shard_map``; each rank runs
    ``_held_share`` for its own experts and the only cross-model traffic
    is one psum of the (g, tg, d) combined output (+ its transpose in
    backward). Letting GSPMD partition this instead moves full (tg*k, d)
    token tensors across the model axis per layer (~0.5 GB/device/layer
    measured on DeepSeek-V2).
  * Shared experts are fused into one wide SwiGLU (mathematically exact:
    elementwise gating makes the sum of k SwiGLUs equal one SwiGLU of
    concatenated hidden width).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from .common import normal_init

Params = Dict[str, Any]


def init_moe(cfg, key) -> Params:
    d = cfg.d_model
    e = cfg.moe_n_routed_padded
    n = cfg.moe_n_held
    f = cfg.moe_d_ff
    dt = cfg.param_dtype
    ks = jax.random.split(key, 5)
    p = {
        "router": normal_init(ks[0], (d, e), jnp.float32, d ** -0.5),
        "w_gate": normal_init(ks[1], (n, d, f), dt, d ** -0.5),
        "w_up": normal_init(ks[2], (n, d, f), dt, d ** -0.5),
        "w_down": normal_init(ks[3], (n, f, d), dt, f ** -0.5),
    }
    if cfg.moe_n_shared:
        fs = cfg.moe_n_shared * cfg.moe_d_ff
        from .common import init_mlp
        p["shared"] = init_mlp(cfg, ks[4], fs)
    return p


def _topk_iterative(probs: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """(T, E) -> (top-k values, indices), k rounds of argmax+mask."""
    vals, idxs = [], []
    cur = probs
    eye = jnp.arange(probs.shape[-1])[None, :]
    for _ in range(k):
        i = jnp.argmax(cur, axis=-1)
        v = jnp.max(cur, axis=-1)
        vals.append(v)
        idxs.append(i.astype(jnp.int32))
        # elementwise mask (a scatter here re-introduces collective traffic)
        cur = jnp.where(eye == i[:, None], -jnp.inf, cur)
    return jnp.stack(vals, axis=-1), jnp.stack(idxs, axis=-1)


def _route(cfg, p: Params, x2d: jax.Array, n_seq: int = 1
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x2d: (T, d) -> (top-k weights (T,k), top-k ids (T,k), aux loss).

    The aux loss is Switch-style over all T tokens, or with
    ``cfg.moe_seq_aux`` DeepSeek-V2's sequence-wise expert balance loss
    (arXiv:2405.04434 §2.2.3) over each of the ``n_seq`` sequences the
    T tokens hold, averaged over them."""
    e_pad, e = cfg.moe_n_routed_padded, cfg.moe_n_routed
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), p["router"])
    if e_pad != e:
        logits = jnp.where(jnp.arange(e_pad) < e, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    # iterative argmax top-k: lax.top_k lowers to a sort that XLA:SPMD
    # all-gathers across the mesh (measured: a full (T, E) gather per
    # layer); k argmax+mask rounds stay perfectly token-sharded.
    top_p, top_i = _topk_iterative(probs, cfg.moe_top_k)
    if cfg.moe_norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    if cfg.moe_routed_scale != 1.0:
        top_p = top_p * cfg.moe_routed_scale
    if cfg.moe_seq_aux:
        # f_i = E / (S k) x pairs of the sequence routed to expert i,
        # P_i = the sequence's mean router probability of expert i
        ids = top_i.reshape(n_seq, -1)
        f = jax.vmap(lambda r: jnp.zeros((e_pad,)).at[r].add(1.0))(ids)[:, :e]
        f = f * (e / ids.shape[1])
        pm = probs.reshape(n_seq, -1, e_pad).mean(axis=1)[:, :e]
        aux = jnp.mean(jnp.sum(f * pm, axis=-1))
    else:
        # Switch-style load-balance auxiliary loss over logical experts.
        me = probs.mean(axis=0)[:e]
        ce = jnp.zeros((e_pad,)).at[top_i.reshape(-1)].add(1.0)[:e]
        ce = ce / jnp.maximum(ce.sum(), 1.0)
        aux = e * jnp.sum(me * ce)
    return top_p.astype(x2d.dtype), top_i, aux


def _dispatch_plan(cfg, top_p, top_i, groups: int, tg: int, cap: int, e: int):
    """Sort-based dispatch metadata, all group-local ops: for every
    (token, slot) pair in expert order, its slot ``dest`` in an
    (e * cap) buffer (``e * cap`` where the capacity drops it)."""
    k = cfg.moe_top_k
    flat_e = top_i.reshape(groups, tg * k)
    flat_w = top_p.reshape(groups, tg * k)
    order = jnp.argsort(flat_e, axis=-1)               # per-group, stable
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    sorted_tok = order // k
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    counts = onehot.sum(axis=1)                        # (g, e)
    seg_start = jnp.cumsum(counts, axis=-1) - counts
    pos_in_e = (jnp.arange(tg * k, dtype=jnp.int32)[None, :]
                - jnp.take_along_axis(seg_start, sorted_e, axis=-1))
    keep = pos_in_e < cap
    dest = jnp.where(keep, sorted_e * cap + pos_in_e, e * cap)  # OOB -> drop
    wsort = jnp.take_along_axis(flat_w, order, axis=-1)
    return dest, sorted_tok, wsort


def _expert_block(p, buf, x_dtype):
    """Per-expert SwiGLU on packed (g, e?, cap, d) buffers."""
    g_ = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])
    u_ = jnp.einsum("gecd,edf->gecf", buf, p["w_up"])
    h = jax.nn.silu(g_.astype(jnp.float32)).astype(x_dtype) * u_
    return jnp.einsum("gecf,efd->gecd", h, p["w_down"])


def _held_share(w: Params, xg, dest, sorted_tok, wsort, first, cap: int):
    """The part of the routed output that the experts of ``w`` (router
    ids ``first`` .. ``first + n - 1``) give.  xg: (g, tg, d); the plan
    is per group.  Only the rows routed to these experts are gathered.
    Returns ((g, tg, d) output, pairs computed)."""
    g, tg, d = xg.shape
    n = w["w_gate"].shape[0]
    local = dest - first * cap
    mine = (local >= 0) & (local < n * cap)
    slot = jnp.where(mine, local, n * cap)             # OOB -> dropped

    def tables(sl, tok, wt):
        idx = jnp.full((n * cap,), tg, jnp.int32).at[sl].set(tok, mode="drop")
        wts = jnp.zeros((n * cap,), wt.dtype).at[sl].set(wt, mode="drop")
        return idx, wts

    idx, wts = jax.vmap(tables)(slot, sorted_tok, wsort)   # (g, n*cap)
    rows = jax.vmap(lambda x_g, i: x_g.at[i].get(mode="fill", fill_value=0))(
        xg, idx)
    out = _expert_block(w, rows.reshape(g, n, cap, d), xg.dtype)
    out = out.reshape(g, n * cap, d) * wts[..., None]
    combined = jax.vmap(lambda o, i: jnp.zeros((tg, d), xg.dtype)
                        .at[i].add(o, mode="drop"))(out, idx)
    return combined, jnp.sum(mine)


def moe_forward(cfg, p: Params, x: jax.Array, *, groups: int = 1,
                ep_axis: Optional[str] = None
                ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, d) -> (out, aux loss, counters). See module docstring.

    Counters: ``moe_pairs`` the (token, expert) pairs the held experts
    computed, ``moe_dropped`` the pairs routed to them that the capacity
    dropped."""
    B, S, d = x.shape
    T = B * S
    k = cfg.moe_top_k
    e = cfg.moe_n_routed_padded
    if T % groups != 0:
        groups = 1
    tg = T // groups                                   # tokens per group
    cap = int(-(-cfg.moe_capacity_factor * tg * k // e))
    cap = max(8, ((cap + 7) // 8) * 8)

    x2d = x.reshape(T, d)
    top_p, top_i, aux = _route(cfg, p, x2d, B)
    xg = x2d.reshape(groups, tg, d)
    dest, sorted_tok, wsort = _dispatch_plan(cfg, top_p, top_i, groups, tg,
                                             cap, e)
    w = {k_: p[k_] for k_ in ("w_gate", "w_up", "w_down")}
    first = cfg.moe_first_expert
    n = w["w_gate"].shape[0]

    ep = None
    if ep_axis is not None:
        mesh = jax.sharding.get_abstract_mesh()
        if ep_axis in mesh.shape and n % mesh.shape[ep_axis] == 0:
            ep = (mesh, ep_axis, mesh.shape[ep_axis])

    if ep is None:
        combined, pairs = _held_share(w, xg, dest, sorted_tok, wsort, first,
                                      cap)
    else:
        combined, pairs = _combine_ep_shardmap(w, xg, dest, sorted_tok,
                                               wsort, first, cap, groups, ep)

    out = combined.reshape(B, S, d)
    if "shared" in p:
        from .common import mlp
        out = out + mlp(p["shared"], x)
    held = (top_i >= first) & (top_i < first + n)
    pairs = pairs.astype(jnp.float32)
    stats = {"moe_pairs": pairs,
             "moe_dropped": jnp.sum(held).astype(jnp.float32) - pairs}
    return out, aux.astype(jnp.float32), stats


def _combine_ep_shardmap(w, xg, dest, sorted_tok, wsort, first, cap,
                         groups, ep):
    """EP path: fully-manual shard_map (groups over the dp axes, the held
    experts over the model axis). Each rank runs ``_held_share`` for its
    own experts; the only cross-model traffic is one psum of the
    (g_local, tg, d) combined output (+ its transpose in backward).
    Fully-manual avoids the mixed auto/manual scatter partitioning that
    crashes XLA's SPMD partitioner."""
    mesh, axis, n_shards = ep
    n_local = w["w_gate"].shape[0] // n_shards
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    g_spec = dp_axes if (dp_axes and groups % dp_size == 0) else None

    def rank_fn(xg, dest, sorted_tok, wsort, w_gate, w_up, w_down):
        r_first = first + jax.lax.axis_index(axis) * n_local
        part, pairs = _held_share(
            {"w_gate": w_gate, "w_up": w_up, "w_down": w_down},
            xg, dest, sorted_tok, wsort, r_first, cap)
        pairs = jax.lax.psum(pairs, axis)
        if g_spec is not None:
            pairs = jax.lax.psum(pairs, dp_axes)
        return jax.lax.psum(part, axis), pairs      # (g_l, tg, d)

    fn = shard_map(
        rank_fn, mesh=mesh, check_vma=False,
        in_specs=(P(g_spec, None, None), P(g_spec, None), P(g_spec, None),
                  P(g_spec, None),
                  P(axis, None, None), P(axis, None, None),
                  P(axis, None, None)),
        out_specs=(P(g_spec, None, None), P()))
    return fn(xg, dest, sorted_tok, wsort,
              w["w_gate"], w["w_up"], w["w_down"])
