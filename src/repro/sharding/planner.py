"""Sharding planner: PartitionSpecs for params, optimizer state, batches, caches.

Strategy (baseline, see EXPERIMENTS.md §Perf for variants):
  * DP   — batch over ("pod", "data").
  * FSDP — parameters + optimizer state additionally sharded over "data"
           on a non-TP dimension (ZeRO-3 style; XLA inserts the all-gathers).
  * TP   — head / FFN-hidden / expert / SSM-channel dims over "model".
  * Fallback — any dim not divisible by its mesh axis is replicated
           (e.g. Hymba's 25 heads): the planner never produces an invalid
           spec, it degrades per-tensor.

Roles are assigned per parameter-leaf name; the same table drives both
single-layer and scan-stacked (leading L dim) parameters by aligning the
role tuple to the trailing dimensions.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig, ShapeConfig

# role -> which logical mesh resource it wants
_ROLE_TABLE: Dict[str, Tuple[Optional[str], ...]] = {
    # embeddings
    "embed": ("tp", "fsdp"),
    "lm_head": ("tp", "fsdp"),
    # GQA attention
    "wq": ("fsdp", "tp", None),
    "wk": ("fsdp", "tp", None),
    "wv": ("fsdp", "tp", None),
    "wo": ("tp", None, "fsdp"),
    # MLA (latent dims FSDP-sharded for storage; XLA gathers at use)
    "w_q": ("fsdp", "tp", None),
    "w_dq": ("fsdp", "tp"),
    "w_uq": ("fsdp", "tp", None),
    "w_dkv": ("fsdp", "tp"),
    "w_uk": ("fsdp", "tp", None),
    "w_uv": ("fsdp", "tp", None),
    # MLP
    "w_gate": ("fsdp", "tp"),
    "w_up": ("fsdp", "tp"),
    "w_down": ("tp", "fsdp"),
    # MoE (keys prefixed with moe/ in the path get the expert variants)
    "moe/w_gate": ("tp", "fsdp", None),
    "moe/w_up": ("tp", "fsdp", None),
    "moe/w_down": ("tp", None, "fsdp"),
    # router is tiny (d x E): replicate over model — sharding it makes its
    # backward psum a full (T, d) f32 tensor over the model axis per layer
    "moe/router": ("fsdp", None),
    # Mamba
    "in_proj": ("fsdp", "tp"),
    "conv_w": (None, "tp"),
    "conv_b": ("tp",),
    "x_proj": ("tp", None),
    "dt_proj": (None, "tp"),
    "dt_bias": ("tp",),
    "A_log": ("tp", None),
    "D": ("tp",),
    "out_proj": ("tp", "fsdp"),
}


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


@dataclasses.dataclass(frozen=True)
class Plan:
    """Resolved axis names + sizes for one mesh."""
    mesh_axes: Dict[str, int]            # name -> size
    dp_axes: Tuple[str, ...]             # batch axes, e.g. ("pod", "data")
    fsdp_axis: Optional[str] = "data"    # parameter-sharding axis
    tp_axis: str = "model"
    # serving (weight-stationary) mode: TP-sharded leaves drop their FSDP
    # axis — no per-token weight re-gather; leaves with no TP shard (e.g.
    # GQA wk/wv when kv_heads < tp) stay FSDP'd for HBM and stream once
    # per step. See EXPERIMENTS.md §Perf cell 3.
    serving: bool = False

    @classmethod
    def for_mesh(cls, mesh: Mesh, *, fsdp: bool = True) -> "Plan":
        axes = dict(mesh.shape)
        dp = tuple(a for a in ("pod", "data") if a in axes)
        return cls(mesh_axes=axes, dp_axes=dp,
                   fsdp_axis="data" if fsdp and "data" in axes else None)

    @property
    def dp_size(self) -> int:
        n = 1
        for a in self.dp_axes:
            n *= self.mesh_axes[a]
        return n

    # -------------------------------------------------------------- params
    def _resolve(self, roles: Tuple[Optional[str], ...], shape: Tuple[int, ...],
                 ) -> P:
        """Align roles to trailing dims; drop non-divisible assignments."""
        ndim = len(shape)
        full = (None,) * (ndim - len(roles)) + tuple(roles)
        spec = []
        for dim, role in zip(shape, full):
            axis = None
            if role == "tp":
                axis = self.tp_axis
            elif role == "fsdp":
                axis = self.fsdp_axis
            if axis is not None and dim % self.mesh_axes[axis] != 0:
                axis = None
            spec.append(axis)
        if self.serving and self.tp_axis in spec and self.fsdp_axis in spec:
            spec = [None if a == self.fsdp_axis else a for a in spec]
        return P(*spec)

    def param_specs(self, params: Any) -> Any:
        """PartitionSpec pytree matching a params (or m/v) pytree."""
        def leaf_spec(path, leaf):
            pstr = _path_str(path)
            name = pstr.rsplit("/", 1)[-1]
            if re.search(r"(ln|norm|scale)", name):
                return P()
            key = f"moe/{name}" if "/moe/" in f"/{pstr}/" and f"moe/{name}" in _ROLE_TABLE else name
            # shared experts inside MoE use the plain MLP rules
            if "/shared/" in f"/{pstr}/":
                key = name
            roles = _ROLE_TABLE.get(key)
            if roles is None:
                return P()
            return self._resolve(roles, leaf.shape)

        return jax.tree_util.tree_map_with_path(leaf_spec, params)

    # -------------------------------------------------------------- batch
    def _dp(self, size: int):
        """Batch sharding: largest prefix of dp axes that divides size."""
        axes = []
        prod = 1
        for a in self.dp_axes:
            if size % (prod * self.mesh_axes[a]) == 0:
                axes.append(a)
                prod *= self.mesh_axes[a]
        return tuple(axes) if axes else None

    def batch_specs(self, batch: Any) -> Any:
        def spec(leaf):
            b = self._dp(leaf.shape[0])
            return P(b, *([None] * (len(leaf.shape) - 1)))
        return jax.tree_util.tree_map(spec, batch)

    # -------------------------------------------------------------- caches
    def cache_specs(self, cfg: ModelConfig, caches: Any) -> Any:
        """Decode-cache specs: batch over dp; heads over tp if divisible,
        otherwise the sequence dim over tp (flash-decode style)."""
        tp = self.mesh_axes[self.tp_axis]

        def leaf_spec(path, leaf):
            name = _path_str(path).rsplit("/", 1)[-1]
            shape = leaf.shape  # leading dim is the stacked layer dim
            b = self._dp(shape[1])
            if name in ("k", "v", "xk", "xv"):
                _, _, S, kv, _ = shape
                if kv % tp == 0:
                    return P(None, b, None, self.tp_axis, None)
                if S % tp == 0:
                    return P(None, b, self.tp_axis, None, None)
                return P(None, b, None, None, None)
            if name == "ckv" or name == "k_rope":
                _, _, S, _ = shape
                if S % tp == 0:
                    return P(None, b, self.tp_axis, None)
                return P(None, b, None, None)
            if name == "conv":   # (L, B, dc-1, di)
                return P(None, b, None,
                         self.tp_axis if shape[3] % tp == 0 else None)
            if name == "h":      # (L, B, di, st)
                return P(None, b,
                         self.tp_axis if shape[2] % tp == 0 else None, None)
            return P(*([None] * len(shape)))

        return jax.tree_util.tree_map_with_path(leaf_spec, caches)

    # -------------------------------------------------------------- acts
    def act_spec(self, sp: bool = False) -> P:
        """Residual-stream constraint (B, S, D). ``sp`` adds Megatron-style
        sequence sharding over the model axis — scan-saved activation
        stacks shrink by the TP degree, buying fewer microbatches (and
        therefore fewer ZeRO-3 weight re-gathers) at the cost of per-layer
        sequence gather/scatter."""
        return P(self.dp_axes if self.dp_axes else None,
                 self.tp_axis if sp else None, None)

    def logits_spec(self, batch_size: int = 0) -> P:
        b = self._dp(batch_size) if batch_size else (self.dp_axes or None)
        return P(b, None, self.tp_axis)

    # -------------------------------------------------------------- helpers
    def named(self, mesh: Mesh, spec_tree: Any) -> Any:
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))
