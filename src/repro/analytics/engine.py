"""MapReduce-style analytics engine on a JAX mesh (the Hadoop/Spark stage).

The paper's Hadoop stages are fine-grained data-parallel map/shuffle/
reduce tasks over HDFS blocks. The TPU-native mapping (DESIGN.md):
  * a dataset is a sharded array (blocks = per-device shards, PilotData);
  * ``map`` is an element-wise shard-local computation (no comm);
  * ``reduce`` is a shard-local partial reduce + ``psum`` tree (the
    shuffle's all-to-one collapses into an all-reduce on ICI);
  * ``map_reduce`` fuses both, executed via ``shard_map`` over the
    pilot's data axis.

Two data paths, mirroring the paper's local-disk vs Lustre comparison:
  * data-local: compute where the shards already live (RP-YARN path);
  * global-reshard: gather/redistribute first (RP/Lustre path) — the
    engine records moved bytes via the PilotData registry.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import require_auto_axes, shard_map
from repro.core.dataplane import DataPlane, Link
from repro.spans import span


class AnalyticsEngine:
    def __init__(self, mesh: Mesh, data: Optional[DataPlane] = None,
                 axis: str = "data"):
        require_auto_axes(mesh, "AnalyticsEngine")
        self.mesh = mesh
        self.axis = axis
        self.data = data or DataPlane()
        self._exec_cache: dict[Any, Any] = {}

    # ------------------------------------------------------------- dataset
    def block_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.axis))

    def put(self, name: str, array: jax.Array | np.ndarray) -> None:
        """Register a dataset, sharded block-wise over the engine's mesh."""
        with span("engine.put") as sp:
            arr = jax.device_put(jnp.asarray(array), self.block_sharding())
            self.data.put(name, arr)
            sp.set_metadata(bytes=arr.nbytes)

    def get(self, name: str) -> jax.Array:
        return self.data.get(name).array

    # ------------------------------------------------------------ map/reduce
    def map_blocks(self, fn: Callable, name: str, out_name: str) -> jax.Array:
        """Shard-local map (Hadoop map phase; zero communication)."""
        x = self.ensure_local(name)
        mapped = shard_map(fn, mesh=self.mesh, in_specs=P(self.axis),
                           out_specs=P(self.axis), check_vma=False)(x)
        self.data.put(out_name, mapped)
        return mapped

    def map_reduce(self, map_fn: Callable, name: str, *,
                   extra_args: tuple = (), cache_key: Any = None) -> Any:
        """map + shuffle + reduce: per-shard partials psum'd over the mesh.

        ``map_fn(block, *extra_args) -> pytree of partial aggregates``;
        the reduce combiner is summation (sufficient for K-Means et al.;
        generalized combiners compose by encoding into sums).
        ``cache_key`` enables executor re-use across rounds (the paper's
        container re-use: iterative algorithms pay tracing/compile once).
        """
        with span("engine.map_reduce"):
            x = self.ensure_local(name)
            key = cache_key if cache_key is not None else id(map_fn)
            fn = self._exec_cache.get(key)
            if fn is None:
                def shard_fn(block, *args):
                    partial = map_fn(block, *args)
                    return jax.tree.map(
                        lambda t: jax.lax.psum(t, self.axis), partial)

                extra_specs = tuple(P() for _ in extra_args)
                fn = jax.jit(shard_map(
                    shard_fn, mesh=self.mesh,
                    in_specs=(P(self.axis),) + extra_specs,
                    out_specs=P(), check_vma=False))
                self._exec_cache[key] = fn
            return fn(x, *extra_args)

    # ----------------------------------------------------------- data paths
    def ensure_local(self, name: str) -> jax.Array:
        """Data-local path: reshard only if placement mismatches (and count
        the moved bytes if it does — the locality-vs-movement trade-off)."""
        pd = self.data.get(name)
        want = self.block_sharding()
        if pd.array.sharding == want:
            return pd.array
        return self.data.reshard_to(name, want, link=Link.ICI,
                                    reason="ensure-local")

    def global_reshard(self, name: str,
                       spool_dir: Optional[str] = None) -> jax.Array:
        """Global-FS path (Lustre analogue): per the paper, hybrid stages
        "involve persisting files and re-reading them" — the dataset is
        written out through the 'parallel filesystem' and re-read before
        re-blocking, vs the data-local path that computes on resident
        shards. Moved bytes recorded both ways.  The spool file goes to
        ``spool_dir``, else the temp directory (``TMPDIR``)."""
        import os
        import tempfile

        pd = self.data.get(name)
        host = np.asarray(pd.array)                    # device -> host
        fd, path = tempfile.mkstemp(dir=spool_dir, suffix=".pfs")
        try:
            with os.fdopen(fd, "wb") as f:             # persist ...
                np.save(f, host)
            self.data.record_moved(pd.nbytes, Link.GFS, "gfs-spool-write")
            reread = np.load(path)                     # ... and re-read
            self.data.record_moved(pd.nbytes, Link.GFS, "gfs-spool-read")
        finally:
            os.unlink(path)
        re_blocked = jax.device_put(reread, self.block_sharding())
        self.data.put(name, re_blocked)
        return re_blocked

    @property
    def moved_bytes(self) -> int:
        return self.data.moved_bytes
