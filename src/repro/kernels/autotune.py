"""Block-size autotuner for the Pallas kernels + cached best-config registry.

The kernels ship with hardcoded block sizes (``bq=256, bk=256`` for
flash attention, fixed blocks for kmeans / mamba_scan) that leave
MXU/VMEM utilization on the table for shapes they were not tuned on.
This module sweeps divisor-snapped, VMEM-budget-filtered block-size
candidates through timed trials (the drive-one-cell shape of
``benchmarks/hillclimb.py``) and persists the winner in a JSON registry
keyed by ``(kernel, shape-bucket, backend, dtype)``.  The ``ops.py``
wrappers consult the registry by default — :func:`lookup` is a dict
probe, no timing — and fall back to the legacy constants on a miss.

Registry location: ``REPRO_AUTOTUNE_REGISTRY`` env var, else
``.cache/autotune.json`` inside the checkout (``repro.launch.cache``).
A corrupt registry file degrades to an empty one (defaults win) instead
of crashing the caller.

CLI (HPC-Wales-style automated environment tuning):

    PYTHONPATH=src python -m repro.kernels.autotune all
    PYTHONPATH=src python -m repro.kernels.autotune flash_attention \\
        --shapes '{"S_q": 2048, "hd": 128}' --reps 5
"""
from __future__ import annotations

import argparse
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

KERNELS = ("flash_attention", "kmeans", "mamba_scan")

# the shipped constants — the fallback when the registry has no entry,
# and the baseline every speedup is reported against
DEFAULTS: Dict[str, Dict[str, int]] = {
    "flash_attention": {"bq": 256, "bk": 256},
    # points and centroids a block of the lane-dense assignment kernel
    "kmeans": {"bn": 65536, "bk": 64},
    # bdi=512 at st=16 needs 16 MiB of VMEM for the a/b blocks alone once
    # st is padded to 128 lanes and double-buffered: v5e refuses it
    "mamba_scan": {"bdi": 256, "bs": 16},
}

# the registry's name of a kernel whose blocks changed meaning, so that an
# entry tuned for the old blocking is never applied to the new kernel
REGISTRY_NAMES = {"kmeans": "kmeans_planes"}

# ~16 MiB scoped VMEM per TPU core; keep headroom for the compiler's own
# scratch and the kernel body's temporaries
VMEM_BUDGET_BYTES = 12 * 2 ** 20

_BLOCKS = (64, 128, 256, 512, 1024, 2048)       # candidate tile edges
_SMALL_BLOCKS = (8, 16, 32, 64, 128)            # seq-chunk style edges


# --------------------------------------------------------------- snapping
def snap_block(n: int, b: int) -> int:
    """Largest divisor of ``n`` that is <= ``b`` (>= 1): autotuned and
    odd shapes both get a legal grid instead of a shape assert."""
    b = max(1, min(b, n))
    while n % b:
        b -= 1
    return b


def _bucket(n: int) -> int:
    """Shape bucket: next power of two >= n (shapes in one bucket share
    a tuned config — tuning is amortized across nearby sizes)."""
    p = 1
    while p < n:
        p *= 2
    return p


def shape_bucket(kernel: str, shape: Dict[str, int]) -> str:
    dims = sorted(shape.items())
    return ",".join(f"{k}{_bucket(int(v))}" for k, v in dims)


# --------------------------------------------------------------- registry
def _default_path() -> str:
    from repro.launch.cache import autotune_registry_path
    return autotune_registry_path()


class Registry:
    """JSON best-config store keyed ``kernel|shape-bucket|backend|dtype``.

    Tolerant by design: a corrupt or unreadable file loads as empty
    (``corrupt`` flag set) so kernels silently fall back to defaults —
    a stale cache must never take the hot path down.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path or _default_path()
        self.corrupt = False
        self._lock = threading.Lock()
        self._entries: Dict[str, Dict[str, Any]] = self._load()

    def _load(self) -> Dict[str, Dict[str, Any]]:
        try:
            with open(self.path) as f:
                data = json.load(f)
            if not isinstance(data, dict) or not all(
                    isinstance(v, dict) for v in data.values()):
                raise ValueError("registry root must be a dict of dicts")
            return data
        except FileNotFoundError:
            return {}
        except (ValueError, OSError):
            self.corrupt = True
            return {}

    @staticmethod
    def key(kernel: str, bucket: str, backend: str, dtype: str) -> str:
        name = REGISTRY_NAMES.get(kernel, kernel)
        return f"{name}|{bucket}|{backend}|{dtype}"

    def get(self, key: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self._entries.get(key)

    def put(self, key: str, entry: Dict[str, Any]) -> None:
        with self._lock:
            self._entries[key] = entry

    def save(self) -> None:
        with self._lock:
            entries = dict(self._entries)
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(entries, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


_default_registry: Optional[Registry] = None
_registry_lock = threading.Lock()


def default_registry(reload: bool = False) -> Registry:
    """Process-wide registry the ops wrappers probe (lazy-loaded)."""
    global _default_registry
    with _registry_lock:
        if (_default_registry is None or reload
                or _default_registry.path != _default_path()):
            _default_registry = Registry()
        return _default_registry


def backend_tag() -> str:
    """Registry backend axis: the jax platform, suffixed where kernels
    run under the Pallas interpreter — on the CPU, the same rule as
    :func:`repro.kernels.pallas_on_platform` (interpret timings must
    never be mistaken for compiled-TPU timings)."""
    import jax
    tag = jax.default_backend()
    return tag + "+interpret" if tag == "cpu" else tag


def lookup(kernel: str, shape: Dict[str, int],
           dtype: Any) -> Optional[Dict[str, int]]:
    """Cheap best-config probe for the ops wrappers: dict lookup on the
    in-memory registry, None on miss (caller falls back to DEFAULTS)."""
    import numpy as np
    reg = default_registry()
    if not len(reg):
        return None
    key = Registry.key(kernel, shape_bucket(kernel, shape), backend_tag(),
                       np.dtype(dtype).name)
    entry = reg.get(key)
    return dict(entry["config"]) if entry else None


# ------------------------------------------------------------- candidates
def _f32(nelem: float) -> float:
    return 4.0 * nelem


def _tile_f32(rows: int, cols: int) -> float:
    """VMEM bytes of an f32 (rows, cols) tile as the TPU lays it out: the
    minor dim padded to 128 lanes, the second-minor to 8 sublanes."""
    return _f32(-(-rows // 8) * 8 * (-(-cols // 128) * 128))


def candidates_flash(S_q: int, S_k: int, hd: int,
                     budget: int = VMEM_BUDGET_BYTES
                     ) -> List[Dict[str, int]]:
    """(bq, bk) grid: divisor-snapped to the sequence lengths, filtered
    by the kernel's VMEM working set (q/k/v/o blocks + f32 scratch)."""
    out, seen = [], set()
    for bq_w in _BLOCKS:
        for bk_w in _BLOCKS:
            bq = snap_block(S_q, bq_w)
            bk = snap_block(S_k, bk_w)
            vmem = (_f32(bq * hd)            # q block
                    + 2 * _f32(bk * hd)      # k, v blocks
                    + _f32(bq * hd)          # o block
                    + _f32(2 * bq)           # m, l scratch
                    + _f32(bq * hd))         # acc scratch
            if vmem > budget or (bq, bk) in seen:
                continue
            seen.add((bq, bk))
            out.append({"bq": bq, "bk": bk})
    return out


def candidates_kmeans(n: int, k: int, d: int,
                      budget: int = VMEM_BUDGET_BYTES
                      ) -> List[Dict[str, int]]:
    """(bn, bk) grid for the assignment kernel, as ``resolve_blocks`` lands
    each pair on this shape.  The VMEM estimate counts the double-buffered
    blocks: ``d`` coordinate planes of (bn/128, 128) points, the 1-D idx
    and distance outputs, and the centroids' scalars (SMEM, counted here
    too as headroom)."""
    from repro.kernels.kmeans import kmeans as km_kernel, ops as km
    out, seen = [], set()
    for bn_w in (4096, 16384, 65536, 262144):
        for bk_w in (16, 32, 64, 128):
            bn, bk = km.resolve_blocks(n, k, d, None, bn_w, bk_w)
            vmem = 2 * (d * _tile_f32(bn // 128, 128)   # point planes
                        + 2 * _f32(bn)                  # idx, distance
                        + _f32(km_kernel.centroid_stride(d, bk)))
            if vmem > budget or (bn, bk) in seen:
                continue
            seen.add((bn, bk))
            out.append({"bn": bn, "bk": bk})
    return out


def candidates_mamba(S: int, di: int, st: int,
                     budget: int = VMEM_BUDGET_BYTES
                     ) -> List[Dict[str, int]]:
    """(bdi, bs) grid: bdi snapped to d_inner divisors, bs to sequence
    divisors (the unrolled time loop caps bs — past ~128 the kernel
    body explodes).  The VMEM estimate counts what the compiler
    allocates: ``st`` padded to 128 lanes, and two buffers for every
    pipelined block (inputs and outputs); only the h scratch is single."""
    out, seen = [], set()
    for bdi_w in _BLOCKS:
        for bs_w in _SMALL_BLOCKS:
            bdi = snap_block(di, bdi_w)
            bs = snap_block(S, bs_w)
            vmem = (2 * (2 * bs * _tile_f32(bdi, st)   # a, b blocks
                         + _tile_f32(bs, st)           # C block
                         + _tile_f32(bdi, st)          # h0 block
                         + _tile_f32(bs, bdi)          # y block
                         + _tile_f32(bdi, st))         # h_out block
                    + _tile_f32(bdi, st))              # h scratch
            if vmem > budget or (bdi, bs) in seen:
                continue
            seen.add((bdi, bs))
            out.append({"bdi": bdi, "bs": bs})
    return out


# ----------------------------------------------------------- timed trials
BENCH_SHAPES: Dict[str, Dict[str, int]] = {
    # representative sizes: flash at the serving sequence length, kmeans
    # at the paper's mid scenario, mamba at the hybrid-arch inner width
    "flash_attention": {"B": 1, "H": 4, "S_q": 1024, "S_k": 1024, "hd": 64},
    "kmeans": {"n": 8192, "k": 64, "d": 4},
    "mamba_scan": {"B": 2, "S": 256, "di": 64, "st": 16},
}


def _time_call(fn, reps: int) -> float:
    """Warm up (compile + first run), then average ``reps`` timed calls
    — every output shape is blocked on, tuple or not."""
    import jax
    jax.block_until_ready(fn())
    t0 = time.monotonic()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.monotonic() - t0) / reps


def _make_cell(kernel: str, shape: Dict[str, int], dtype):
    """Drive-one-cell closure (hillclimb.py's shape): returns
    ``run(config) -> timed callable`` plus the candidate list."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(0)
    if kernel == "flash_attention":
        from repro.kernels.flash_attention import ops as fa
        B, H = shape.get("B", 1), shape.get("H", 4)
        S_q, S_k, hd = shape["S_q"], shape.get("S_k", shape["S_q"]), shape["hd"]
        q = jnp.asarray(rng.normal(size=(B, S_q, H, hd)), dtype) * 0.3
        k = jnp.asarray(rng.normal(size=(B, S_k, H, hd)), dtype) * 0.3
        v = jnp.asarray(rng.normal(size=(B, S_k, H, hd)), dtype)
        cands = candidates_flash(S_q, S_k, hd)

        def run(cfg):
            return lambda: fa.attention(q, k, v, bq=cfg["bq"], bk=cfg["bk"])
        return run, cands

    if kernel == "kmeans":
        from repro.kernels.kmeans import ops as km
        n, k_, d = shape["n"], shape["k"], shape["d"]
        p = jnp.asarray(rng.normal(size=(n, d)), dtype)
        c = jnp.asarray(rng.normal(size=(k_, d)), dtype)
        cands = candidates_kmeans(n, k_, d)

        def run(cfg):
            return lambda: km.assign(p, c, bn=cfg["bn"], bk=cfg["bk"])
        return run, cands

    if kernel == "mamba_scan":
        from repro.kernels.mamba_scan import ops as ms
        B, S, di, st = shape["B"], shape["S"], shape["di"], shape["st"]
        a = jnp.asarray(rng.uniform(0.8, 0.99, (B, S, di, st)), dtype)
        b = jnp.asarray(rng.normal(size=(B, S, di, st)), dtype) * 0.1
        C = jnp.asarray(rng.normal(size=(B, S, st)), dtype)
        h0 = jnp.zeros((B, di, st), dtype)
        cands = candidates_mamba(S, di, st)

        def run(cfg):
            return lambda: ms.scan(a, b, C, h0, bdi=cfg["bdi"], bs=cfg["bs"])
        return run, cands

    raise ValueError(f"unknown kernel {kernel!r}; valid: {KERNELS}")


def _resolve_default(kernel: str, shape: Dict[str, int]) -> Dict[str, int]:
    """The shipped constants as they would actually land on this shape
    (after the wrappers' min/snap) — the fair speedup baseline."""
    d = dict(DEFAULTS[kernel])
    if kernel == "flash_attention":
        d["bq"] = snap_block(shape["S_q"], d["bq"])
        d["bk"] = snap_block(shape.get("S_k", shape["S_q"]), d["bk"])
    elif kernel == "mamba_scan":
        d["bdi"] = snap_block(shape["di"], d["bdi"])
        d["bs"] = snap_block(shape["S"], d["bs"])
    elif kernel == "kmeans":
        from repro.kernels.kmeans import ops as km
        d["bn"], d["bk"] = km.resolve_blocks(shape["n"], shape["k"],
                                             shape["d"], None, d["bn"],
                                             d["bk"])
    return d


def autotune(kernel: str, shape: Optional[Dict[str, int]] = None, *,
             dtype=None, reps: int = 3, registry: Optional[Registry] = None,
             force: bool = False, max_candidates: Optional[int] = None
             ) -> Dict[str, Any]:
    """Tune one kernel at one shape; persist the winner.

    Returns ``{"config", "trials", "cached", "key", "speedup_vs_default",
    ...}``.  A registry hit short-circuits with ``trials == 0`` unless
    ``force`` — re-timing on every process start would defeat the cache.
    """
    import jax.numpy as jnp
    import numpy as np
    dtype = dtype or jnp.float32
    shape = {**BENCH_SHAPES[kernel], **(shape or {})}
    # `registry or ...` would be wrong here: an EMPTY Registry is falsy
    reg = registry if registry is not None else default_registry()
    key = Registry.key(kernel, shape_bucket(kernel, shape), backend_tag(),
                       np.dtype(dtype).name)
    hit = reg.get(key)
    if hit is not None and not force:
        return {**hit, "key": key, "trials": 0, "cached": True}

    run, cands = _make_cell(kernel, shape, dtype)
    default_cfg = _resolve_default(kernel, shape)
    if default_cfg not in cands:
        cands = [default_cfg] + cands      # the winner is never worse
    if max_candidates is not None and len(cands) > max_candidates:
        # keep the default + an even spread (smoke runs stay bounded)
        keep = [default_cfg]
        stride = max(1, len(cands) // max_candidates)
        keep += [c for c in cands[::stride] if c != default_cfg]
        cands = keep[:max_candidates + 1]

    timings: List[Tuple[float, Dict[str, int]]] = []
    for cfg in cands:
        timings.append((_time_call(run(cfg), reps), cfg))
    best_t, best_cfg = min(timings, key=lambda tc: tc[0])
    default_t = next(t for t, c in timings if c == default_cfg)
    entry = {
        "config": best_cfg,
        "default_config": default_cfg,
        "best_s": best_t,
        "default_s": default_t,
        "speedup_vs_default": default_t / max(best_t, 1e-12),
        "shape": shape,
        "n_candidates": len(cands),
        "reps": reps,
    }
    reg.put(key, entry)
    reg.save()
    return {**entry, "key": key, "trials": len(cands), "cached": False}


# -------------------------------------------------------------------- CLI
def main(argv: Optional[Sequence[str]] = None) -> None:
    ap =argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("kernel", choices=list(KERNELS) + ["all"],
                    help="kernel family to tune (or 'all')")
    ap.add_argument("--shapes", default=None, metavar="JSON",
                    help="shape overrides, e.g. '{\"S_q\": 2048}'")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--registry", default=None,
                    help="registry path (default: REPRO_AUTOTUNE_REGISTRY "
                         "or .cache/autotune.json in the checkout)")
    ap.add_argument("--force", action="store_true",
                    help="re-time even on a registry hit")
    args = ap.parse_args(argv)

    import jax.numpy as jnp
    dtype = jnp.dtype(args.dtype)
    shape = json.loads(args.shapes) if args.shapes else None
    reg = Registry(args.registry) if args.registry else default_registry()
    kernels = KERNELS if args.kernel == "all" else (args.kernel,)
    for kern in kernels:
        rec = autotune(kern, shape, dtype=dtype, reps=args.reps,
                       registry=reg, force=args.force)
        src = "cache" if rec["cached"] else f"{rec['trials']} trials"
        print(f"{kern}: {rec['config']} "
              f"({rec['speedup_vs_default']:.2f}x vs default "
              f"{rec['default_config']}, {src})")
    print(f"registry: {reg.path} ({len(reg)} entries)")


if __name__ == "__main__":
    main()
