"""Per-backend platform configuration (XLA flags) in one place.

Every launcher used to sprinkle its own ``os.environ`` pokes before the
first ``import jax``; this module centralizes them.  Call
:func:`configure` (idempotent) before any jax backend initialization —
XLA reads ``XLA_FLAGS`` exactly once, at first backend init, so flags set
later are silently ignored, and a flag it does not know aborts the
process.

Deliberately imports no jax at module level: the whole point is to run
*before* jax.  The backend is named by the environment (``JAX_PLATFORMS``
/ ``REPRO_PLATFORM``); with neither set nothing is configured, and JAX
picks the accelerator it finds.
"""
from __future__ import annotations

import os
from typing import Dict, Optional

# flags per backend; merged into XLA_FLAGS (existing user flags win)
_XLA_FLAGS: Dict[str, Dict[str, str]] = {
    "cpu": {
        # the dry-run pod mesh: 512 host devices on one CPU
        "--xla_force_host_platform_device_count": "512",
    },
    "gpu": {
        "--xla_gpu_enable_latency_hiding_scheduler": "true",
    },
}

_configured: Optional[str] = None


def backend() -> Optional[str]:
    """Target backend: REPRO_PLATFORM, else JAX_PLATFORMS' first entry,
    else None (not named: JAX chooses)."""
    plat = os.environ.get("REPRO_PLATFORM")
    if plat:
        return plat.lower()
    jp = os.environ.get("JAX_PLATFORMS", "")
    if jp:
        return jp.split(",")[0].strip().lower()
    return None


def _merge_xla_flags(new: Dict[str, str]) -> str:
    """Merge backend flags under existing XLA_FLAGS; flags the user
    already set keep their value."""
    existing = os.environ.get("XLA_FLAGS", "")
    present = {tok.split("=", 1)[0] for tok in existing.split() if tok}
    extra = [f"{k}={v}" for k, v in new.items() if k not in present]
    return " ".join(filter(None, [existing, " ".join(extra)]))


def configure(plat: Optional[str] = None, *,
              force: bool = False) -> Optional[str]:
    """Set the per-backend XLA flags.  Idempotent: a second call for the
    same backend is a no-op (XLA would ignore the changes anyway once a
    backend exists).  Returns the backend configured, None if none was
    named."""
    global _configured
    plat = plat or backend()
    if plat is None:
        return None
    plat = plat.lower()
    if _configured == plat and not force:
        return plat
    flags = _XLA_FLAGS.get(plat, {})
    if flags:
        os.environ["XLA_FLAGS"] = _merge_xla_flags(flags)
    _configured = plat
    return plat
