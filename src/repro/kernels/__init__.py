"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel ships as a triple:
  <name>/<name>.py — pl.pallas_call + BlockSpec VMEM tiling (TPU target)
  <name>/ops.py    — jit'd public wrapper (padding, block choice)
  <name>/ref.py    — pure-jnp oracle used by the allclose sweeps

Kernels run compiled on the accelerator and under the Pallas interpreter
only where the call is lowered for the CPU (:func:`pallas_on_platform`).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax


def pallas_on_platform(kernel: Callable, *args, **kwargs):
    """Call ``kernel(*args, interpret=..., **kwargs)`` with interpret mode
    chosen by the platform the enclosing computation is lowered for: the
    interpreter on the CPU, the compiled kernel everywhere else.  Only the
    branch of that platform is lowered."""
    def branch(interpret: bool):
        return functools.partial(kernel, interpret=interpret, **kwargs)

    return jax.lax.platform_dependent(*args, cpu=branch(True),
                                      default=branch(False))
