"""Production meshes.

Functions, not module-level constants, so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS first; smoke tests
see the 1-CPU default).

Single-pod: (16, 16)    axes ("data", "model")      = 256 chips (one v5e pod)
Multi-pod:  (2, 16, 16) axes ("pod", "data", "model") = 512 chips
"""
from __future__ import annotations

from typing import Optional

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto-typed."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist, as a (data, model=1) mesh — CPU tests/drivers."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


# Hardware constants (TPU v5e-class chip — per-instruction roofline terms).
PEAK_FLOPS_BF16 = 197e12     # FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link
