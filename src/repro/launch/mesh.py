"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax init).
Mesh construction goes through repro.common.jax_compat.
"""
from __future__ import annotations

import math

import jax

from repro.common import jax_compat as jc


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jc.make_mesh(shape, axes,
                        axis_types=(jc.AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over the first ``pod * data * model`` local devices
    (tests / smoke runs); the host may hold more."""
    shape = (pod, data, model) if pod else (data, model)
    axes = ("pod", "data", "model") if pod else ("data", "model")
    return jc.make_mesh(shape, axes,
                        devices=jax.devices()[:math.prod(shape)],
                        axis_types=(jc.AxisType.Auto,) * len(axes))


# TPU v5e hardware constants (roofline targets; this container is CPU-only)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (intra-pod)
DCN_BW = 6.25e9                   # bytes/s per chip (cross-pod, 50 Gbit)
HBM_BYTES = 16 * 1024**3          # 16 GiB per chip
