"""The JAX/Pallas surface the repo calls, in one place.

The repo pins one jax (``jax==0.9.0``, requirements.txt / pyproject.toml)
and targets its explicit-sharding API: ``jax.sharding.AxisType``,
``jax.make_mesh(..., axis_types=...)``, ``jax.set_mesh``,
``jax.sharding.get_abstract_mesh``, top-level ``jax.shard_map`` with
``check_vma``, ``pltpu.CompilerParams``, the ``jax.enable_x64`` scope and
the splash attention kernels of ``jax.experimental.pallas.ops.tpu``.
Callers go through this module instead of reaching into jax inline, so a
version bump is a one-file change (docs/compat.md).
"""
from __future__ import annotations

from typing import Sequence

import jax

AxisType = jax.sharding.AxisType
set_mesh = jax.set_mesh
get_abstract_mesh = jax.sharding.get_abstract_mesh
shard_map = jax.shard_map
axis_size = jax.lax.axis_size

tree_map = jax.tree.map


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None, axis_types=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` over ``devices`` (default: every visible device,
    which the mesh must then cover exactly)."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=axis_types, devices=devices)


def enable_x64():
    """The 64-bit scope (``with enable_x64(): ...``) the C4D detection
    kernels run under: float64 medians and z-scores, int64 keys."""
    return jax.enable_x64(True)


def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams`` (imported here so that importing this
    module never pulls in the Pallas machinery)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kwargs)


def splash_attention():
    """The splash attention package (``make_splash_mha``, ``BlockSizes``,
    ``CausalMask``, ``MultiHeadMask``), imported on first use."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa
    return sa
