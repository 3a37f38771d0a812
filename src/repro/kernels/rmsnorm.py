"""Pallas TPU fused RMSNorm.

Row-blocked over the token dimension; the full feature dim stays resident in
VMEM (d_model <= 8k => <= 32 KiB fp32 per row block — far under VMEM).
Single pass: mean-square, rsqrt, scale — one HBM read + one write.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.common import jax_compat as jc

DEFAULT_BLOCK_ROWS = 256


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * (1.0 + scale_ref[...].astype(jnp.float32))).astype(o_ref.dtype)


def rmsnorm_fwd(x, scale, eps: float = 1e-6, block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool = False):
    """x: (..., D); scale: (D,). Rows are flattened and tiled."""
    orig_shape = x.shape
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    br = min(block_rows, n)
    pad = (-n) % br
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    grid = (xf.shape[0] // br,)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        compiler_params=jc.tpu_compiler_params(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="rmsnorm_fwd",
    )(xf, scale)
    if pad:
        out = out[:n]
    return out.reshape(orig_shape)
