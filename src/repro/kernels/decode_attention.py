"""Pallas TPU decode attention: one new token against a long KV cache.

This is the hot spot of the ``decode_32k`` / ``long_500k`` cells: entirely
memory-bound (the whole KV cache is read once per token), so the kernel's
job is to stream K/V blocks HBM->VMEM at full bandwidth while keeping the
online softmax in VMEM scratch.

Grid = (batch*kv_heads, kv_blocks); kv innermost (sequential).  The current
position arrives as an SMEM scalar; fully-out-of-range blocks only cost the
masked-lane compute of one tile (no extra HBM traffic beyond the stream).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import jax_compat as jc

NEG_INF = -2.3819763e38
DEFAULT_BLOCK_K = 512


def _decode_kernel(scalar_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale: float, logit_cap: float, block_k: int, n_kv_blocks: int):
    """scalar_ref: SMEM (2,) int32 = [pos, window]."""
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = scalar_ref[0]
    window = scalar_ref[1]
    k_start = ki * block_k

    q = q_ref[0].astype(jnp.float32)                 # (group, d)
    k = k_ref[0].astype(jnp.float32)                 # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if logit_cap:
        s = jnp.tanh(s / logit_cap) * logit_cap      # (group, bk)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)[0]
    mask = k_pos <= pos
    # logical_or, not a select: Mosaic cannot lower a select on the SMEM
    # scalar predicate over a bool vector
    mask = jnp.logical_and(mask, jnp.logical_or(window <= 0, k_pos > pos - window))
    s = jnp.where(mask[None], s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)       # (group,1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    v = v_ref[0].astype(jnp.float32)                 # (bk, d)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_fwd(q, k_cache, v_cache, pos, *, window=None,
                         logit_cap: float = 0.0, scale: float,
                         block_k: int = DEFAULT_BLOCK_K,
                         interpret: bool = False):
    """q: (B,1,H,D); caches: (B,S,Hkv,D); pos scalar int32 -> (B,1,H,D)."""
    b, _, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    group = h // hkv
    assert s % block_k == 0, (s, block_k)
    nk = s // block_k

    qt = q.reshape(b, hkv, group, d).reshape(b * hkv, group, d)
    kt = k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vt = v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    scalars = jnp.stack([jnp.asarray(pos, jnp.int32),
                         jnp.asarray(0 if window is None else window, jnp.int32)])

    kernel = functools.partial(_decode_kernel, scale=scale, logit_cap=logit_cap,
                               block_k=block_k, n_kv_blocks=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b * hkv, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, group, d), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, group, d), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, group, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
        compiler_params=jc.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention_fwd",
    )(scalars, qt, kt, vt)

    return out.reshape(b, 1, h, d)
