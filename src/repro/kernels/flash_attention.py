"""Pallas TPU flash attention (forward).

TPU-native design (see DESIGN.md section 6):
  * Q/K/V live in HBM; each grid step streams one (block_q x d) query tile and
    one (block_k x d) KV tile into VMEM via BlockSpec.
  * Grid = (batch*kv_heads, q_blocks, kv_blocks); the kv dimension is the
    innermost (sequential/"arbitrary") axis so the online-softmax accumulator
    persists in VMEM scratch across kv steps.
  * All `group = H/Hkv` query heads sharing a kv head are processed in one
    tile, so the MXU matmul is (group*block_q, d) x (d, block_k) —
    hardware-aligned when block sizes are multiples of 128.
  * Causality is exploited by statically skipping fully-masked kv blocks.
    The sliding window arrives as an SMEM scalar (it can be a traced value —
    gemma2 alternates local/global inside a scanned layer stack), so window
    masking is done in-kernel; window *skipping* is only applied when the
    window is static.
  * fp32 accumulation; bf16 in/out supported.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import jax_compat as jc

NEG_INF = -2.3819763e38
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256


def _flash_kernel(win_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, logit_cap: float, block_q: int, block_k: int,
                  n_kv_blocks: int, causal: bool):
    """Grid point: (bh, qi, ki). win_ref: SMEM (1,) int32 sliding window."""
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    window = win_ref[0]

    def compute():
        q = q_ref[0].astype(jnp.float32)            # (group, bq, d)
        k = k_ref[0].astype(jnp.float32)            # (bk, d)
        s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                # (group, bq, bk)
        if logit_cap:
            s = jnp.tanh(s / logit_cap) * logit_cap
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos <= q_pos if causal else jnp.ones((block_q, block_k), bool)
        # logical_or, not a select: Mosaic cannot lower a select on the
        # SMEM scalar predicate over a bool vector
        mask = jnp.logical_and(
            mask, jnp.logical_or(window <= 0, k_pos > q_pos - window))
        s = jnp.where(mask[None], s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=-1)                  # (group, bq)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1)
        m_ref[...] = m_new
        v = v_ref[0].astype(jnp.float32)             # (bk, d)
        pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[..., None] + pv

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(compute)
    else:
        compute()

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, window=None, logit_cap: float = 0.0,
                        scale: float, block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K, causal: bool = True,
                        interpret: bool = False):
    """q: (B,S,H,D); k,v: (B,S,Hkv,D) -> (B,S,H,D).

    S must be a multiple of the block sizes (ops.py picks ones that divide
    S, or raises).  ``interpret=True`` runs the kernel body in the Pallas
    interpreter (CPU tests).
    ``window``: None/0 = full causal; int or traced int32 scalar = sliding.
    """
    b, s, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    nq, nk = s // block_q, s // block_k

    # (B,S,H,D) -> (B*Hkv, group, S, D); K/V -> (B*Hkv, S, D)
    qt = q.reshape(b, s, hkv, group, d).transpose(0, 2, 3, 1, 4).reshape(b * hkv, group, s, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)

    win = jnp.asarray(0 if window is None else window, jnp.int32).reshape(1)

    kernel = functools.partial(
        _flash_kernel, scale=scale, logit_cap=logit_cap, block_q=block_q,
        block_k=block_k, n_kv_blocks=nk, causal=causal)

    out = pl.pallas_call(
        kernel,
        grid=(b * hkv, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                       # window
            pl.BlockSpec((1, group, block_q, d), lambda bh, qi, ki: (bh, 0, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, group, block_q, d), lambda bh, qi, ki: (bh, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, group, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, block_q), jnp.float32),      # m
            pltpu.VMEM((group, block_q), jnp.float32),      # l
            pltpu.VMEM((group, block_q, d), jnp.float32),   # acc
        ],
        compiler_params=jc.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(win, qt, kt, vt)

    return out.reshape(b, hkv, group, s, d).transpose(0, 3, 1, 2, 4).reshape(b, s, h, d)
