"""Causal flash attention with a backward pass: the splash kernel.

Training attention on the TPU goes through jax's splash attention
(``jax.experimental.pallas.ops.tpu.splash_attention``), which brings dq and
dk/dv backward kernels beside the forward one:

  * q, k and v are laid out ``(heads, S, D)`` and the kernel is vmapped over
    the batch; grouped query heads (``H % Hkv == 0``) and a value width
    other than the key width (MLA's 192 / 128) are the kernel's own,
  * one ``CausalMask`` per head: blocks wholly above the diagonal are
    skipped, forward and backward,
  * the softmax scale is folded into q in float32 before one cast back to
    q's dtype (the kernel takes no scale); scores, softmax and accumulation
    are float32, the soft cap is the kernel's ``attn_logits_soft_cap``.
    The forward kernel multiplies its float32 probabilities by v cast to
    float32, where the jnp path first rounds the probabilities to v's
    dtype; the backward kernels take bf16 operands.

The function knows nothing of meshes: a ``pallas_call`` is opaque to
GSPMD, so on a mesh the model layer runs it under ``shard_map``
(``models/attention.py``).

Blocks are 512 where the sequence allows, else the largest multiple of 128
below 512 that divides it; the sequence must be a multiple of 128.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common import jax_compat as jc

BLOCK = 512
LANES = 128


def block_size(s: int) -> int:
    """The kernel's block for a sequence of ``s`` (a multiple of 128)."""
    for b in range(BLOCK, 0, -LANES):
        if s % b == 0:
            return b
    raise ValueError(f"sequence length {s} is not a multiple of {LANES}")


def causal_attention(q, k, v, *, scale: float, logit_cap: float = 0.0,
                     interpret: bool = False):
    """Causal GQA attention. q: (B,S,H,D); k: (B,S,Hkv,D); v: (B,S,Hkv,Dv)
    -> (B,S,H,Dv)."""
    _, s, h, _ = q.shape
    kernel = _kernel(h, s, logit_cap, interpret)
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    out = jax.vmap(kernel)(_heads_first(q), _heads_first(k), _heads_first(v))
    return _heads_first(out)


def _kernel(h: int, s: int, logit_cap: float, interpret: bool):
    """The splash kernel of one row: ``h`` query heads of ``s`` positions,
    a causal mask per head, the same block for every pass."""
    sa = jc.splash_attention()
    blk = block_size(s)
    return sa.make_splash_mha(
        sa.MultiHeadMask([sa.CausalMask((s, s))] * h),
        block_sizes=sa.BlockSizes(
            block_q=blk, block_kv=blk, block_kv_compute=blk,
            block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
            block_q_dq=blk, block_kv_dq=blk),
        head_shards=1, q_seq_shards=1,
        attn_logits_soft_cap=logit_cap or None, interpret=interpret)


def _heads_first(x):
    """(B, S, H, D) <-> (B, H, S, D)."""
    return x.transpose(0, 2, 1, 3)

