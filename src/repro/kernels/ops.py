"""Dispatch wrappers for the Pallas kernels.

``flash_attention`` / ``decode_attention`` / ``rmsnorm`` pick the execution
path from the platform:

  * TPU backend and ``use_kernel=True`` -> the compiled Pallas kernel; a
    sequence no kernel block size divides is an error, not a quiet detour,
  * anything else -> a memory-sane pure-jnp lowering (query-chunked
    attention), which is what the CPU smoke tests and the 512-host-device
    dry-run compile.

The Pallas interpreter is never chosen here: tests that want the kernel
body on CPU call the kernels directly with ``interpret=True``.
"""
from __future__ import annotations

import jax


def _kernel_path(use_kernel: bool) -> bool:
    return use_kernel and jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, window=None, logit_cap: float = 0.0,
                    scale: float, use_kernel: bool = True, q_chunk: int = 1024):
    """Causal GQA attention. q: (B,S,H,D); k,v: (B,S,Hkv,D)."""
    if _kernel_path(use_kernel):
        from repro.kernels.flash_attention import flash_attention_fwd
        s = q.shape[1]
        block = 256 if s % 256 == 0 else _largest_block(s)
        return flash_attention_fwd(
            q, k, v, window=window, logit_cap=logit_cap, scale=scale,
            block_q=block, block_k=block)
    from repro.models.attention import chunked_causal_attention
    return chunked_causal_attention(q, k, v, window=window, logit_cap=logit_cap,
                                    scale=scale, q_chunk=q_chunk)


def decode_attention(q, k_cache, v_cache, pos, *, window=None,
                     logit_cap: float = 0.0, scale: float, use_kernel: bool = True):
    """One-token decode against a KV cache. q: (B,1,H,D)."""
    if _kernel_path(use_kernel):
        from repro.kernels.decode_attention import decode_attention_fwd
        s = k_cache.shape[1]
        block = 512 if s % 512 == 0 else _largest_block(s)
        return decode_attention_fwd(
            q, k_cache, v_cache, pos, window=window, logit_cap=logit_cap,
            scale=scale, block_k=block)
    from repro.kernels import ref
    return ref.decode_attention(q, k_cache, v_cache, pos, window=window,
                                logit_cap=logit_cap, scale=scale)


def rmsnorm(x, scale, eps: float = 1e-6, use_kernel: bool = True):
    if _kernel_path(use_kernel):
        from repro.kernels.rmsnorm import rmsnorm_fwd
        return rmsnorm_fwd(x, scale, eps=eps)
    from repro.kernels import ref
    return ref.rmsnorm(x, scale, eps=eps)


def _largest_block(s: int) -> int:
    for b in (128, 64, 32, 16, 8):
        if s % b == 0:
            return b
    raise ValueError(
        f"sequence length {s} is not a multiple of 8, so no Pallas block "
        f"size divides it; pad the sequence or build the model with "
        f"use_kernel=False")
