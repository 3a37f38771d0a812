"""Dispatch wrappers for the Pallas kernels.

``flash_attention`` / ``decode_attention`` / ``rmsnorm`` pick the execution
path from the platform:

  * TPU backend and ``use_kernel=True`` -> a compiled Pallas kernel; a
    sequence no kernel block size divides is an error, not a quiet detour.
    Causal attention with a static window (``None`` or 0) and a sequence
    that is a multiple of 128 runs the splash kernel, which has a backward
    pass (``splash_attention.py``).  Otherwise, where no gradient is taken
    (prefill) and values are as wide as keys, the forward-only
    ``flash_attention_fwd`` runs (a traced window: gemma2's alternation);
    training takes the jnp lowering instead,
  * anything else -> a memory-sane pure-jnp lowering (query-chunked
    attention), which is what the CPU smoke tests and the 512-host-device
    dry-run compile.

Each attention call counts, where it is traced, the path it took
(``attention.kernel_layers`` for the splash kernel,
``attention.flash_fwd_layers``, ``attention.chunked_layers``) and runs
under the ``attention`` named scope.

The Pallas interpreter is never chosen here: tests that want the kernel
body on CPU call the kernels directly with ``interpret=True``.
"""
from __future__ import annotations

import jax

from repro.common import tracing


def _kernel_path(use_kernel: bool) -> bool:
    return use_kernel and jax.default_backend() == "tpu"


def attention_path(q, k, v, *, window, use_kernel: bool, train: bool) -> str:
    """Which lowering ``flash_attention`` gives these operands: ``"splash"``,
    ``"flash_fwd"`` or ``"chunked"``, from the platform, whether the
    window is static, the shapes, and whether a gradient is taken
    (``train``): ``flash_attention_fwd`` has no backward pass."""
    if not _kernel_path(use_kernel):
        return "chunked"
    s = q.shape[1]
    if _static_full(window) and s % 128 == 0 and k.shape[1] == s:
        return "splash"
    # the forward-only kernel takes values as wide as the keys
    if train or v.shape[-1] != q.shape[-1]:
        return "chunked"
    return "flash_fwd"


def flash_attention(q, k, v, *, window=None, logit_cap: float = 0.0,
                    scale: float, train: bool, use_kernel: bool = True,
                    q_chunk: int = 1024):
    """Causal GQA attention. q: (B,S,H,D); k: (B,S,Hkv,D); v: (B,S,Hkv,Dv).
    ``train``: a gradient is taken through it."""
    path = attention_path(q, k, v, window=window, use_kernel=use_kernel,
                          train=train)
    with jax.named_scope("attention"):
        if path == "splash":
            tracing.count("attention.kernel_layers")
            from repro.kernels.splash_attention import causal_attention
            return causal_attention(q, k, v, scale=scale, logit_cap=logit_cap)
        if path == "flash_fwd":
            tracing.count("attention.flash_fwd_layers")
            from repro.kernels.flash_attention import flash_attention_fwd
            s = q.shape[1]
            block = 256 if s % 256 == 0 else _largest_block(s)
            return flash_attention_fwd(
                q, k, v, window=window, logit_cap=logit_cap, scale=scale,
                block_q=block, block_k=block)
        tracing.count("attention.chunked_layers")
        from repro.models.attention import chunked_causal_attention
        return chunked_causal_attention(
            q, k, v, window=window, logit_cap=logit_cap, scale=scale,
            q_chunk=q_chunk)


def _static_full(window) -> bool:
    """Whether the window is statically none (``None`` or a Python 0), not
    a traced or non-zero one."""
    return window is None or (isinstance(window, int) and window == 0)


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
