"""Shared building blocks: norms, rotary embeddings, MLPs, embeddings.

All modules are pure functions over explicit parameter pytrees.  Parameters
are created by ``init_*`` functions and consumed by the matching ``apply_*``.
Layer stacks store parameters with a leading ``(L, ...)`` axis for
scan-over-layers.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def truncated_normal(key, shape, std, dtype=jnp.float32):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.zeros((d,), dtype)}  # gemma-style (1 + scale)


def apply_rmsnorm(p, x, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + p["scale"].astype(jnp.float32))).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               inv_freq: Optional[np.ndarray] = None) -> jnp.ndarray:
    """x: (..., S, H, D) ; positions: (..., S) broadcastable.  ``inv_freq``
    (D/2,) replaces the plain ``rope_freqs(D, theta)`` (YaRN)."""
    d = x.shape[-1]
    if inv_freq is None:
        inv_freq = rope_freqs(d, theta)
    freqs = jnp.asarray(inv_freq, dtype=jnp.float32)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # (..., S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense / MLP
# ---------------------------------------------------------------------------

def init_dense(key, d_in: int, d_out: int, dtype=jnp.float32, std: Optional[float] = None):
    std = std if std is not None else d_in ** -0.5
    return {"w": truncated_normal(key, (d_in, d_out), std, dtype)}


def apply_dense(p, x):
    return x @ p["w"].astype(x.dtype)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": lambda x: jax.nn.gelu(x, approximate=True)}[name]


def init_glu_mlp(key, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi_gate": truncated_normal(k1, (d_model, d_ff), d_model ** -0.5, dtype),
        "wi_up": truncated_normal(k2, (d_model, d_ff), d_model ** -0.5, dtype),
        "wo": truncated_normal(k3, (d_ff, d_model), d_ff ** -0.5, dtype),
    }


def apply_glu_mlp(p, x, act: str = "silu"):
    g = act_fn(act)(x @ p["wi_gate"].astype(x.dtype))
    u = x @ p["wi_up"].astype(x.dtype)
    return (g * u) @ p["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int, dtype=jnp.float32):
    return {"table": truncated_normal(key, (vocab, d_model), d_model ** -0.5, dtype)}


def apply_embedding(p, tokens, scale_by_sqrt_dim: bool = False):
    x = jnp.take(p["table"], tokens, axis=0)
    if scale_by_sqrt_dim:
        x = x * jnp.asarray(x.shape[-1] ** 0.5, x.dtype)
    return x


def logits_from_embedding(p, x):
    """Tied read-out."""
    return x @ p["table"].astype(x.dtype).T


def softcap(x, cap: float):
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap
