"""Plain float32 reference of a Llama-style decoder's training steps
(SmolLM-135M: RMSNorm, rotary embeddings, grouped-query attention, SwiGLU,
tied embedding), in straightforward ``jax.numpy`` at
``jax.default_matmul_precision("highest")``.

It follows the published architecture, with these departures, each of
which is the configuration as run (``configs/smollm-135m.json``):

* RMSNorm multiplies by ``1 + scale`` with ``scale`` starting at zero (the
  published form multiplies by a weight starting at one: the same
  function), with ``eps`` from the configuration's ``rms_norm_eps``;
* rotary embeddings rotate the two halves of each head (not interleaved
  pairs), at ``rope_theta``;
* weights start from a truncated normal (two standard deviations) of
  standard deviation ``fan_in ** -0.5``, drawn from ``--seed`` with the
  key schedule the configuration states, and are held in bfloat16, the
  configuration's parameter type: each update is computed in float32 and
  rounded to bfloat16, as the configuration stores it;
* the optimizer is AdamW with global-norm clipping and linear warm-up.

The loss is the mean next-token cross entropy over every position but the
last.  Rows are processed in blocks, so that the reference fits on one
chip next to nothing else.  ``dot`` is the one matrix product every layer
uses; ``precision="fp8"`` rounds both of its operands to float8 (e4m3),
the control one step below the configuration's bfloat16.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def batch_tokens(seed: int, step: int, batch: int, seq_len: int,
                 vocab: int) -> np.ndarray:
    """The job's tokens for one step: uniform ids from a counter-based
    Philox stream keyed by the seed, counter ``step << 8`` (the data
    feed's own scheme, so that a restarted job reads the same rows)."""
    rng = np.random.default_rng(np.random.Philox(key=seed, counter=step << 8))
    return rng.integers(0, vocab, size=(batch, seq_len)).astype(np.int32)


def to_bf16(x):
    """Round float32 to the nearest bfloat16 value, kept as float32.  (A
    cast there and back may be folded away by the compiler, which is
    allowed to keep excess precision; this rounding is not.)"""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _tn(key, shape, std):
    """Truncated normal at two standard deviations, rounded to bfloat16."""
    return to_bf16(jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                               jnp.float32) * std)


def init_params(cfg: dict, seed: int) -> Dict:
    """The configuration's initial weights for ``seed``.

    Key schedule: the seed's key splits into four; the first draws the
    embedding, the fourth (folded with 0) splits into one key per layer;
    a layer's key splits into four, the first of which splits into the
    q, k, v and output projections' keys and the second into the gate,
    up and down projections' keys."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    ff, L, V = cfg["intermediate_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    keys = jax.random.split(jax.random.key(seed), 4)

    def layer(k):
        ks = jax.random.split(k, 4)
        qa = jax.random.split(ks[0], 4)
        ma = jax.random.split(ks[1], 3)
        return {
            "ln1": jnp.zeros((d,), jnp.float32),
            "wq": _tn(qa[0], (d, h * hd), d ** -0.5),
            "wk": _tn(qa[1], (d, hkv * hd), d ** -0.5),
            "wv": _tn(qa[2], (d, hkv * hd), d ** -0.5),
            "wo": _tn(qa[3], (h * hd, d), (h * hd) ** -0.5),
            "ln2": jnp.zeros((d,), jnp.float32),
            "gate": _tn(ma[0], (d, ff), d ** -0.5),
            "up": _tn(ma[1], (d, ff), d ** -0.5),
            "down": _tn(ma[2], (ff, d), ff ** -0.5),
        }

    layer_keys = jax.random.split(jax.random.fold_in(keys[3], 0), L)
    return {"embed": _tn(keys[0], (V, d), d ** -0.5),
            "final_norm": jnp.zeros((d,), jnp.float32),
            "layers": jax.vmap(layer)(layer_keys)}


def dot(a, b, precision: str):
    if precision == "fp8":
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + scale)


def _rope(x, theta):
    """x: (B, S, H, D); rotates the halves of each head."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, precision, x, p):
    b, s, d = x.shape
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    y = _rmsnorm(x, p["ln1"], eps)
    q = _rope(dot(y, p["wq"], precision).reshape(b, s, h, hd), cfg["rope_theta"])
    k = _rope(dot(y, p["wk"], precision).reshape(b, s, hkv, hd), cfg["rope_theta"])
    v = dot(y, p["wv"], precision).reshape(b, s, hkv, hd)
    q = q.reshape(b, s, hkv, h // hkv, hd)
    # scores (b, hkv, group, q, k), one kv head shared by its group
    scores = dot(q.transpose(0, 2, 3, 1, 4),
                 k.transpose(0, 2, 3, 1)[:, :, None], precision) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = dot(probs, v.transpose(0, 2, 1, 3)[:, :, None], precision)
    att = att.transpose(0, 3, 1, 2, 4).reshape(b, s, h * hd)
    x = x + dot(att, p["wo"], precision)
    y = _rmsnorm(x, p["ln2"], eps)
    ffn = dot(jax.nn.silu(dot(y, p["gate"], precision)) * dot(y, p["up"], precision),
              p["down"], precision)
    return x + ffn


def nll_sum(params, tokens, cfg, precision="f32"):
    """Sum of next-token negative log-likelihoods over a block of rows."""
    x = params["embed"][tokens]
    body = jax.checkpoint(lambda x, p: (_layer(cfg, precision, x, p), None))
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _rmsnorm(x, params["final_norm"], cfg["rms_norm_eps"])
    logits = dot(x[:, :-1], params["embed"].T, precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))


@partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _block_grad(params, tokens, cfg_items, precision):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(nll_sum)(params, tokens, cfg, precision)


def loss_and_grad(params, tokens: np.ndarray, cfg: dict, rows: int,
                  precision: str = "f32"):
    """Per-row summed losses and the gradient of the mean loss over the
    batch, ``rows`` rows at a time."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    b, s = tokens.shape
    row_nll, grad = [], None
    for i in range(0, b, rows):
        nll, g = _block_grad(params, jnp.asarray(tokens[i:i + rows]), items,
                             precision)
        row_nll.append(float(nll))
        grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
    n = b * (s - 1)
    return np.asarray(row_nll), jax.tree.map(lambda x: x / n, grad)


@jax.jit
def _adamw(params, grads, mu, nu, step, lr, hp):
    b1, b2, eps, wd, clip = hp
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    t = step + 1.0

    def upd(p, m, v):
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return to_bf16(p - lr * (u + wd * p))

    return jax.tree.map(upd, params, mu, nu), mu, nu, grads


def leaf_norms(tree) -> Dict[str, float]:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update({f"{k}/{kk}": vv for kk, vv in leaf_norms(v).items()})
        else:
            out[k] = float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
    return out


def train_steps(cfg: dict, opt: dict, seed: int, batches: List[np.ndarray],
                rows: int, precision: str = "f32", loss_rows=None) -> dict:
    """Follow the configuration's first ``len(batches)`` steps from ``seed``.

    Returns each step's mean loss over the rows ``loss_rows`` (a
    ``(start, stop)`` range of whole blocks; all rows by default), the
    per-leaf norms of the first gradient as the optimizer gets it (after
    clipping), and the per-leaf norms of the parameters' change over all
    the steps."""
    params = init_params(cfg, seed)
    p0 = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    hp = (opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
          opt["grad_clip_norm"])
    losses, first_grad = [], None
    for step, tokens in enumerate(batches):
        row_nll, grads = loss_and_grad(params, tokens, cfg, rows, precision)
        lo, hi = loss_rows or (0, tokens.shape[0])
        losses.append(float(row_nll[lo // rows:hi // rows].sum())
                      / ((hi - lo) * (tokens.shape[1] - 1)))
        lr = opt["learning_rate"] * min(step + 1, opt["warmup_steps"]) / opt["warmup_steps"]
        params, mu, nu, clipped = _adamw(params, grads, mu, nu, float(step), lr, hp)
        if first_grad is None:
            first_grad = leaf_norms(clipped)
        del grads, clipped
    change = leaf_norms(jax.tree.map(jnp.subtract, params, p0))
    return {"losses": losses, "first_grad": first_grad, "change": change}
