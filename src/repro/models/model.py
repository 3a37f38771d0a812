"""Model facade: build, loss, parameter accounting, input specs.

``input_specs`` returns ``jax.ShapeDtypeStruct`` stand-ins for every model
input (weak-type-correct, shardable, no device allocation) — used by the
multi-pod dry-run.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import ModelConfig, RunConfig, ShapeSpec
from repro.models.transformer import LM


def build_model(run: RunConfig, use_kernel: bool = True) -> LM:
    dtype = jnp.dtype(run.parallel.param_dtype)
    sp = run.parallel.attn_activation_sharding
    if sp == "auto":
        sp = "batch" if (run.model.n_kv_heads % 16 != 0
                         and run.model.mla is None) else "off"
    sp_attn = "" if sp == "off" else sp
    return LM(run.model, param_dtype=dtype, remat=run.parallel.remat,
              use_kernel=use_kernel, sp_attn=sp_attn)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

CE_CHUNK = 512


def _chunked_ce(model: LM, params, hidden, labels, chunk: int = CE_CHUNK):
    """Cross entropy computed in sequence chunks so the (B, S, vocab)
    logits tensor is never materialised (a 256x4096x256k fp32 tensor is
    ~1 TB).  The head matmul + log-softmax live inside the scan body."""
    b, s, d = hidden.shape
    c = min(chunk, s)
    n = (s + c - 1) // c
    pad = n * c - s
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
    hs = hidden.reshape(b, n, c, d).swapaxes(0, 1)
    ls = labels.reshape(b, n, c).swapaxes(0, 1)
    valid_len = s

    @jax.checkpoint
    def body(acc, args):
        # rematted: the (B, c, V) logits are recomputed per chunk in the
        # backward pass instead of being saved as scan residuals
        h, l, i = args
        logits = model.logits_fn(params, h)                 # (B, c, V) f32
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, l[..., None], axis=-1)[..., 0]
        # mask padded tail positions
        posn = i * c + jnp.arange(c)
        nll = jnp.where(posn[None, :] < valid_len, nll, 0.0)
        return acc + jnp.sum(nll), None

    from repro.common.scan_utils import scan as _scan
    total, _ = _scan(body, jnp.zeros((), jnp.float32),
                     (hs, ls, jnp.arange(n)))
    return total / (b * valid_len)


def lm_loss(model: LM, params, batch: Dict[str, jnp.ndarray]):
    """Next-token cross entropy plus the MoE layers' aux losses, summed over
    layers. Labels default to shifted tokens."""
    hidden, aux, _ = model.forward(params, batch, mode="train", head="none")
    if "labels" in batch:
        hidden_s, labels_s = hidden, batch["labels"]
    else:
        tokens = batch["tokens"]
        hidden_s, labels_s = hidden[:, :-1], tokens[:, 1:]
    loss = _chunked_ce(model, params, hidden_s, labels_s)
    metrics = {"ce_loss": loss}
    for k, v in aux.items():
        # each layer's aux loss is added whole; counts are only reported
        if k.endswith("_loss"):
            loss = loss + v
        metrics[k] = v
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Parameter accounting
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _abstract_params(cfg: ModelConfig, dtype_name: str = "bfloat16"):
    model = LM(cfg, param_dtype=jnp.dtype(dtype_name))
    return jax.eval_shape(lambda k: model.init(k), jax.random.key(0))


def count_params_analytic(cfg: ModelConfig, active_only: bool = False) -> int:
    """Exact parameter count from abstract init. ``active_only`` scales MoE
    expert tensors to the activated expert fraction (top_k / num_experts)."""
    tree = _abstract_params(cfg)
    total = 0

    def visit(path, leaf):
        nonlocal total
        n = int(np.prod(leaf.shape))
        if active_only and cfg.moe is not None:
            keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
            if any(k in ("wi_gate", "wi_up", "wo") for k in keys) and \
               any(k == "moe" for k in keys):
                n = int(n * cfg.moe.top_k / cfg.moe.num_experts)
        total += n

    jax.tree_util.tree_map_with_path(visit, tree)
    return total


# ---------------------------------------------------------------------------
# Inputs
# ---------------------------------------------------------------------------

def batch_shapes(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Shapes/dtypes for one step's inputs, as (shape, dtype) tuples."""
    b = shape.global_batch
    if shape.kind == "decode":
        s_in = 1
    else:
        s_in = shape.seq_len
    d: Dict[str, Any] = {}
    if cfg.family == "audio":
        d["embeddings"] = ((b, s_in, cfg.d_model), jnp.bfloat16)
        if shape.kind == "train":
            d["labels"] = ((b, s_in), jnp.int32)
    else:
        d["tokens"] = ((b, s_in), jnp.int32)
    if cfg.cross_attn_every:
        d["vision_embed"] = ((b, cfg.vision_seq_len, cfg.vision_d_model), jnp.bfloat16)
    return d


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, jax.ShapeDtypeStruct]:
    return {k: jax.ShapeDtypeStruct(s, dt) for k, (s, dt) in batch_shapes(cfg, shape).items()}


def synthetic_batch(cfg: ModelConfig, shape: ShapeSpec, seed: int = 0):
    """Concrete random batch (for smoke tests / examples)."""
    rng = np.random.default_rng(seed)
    out = {}
    for k, (shp, dt) in batch_shapes(cfg, shape).items():
        if dt == jnp.int32:
            out[k] = jnp.asarray(rng.integers(0, cfg.vocab_size, size=shp), jnp.int32)
        else:
            out[k] = jnp.asarray(rng.normal(0, 1, size=shp), dt)
    return out
