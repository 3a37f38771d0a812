"""jit-able train / serve steps.

``make_train_step`` builds the BSP superstep: microbatched gradient
accumulation (scan), optional int8-compressed cross-pod gradient reduction
(C4P-inspired: treat the pod axis as the scarce fabric), global-norm
clipping, schedule, and the optimizer update.  ``make_prefill_step`` /
``make_decode_step`` build the serving path.

All functions are pure and close over configs only — the Trainer (and the
dry-run) jit them with explicit in/out shardings.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.common import jax_compat as jc
from repro.common.config import RunConfig
from repro.models.model import lm_loss
from repro.optim import adamw
from repro.parallel.compression import ErrorFeedback, quantize_int8, dequantize_int8


def _split_microbatches(batch: Dict[str, jnp.ndarray], k: int):
    def f(x):
        b = x.shape[0]
        assert b % k == 0, (b, k)
        return x.reshape((k, b // k) + x.shape[1:])
    return jc.tree_map(f, batch)


def make_loss_fn(model):
    def loss_fn(params, batch):
        return lm_loss(model, params, batch)
    return loss_fn


def make_train_step(model, run: RunConfig, opt_cfg: adamw.OptimizerConfig,
                    mesh=None):
    """Returns step(params, opt_state, batch) -> (params, opt_state, metrics).

    opt_state may carry an "ef" residual tree when compression is on.
    """
    pcfg = run.parallel
    tcfg = run.train
    loss_fn = make_loss_fn(model)
    k = max(pcfg.microbatches, 1)

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    acc_dtype = jnp.dtype(pcfg.grad_accum_dtype)

    def accumulate(params, batch):
        # each row's index in the global batch rides along into its
        # microbatch (the MoE layers' protected rows)
        batch = dict(batch, rows=jnp.arange(
            jax.tree.leaves(batch)[0].shape[0], dtype=jnp.int32))
        if k == 1:
            return grads_of(params, batch)
        mb = _split_microbatches(batch, k)

        def body(carry, one):
            acc, loss_acc = carry
            loss, metrics, g = grads_of(params, one)
            acc = jc.tree_map(lambda a, b: a + b.astype(acc_dtype), acc, g)
            return (acc, loss_acc + loss), metrics

        from repro.common.scan_utils import scan as _scan
        zero = jc.tree_map(lambda p: jnp.zeros(p.shape, acc_dtype), params)
        (gsum, loss_sum), metrics = _scan(body, (zero, 0.0), mb)
        grads = jc.tree_map(lambda g: g / k, gsum)   # stays in acc_dtype
        # counts add up over the microbatches; the rest is the last one's
        metrics = jc.tree_map(
            lambda m: m.sum(0) if jnp.issubdtype(m.dtype, jnp.integer) else m[-1],
            metrics)
        return loss_sum / k, metrics, grads

    def compress_grads(grads, opt_state):
        """Error-feedback int8 quantisation of the gradient tree (the lossy
        stage); the cross-pod reduction itself happens in the int8 ring when
        running under shard_map, or via GSPMD otherwise."""
        resid = opt_state.get("ef")
        if resid is None:
            resid = ErrorFeedback.init(grads)

        def q(x):
            qi, s = quantize_int8(x)
            return dequantize_int8(qi, s).astype(x.dtype)

        grads, resid = ErrorFeedback.apply(grads, resid, q)
        return grads, resid

    def step(params, opt_state, batch):
        loss, metrics, grads = accumulate(params, batch)
        if pcfg.grad_compression == "int8":
            grads, resid = compress_grads(grads, opt_state)
            opt_state = dict(opt_state, ef=resid)
        grads, gnorm = adamw.clip_by_global_norm(grads, tcfg.grad_clip_norm)
        lr = adamw.warmup_cosine(opt_state["step"], base_lr=tcfg.learning_rate,
                                 warmup=tcfg.warmup_steps, total=tcfg.total_steps)
        ef = opt_state.get("ef")
        core_state = {kk: v for kk, v in opt_state.items() if kk != "ef"}
        params, core_state = adamw.apply_updates(opt_cfg, params, grads,
                                                 core_state, lr)
        if ef is not None:
            core_state = dict(core_state, ef=ef)
        metrics = dict(metrics, grad_norm=gnorm, lr=lr)
        return params, core_state, metrics

    return step


def make_prefill_step(model):
    def prefill(params, batch, cache):
        logits, _, cache = model.forward(params, batch, mode="prefill",
                                         cache=cache, head="last")
        return logits, cache
    return prefill


def make_decode_step(model):
    def decode(params, batch, cache, pos):
        logits, _, cache = model.forward(params, batch, mode="decode",
                                         cache=cache, pos=pos)
        return logits, cache
    return decode
