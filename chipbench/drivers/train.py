"""Driver of dense-decoder training cells: the program's ``Trainer`` runs
``Trainer.train`` as a job does, with its per-step loss sync, the
``StepMonitor`` and checkpoints every ``checkpoint_every`` steps.

One ``Trainer.train`` call carries the whole run.  Its entry checkpoint and
its first ``reference_steps`` steps (the first of which compiles) are
set-up; the window opens at the start of the next step and closes at the
first step boundary at or after ``--seconds``.  A hook at each step
boundary, the ``injector`` that ``Trainer.train`` consults before every
step, keeps the clock: it reads the first steps' results for the
comparison, opens the window, and ends the call once the window is over.

After the window the program's state is freed and the plain float32
reference (``reference/smollm.py``) follows the same first steps from the
same seed and the same tokens; their losses, the first gradient's per-leaf
norms and the per-leaf norms of the parameters' change are compared.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time
from typing import Dict

import numpy as np

from chipbench import harness
from chipbench.reference import smollm as ref

#: program parameter tree paths -> reference leaf names
LEAVES = {
    "embed/table": "embed", "final_norm/scale": "final_norm",
    "ln1/scale": "layers/ln1", "attn/wq": "layers/wq", "attn/wk": "layers/wk",
    "attn/wv": "layers/wv", "attn/wo": "layers/wo", "ln2/scale": "layers/ln2",
    "mlp/wi_gate": "layers/gate", "mlp/wi_up": "layers/up",
    "mlp/wo": "layers/down",
}


class StopWindow(Exception):
    """Ends ``Trainer.train`` at the step boundary that closes the window."""


def program_config(cfg: dict, mix: dict, seed: int):
    from repro.common.config import (ModelConfig, ParallelConfig, RunConfig,
                                     ShapeSpec, TrainConfig)
    model = ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        tie_embeddings=cfg["tie_word_embeddings"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], act=cfg["hidden_act"])
    opt = cfg["optimizer"]
    train = TrainConfig(
        learning_rate=opt["learning_rate"], warmup_steps=opt["warmup_steps"],
        total_steps=opt["total_steps"], weight_decay=opt["weight_decay"],
        grad_clip_norm=opt["grad_clip_norm"], seq_len=mix["seq_len"],
        global_batch=mix["global_batch"],
        checkpoint_every=cfg["checkpoint_every"], seed=seed)
    run = RunConfig(model=model, parallel=ParallelConfig(**cfg["parallel"]),
                    train=train)
    return run, ShapeSpec(mix["name"], mix["seq_len"], mix["global_batch"], "train")


def _norm_fns():
    import jax
    import jax.numpy as jnp

    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    norms = jax.jit(lambda tree: jax.tree.map(norm, tree))
    diff_norms = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: norm(x.astype(jnp.float32) - y.astype(jnp.float32)), a, b))
    return norms, diff_norms


def by_leaf(tree) -> Dict[str, float]:
    """Program tree of scalars -> {reference leaf name: value}."""
    import jax
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        out[LEAVES["/".join(keys[-2:])]] = float(v)
    return out


class Clock:
    """The ``injector`` of ``Trainer.train``: called before every step."""

    def __init__(self, r: harness.Run, trainer, warm_steps: int, b1: float):
        self.r, self.trainer, self.warm = r, trainer, warm_steps
        self.b1 = b1
        self.norms, self.diff_norms = _norm_fns()
        self.p0 = trainer.params
        self.first_grad = self.change = None
        self.t_start = self.t_end = None
        self.steps = 0
        self._span = None

    def check(self, step: int):
        import jax
        tr = self.trainer
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if step == 1:
            mu = jax.tree.map(lambda m: m["mu"], tr.opt_state["m"],
                              is_leaf=lambda m: isinstance(m, dict) and "mu" in m)
            # the first moment after one step is (1 - b1) * clipped gradient
            self.first_grad = {k: v / (1.0 - self.b1)
                               for k, v in by_leaf(self.norms(mu)).items()}
        if step == self.warm:
            self.change = by_leaf(self.diff_norms(tr.params, self.p0))
            self.p0 = None
            self.r.end_setup()
            self.r.start_window()
            self.t_start = time.perf_counter()
        elif step > self.warm:
            self.steps = step - self.warm
            if time.perf_counter() - self.t_start >= self.r.seconds:
                self.t_end = time.perf_counter()
                raise StopWindow
        if self.r.trace and step >= self.warm:
            self._span = self.r.span("step")
            self._span.__enter__()
        return None


def _traced(r: harness.Run, name: str, fn):
    def wrapped(*a, **kw):
        with r.span(name):
            return fn(*a, **kw)
    return wrapped


def run(r: harness.Run) -> harness.Outcome:
    import jax
    from repro.train.trainer import Trainer
    cfg, mix = r.cell.config, r.cell.mix
    run_cfg, shape = program_config(cfg, mix, r.seed)
    mesh = None
    if mix["data"] > 1:
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(data=mix["data"])
    workdir = tempfile.mkdtemp(prefix="chipbench_ckpt_")
    try:
        trainer = Trainer(run_cfg, shape, workdir=workdir, mesh=mesh)
        if r.trace:
            trainer.pipeline.batch = _traced(r, "batch_build", trainer.pipeline.batch)
            trainer._step_fn = _traced(r, "step_dispatch", trainer._step_fn)
            trainer._save_checkpoint = _traced(r, "checkpoint_save",
                                               trainer._save_checkpoint)
        clock = Clock(r, trainer, cfg["reference_steps"], cfg["optimizer"]["b1"])
        try:
            trainer.train(10 ** 9, injector=clock)
        except StopWindow:
            pass
        finally:
            r.stop_window()
        trainer.ckpt.close()
        losses = list(trainer.report.losses)
        memory = r.memory_peak_bytes()
        clock.trainer = None
        del trainer
        gc.collect()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    window_s = clock.t_end - clock.t_start
    tokens = clock.steps * mix["global_batch"] * mix["seq_len"]
    r.facts.update(steps=clock.steps, tokens=tokens, window_s=window_s,
                   tokens_per_s=tokens / window_s,
                   counters={"steps_in_window": clock.steps})
    jax.clear_caches()
    checks = compare(cfg, mix, r.seed, losses, clock.first_grad, clock.change)
    finite = all(np.isfinite(losses))
    return harness.Outcome(
        e2e={"train_tokens_per_s": tokens / window_s}, checks=checks,
        attempted=clock.steps, failed=0 if finite else clock.steps,
        memory_peak_bytes=memory)


def compare(cfg: dict, mix: dict, seed: int, losses, first_grad, change,
            want: dict = None):
    """The three numbers the comparison reads, against the reference that
    follows the first ``reference_steps`` steps (or ``want``, given)."""
    n = cfg["reference_steps"]
    if want is None:
        batches = [ref.batch_tokens(seed, s, mix["global_batch"], mix["seq_len"],
                                    cfg["vocab_size"]) for s in range(n)]
        # the step reports the loss of its last microbatch
        b = mix["global_batch"]
        last = (b - b // cfg["parallel"]["microbatches"], b)
        want = ref.train_steps(cfg, cfg["optimizer"], seed, batches,
                               cfg["reference_rows"], loss_rows=last)
    lim = cfg["correct"]
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses[:n], want["losses"]))
    g_ref = want["first_grad"]
    med = float(np.median(list(g_ref.values())))
    grad_gap = max(abs(first_grad[k] - v) / max(v, med) for k, v in g_ref.items())
    # leaves whose gradient is nought to rounding move by round-off alone
    moving = [k for k, v in g_ref.items() if v >= 1e-3 * med]
    c_ref = want["change"]
    c_med = float(np.median([c_ref[k] for k in moving]))
    change_gap = max(abs(change[k] - c_ref[k]) / max(c_ref[k], c_med)
                     for k in moving)
    return [harness.Check("loss_rel_gap", loss_gap, lim["loss_rel_gap"]),
            harness.Check("first_grad_leaf_gap", grad_gap, lim["first_grad_leaf_gap"]),
            harness.Check("update_leaf_gap", change_gap, lim["update_leaf_gap"])]
