"""Driver of MoE training cells (DeepSeek-V2-Lite): the program's
``Trainer`` runs ``Trainer.train`` as ``drivers/train.py`` runs a dense
decoder, with the per-step loss sync, the ``StepMonitor`` and checkpoints,
on a model whose expert layers hold the configuration's share of the
experts on a fixed row budget.

The clock is ``drivers/train.py``'s, with these differences: it takes
the program's ``moe.rows_kept`` / ``moe.rows_dropped`` counters at the
start and end of the window and puts their differences in the result
line's ``counters``; the parameters' change over the first steps is taken
on the host, so that no copy of the first parameters returns to the chip
just before the window; the set-up's garbage is collected before the
window opens; and each step's wall time in the window goes to standard
error.  A traced run reads the compiled step's text after the window and
records, for each ``moe.*`` / ``mla.*`` named scope, the names of the
compiled ops under it (``facts["scope_ops"]``), so that readers can select
those ops' device time from the trace.

After the window the plain float32 reference (``reference/deepseek_v2.py``)
follows the same first steps from the same seed and tokens, and the
losses, the first gradient's per-leaf norms and the per-leaf norms of the
parameters' change are compared as ``drivers/train.py`` compares them.
"""
from __future__ import annotations

import gc
import re
import shutil
import sys
import tempfile
import time
from typing import Dict, Iterable, Set

import numpy as np

from chipbench import harness
from chipbench.drivers import train
from chipbench.reference import deepseek_v2 as ref

#: named scopes of the program whose compiled ops a traced run records
SCOPES = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine", "mla.attend")
#: program counters whose window deltas go into the result line
COUNTERS = ("moe.rows_kept", "moe.rows_dropped")
#: what the program implements of the published configuration; anything
#: else is a configuration this driver cannot run
FIXED = {"scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
         "topk_group": 1, "routed_scaling_factor": 1, "moe_layer_freq": 1,
         "attention_bias": False, "hidden_act": "silu", "q_lora_rank": None,
         "tie_word_embeddings": False, "seq_aux": True}

#: program parameter tree paths -> reference leaf names
_SEGMENT = {"segments/0/unit/0": "dense_layers", "segments/1/unit/0": "moe_layers"}


def program_config(cfg: dict, mix: dict, seed: int):
    from repro.common.config import (MLAConfig, ModelConfig, MoEConfig,
                                     ParallelConfig, RunConfig, ShapeSpec,
                                     TrainConfig, YarnConfig)
    from repro.models.moe import BUDGET_TILE
    wrong = {k: cfg.get(k) for k, v in FIXED.items() if cfg.get(k) != v}
    budget = cfg["expert_budget"]
    if wrong or budget["rows_multiple"] != BUDGET_TILE:
        raise harness.BenchError(
            f"the program does not implement {wrong or budget} of {cfg['name']}")
    rs = cfg["rope_scaling"]
    model = ModelConfig(
        name=cfg["name"], family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], tie_embeddings=False,
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        act=cfg["hidden_act"], first_k_dense=cfg["first_k_dense_replace"],
        mla=MLAConfig(
            kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=0,
            rope_head_dim=cfg["qk_rope_head_dim"],
            nope_head_dim=cfg["qk_nope_head_dim"], v_head_dim=cfg["v_head_dim"],
            yarn=YarnConfig(
                factor=rs["factor"],
                original_max_position_embeddings=rs["original_max_position_embeddings"],
                beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
                mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"])),
        moe=MoEConfig(
            num_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
            d_ff_expert=cfg["moe_intermediate_size"],
            num_shared_experts=cfg["n_shared_experts"],
            norm_topk_prob=cfg["norm_topk_prob"], seq_aux=cfg["seq_aux"],
            load_balance_loss=cfg["aux_loss_alpha"], router_z_loss=0.0,
            capacity_factor=budget["device_capacity_factor"],
            expert_start=cfg["expert_start"], experts_held=cfg["n_routed_experts"],
            protected_every=budget["protected_every"]))
    opt = cfg["optimizer"]
    tcfg = TrainConfig(
        learning_rate=opt["learning_rate"], warmup_steps=opt["warmup_steps"],
        total_steps=opt["total_steps"], weight_decay=opt["weight_decay"],
        grad_clip_norm=opt["grad_clip_norm"], seq_len=mix["seq_len"],
        global_batch=mix["global_batch"],
        checkpoint_every=cfg["checkpoint_every"], seed=seed)
    run = RunConfig(model=model, parallel=ParallelConfig(**cfg["parallel"]),
                    train=tcfg)
    return run, ShapeSpec(mix["name"], mix["seq_len"], mix["global_batch"], "train")


def by_leaf(tree) -> Dict[str, float]:
    """Program tree of scalars -> {reference leaf name: value}."""
    import jax
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        for prefix, ref_name in _SEGMENT.items():
            if name.startswith(prefix + "/"):
                name = ref_name + name[len(prefix):]
        out[name] = float(v)
    return out


def scope_ops(hlo_text: str, scopes: Iterable[str] = SCOPES) -> Dict[str, Set[str]]:
    """The compiled ops under each named scope: every instruction of the
    compiled module's text whose ``op_name`` metadata holds the scope (its
    forward ops, their recomputation and their transposes).  The TPU
    compiler's grouped-product kernels keep no scope (``op_name`` is
    ``ragged-dot-none`` or ``ragged-dot-metadata``); the expert layer is
    the only grouped product in the step, so they count as ``moe.experts``."""
    out: Dict[str, Set[str]] = {s: set() for s in scopes}
    line_re = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"")
    for line in hlo_text.splitlines():
        m = line_re.match(line)
        if not m:
            continue
        name, op_name = m.groups()
        if op_name.startswith("ragged-dot") and "moe.experts" in out:
            out["moe.experts"].add(name)
        for s in scopes:
            if s in op_name:
                out[s].add(name)
    return out


def host_change_norms(now, before) -> Dict[str, float]:
    """Per-leaf norm of ``now - before`` (host trees of equal structure),
    in float32 with a float64 sum, by reference leaf name."""
    import jax

    def norm(a, b):
        d = np.asarray(a, np.float32) - np.asarray(b, np.float32)
        return float(np.sqrt(np.sum(np.square(d), dtype=np.float64)))

    return by_leaf(jax.tree.map(norm, now, before))


class Clock(train.Clock):
    """``drivers/train.py``'s clock, with the MoE row counters, this
    model's leaf names, the change taken on the host and the window's step
    times."""

    def __init__(self, *a, **kw):
        import jax
        super().__init__(*a, **kw)
        # the first parameters wait on the host, not beside the step's
        # working set on the chip
        self.p0 = jax.device_get(self.p0)
        self.counts0 = self.counts = None
        self.marks = []

    def check(self, step: int):
        import jax
        from repro.common import tracing
        tr = self.trainer
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if step == 1:
            mu = jax.tree.map(lambda m: m["mu"], tr.opt_state["m"],
                              is_leaf=lambda m: isinstance(m, dict) and "mu" in m)
            self.first_grad = {k: v / (1.0 - self.b1)
                               for k, v in by_leaf(self.norms(mu)).items()}
        if step == self.warm:
            self.change = host_change_norms(jax.device_get(tr.params), self.p0)
            self.p0 = None
            gc.collect()
            print(f"window opens at {time.time():.3f} (epoch s)", file=sys.stderr)
            self.r.end_setup()
            self.r.start_window()
            self.counts0 = tracing.counters()
            self.t_start = time.perf_counter()
            self.marks = [self.t_start]
        elif step > self.warm:
            self.steps = step - self.warm
            self.marks.append(time.perf_counter())
            if self.marks[-1] - self.t_start >= self.r.seconds:
                self.t_end = self.marks[-1]
                print("window step seconds: " + " ".join(
                    f"{b - a:.4f}" for a, b in zip(self.marks, self.marks[1:])),
                    file=sys.stderr)
                now = tracing.counters()
                self.counts = {c: now.get(c, 0) - self.counts0.get(c, 0)
                               for c in COUNTERS}
                raise train.StopWindow
        if self.r.trace and step >= self.warm:
            self._span = self.r.span("step")
            self._span.__enter__()
        return None


def run(r: harness.Run) -> harness.Outcome:
    import jax
    from repro.train.trainer import Trainer
    cfg, mix = r.cell.config, r.cell.mix
    run_cfg, shape = program_config(cfg, mix, r.seed)
    workdir = tempfile.mkdtemp(prefix="chipbench_ckpt_")
    try:
        trainer = Trainer(run_cfg, shape, workdir=workdir)
        step_fn = trainer._step_fn
        if r.trace:
            trainer.pipeline.batch = train._traced(r, "batch_build", trainer.pipeline.batch)
            trainer._step_fn = train._traced(r, "step_dispatch", trainer._step_fn)
            trainer._save_checkpoint = train._traced(r, "checkpoint_save",
                                                     trainer._save_checkpoint)
        clock = Clock(r, trainer, cfg["reference_steps"], cfg["optimizer"]["b1"])
        try:
            trainer.train(10 ** 9, injector=clock)
        except train.StopWindow:
            pass
        finally:
            r.stop_window()
        trainer.ckpt.close()
        if r.trace:
            # the step as compiled (from the compilation cache): its ops'
            # names under each scope
            from repro.common import jax_compat as jc
            batch = {k: jax.numpy.asarray(v) for k, v in trainer.pipeline.batch(0).items()}
            with jc.set_mesh(trainer.mesh):
                text = step_fn.lower(trainer.params, trainer.opt_state,
                                     batch).compile().as_text()
            r.facts["scope_ops"] = {s: sorted(ops) for s, ops in scope_ops(text).items()}
        losses = list(trainer.report.losses)
        memory = r.memory_peak_bytes()
        clock.trainer = None
        del trainer, step_fn
        gc.collect()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    window_s = clock.t_end - clock.t_start
    tokens = clock.steps * mix["global_batch"] * mix["seq_len"]
    r.facts.update(steps=clock.steps, tokens=tokens, window_s=window_s,
                   tokens_per_s=tokens / window_s,
                   counters={"steps_in_window": clock.steps, **clock.counts})
    jax.clear_caches()
    checks = compare(cfg, mix, r.seed, losses, clock.first_grad, clock.change)
    finite = all(np.isfinite(losses))
    return harness.Outcome(
        e2e={"train_tokens_per_s": tokens / window_s}, checks=checks,
        attempted=clock.steps, failed=0 if finite else clock.steps,
        memory_peak_bytes=memory)


def reference(cfg: dict, mix: dict, seed: int, precision: str = "f32") -> dict:
    """The reference's first ``reference_steps`` steps from ``seed``."""
    batches = [ref.batch_tokens(seed, s, mix["global_batch"], mix["seq_len"],
                                cfg["vocab_size"])
               for s in range(cfg["reference_steps"])]
    return ref.train_steps(cfg, cfg["optimizer"], seed, batches,
                           cfg["parallel"]["microbatches"], precision)


def compare(cfg: dict, mix: dict, seed: int, losses, first_grad, change,
            want: dict = None):
    """``drivers/train.py``'s three numbers, against this model's reference
    (or ``want``, given)."""
    if want is None:
        want = reference(cfg, mix, seed)
    return train.compare(cfg, mix, seed, losses, first_grad, change, want=want)
