"""Fault-tolerant Trainer: the paper's RUN -> DETECT -> ISOLATE -> RESTORE loop.

Orchestrates:
  * jitted BSP train steps with explicit shardings (FSDP/TP/EP),
  * frequent checkpoints (in-memory + async disk; paper: every ~10 iters),
  * C4D integration: a StepMonitor anchors anomalies at the BSP boundary;
    in simulated-cluster mode a FaultInjector produces enhanced-CCL
    telemetry faults and the real C4D master issues verdicts,
  * elastic restart: on an uncorrectable fault the implicated node is
    isolated, a backup takes its place (SimCluster), the mesh is rebuilt
    over the healthy host set and the job restores from the last valid
    checkpoint — data pipeline determinism guarantees the stream resumes
    exactly.

The control-plane pieces (cluster, steering, C4D master, telemetry) are
injectable, so outer composition layers — notably the scenario campaign
engine's live driver (``repro.scenarios.live``) — can replay an
event-scripted drill on this real training loop against shared state.
"""
from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.common import jax_compat as jc
from repro.common.config import RunConfig, ShapeSpec
from repro.common.tracing import count, span, step_span
from repro.core.c4d.master import C4DMaster
from repro.core.cluster import SimCluster, SteeringService
from repro.core.faults import Fault, RingJobTelemetry
from repro.data.pipeline import PipelineConfig, TokenPipeline
from repro.models.model import build_model
from repro.optim import adamw
from repro.parallel import sharding as shd
from repro.train.hooks import StepMonitor
from repro.train.steps import make_train_step

log = logging.getLogger("repro.trainer")

#: step metrics the host fetches at each step's sync: the loss, the
#: gradient norm and, from held-expert MoE layers, the step's kept and
#: dropped expert rows
SYNCED = ("loss", "grad_norm", "moe_rows_kept", "moe_rows_dropped")


class SimulatedFault(RuntimeError):
    def __init__(self, fault: Fault, step: int):
        super().__init__(f"injected {fault.kind} at step {step}")
        self.fault = fault
        self.step = step


@dataclass
class FaultInjector:
    """Schedule telemetry-level faults at given steps (tests/examples)."""
    schedule: Dict[int, Fault] = field(default_factory=dict)

    def check(self, step: int) -> Optional[Fault]:
        return self.schedule.get(step)


@dataclass
class TrainerReport:
    steps_run: int = 0
    restarts: int = 0
    detections: List[dict] = field(default_factory=list)
    losses: List[float] = field(default_factory=list)
    grad_norms: List[float] = field(default_factory=list)
    downtime_steps: int = 0


class Trainer:
    def __init__(self, run: RunConfig, shape: ShapeSpec, workdir: str,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 sim_nodes: int = 4, use_kernel: bool = True,
                 checkpoint_async: bool = True,
                 cluster: Optional[SimCluster] = None,
                 steering: Optional[SteeringService] = None,
                 c4d: Optional[C4DMaster] = None,
                 telemetry: Optional[RingJobTelemetry] = None):
        self.run = run
        self.shape = shape
        # the default is a one-device job: name that device, so the mesh
        # need not cover every chip of the host
        self.mesh = mesh or jc.make_mesh(
            (1, 1), ("data", "model"), devices=jax.devices()[:1],
            axis_types=(jc.AxisType.Auto,) * 2)
        self.model = build_model(run, use_kernel=use_kernel)
        self.opt_cfg = adamw.OptimizerConfig(
            kind=run.parallel.optimizer_state,
            weight_decay=run.train.weight_decay)
        self.ckpt = CheckpointManager(workdir, keep=run.train.keep_checkpoints,
                                      async_disk=checkpoint_async)
        self.pipeline = TokenPipeline(run.model, shape,
                                      PipelineConfig(seed=run.train.seed))
        self.monitor = StepMonitor()
        # simulated production cluster + C4D control plane; each piece can be
        # injected by an outer composition layer (the scenario campaign
        # engine / live driver share one cluster and telemetry stream across
        # the drill — see repro.scenarios.live)
        self.cluster = cluster or SimCluster(n_active=sim_nodes,
                                             n_backup=max(1, sim_nodes // 4))
        self.steering = steering or SteeringService(self.cluster)
        self.telemetry = telemetry or RingJobTelemetry(n_ranks=sim_nodes * 8,
                                                       seed=run.train.seed)
        self.c4d = c4d or C4DMaster(n_ranks=self.telemetry.n, ranks_per_node=8)
        self.report = TrainerReport()
        self._build()

    # ------------------------------------------------------------------
    def _build(self):
        run = self.run
        with jc.set_mesh(self.mesh):
            abstract = jax.eval_shape(self.model.init, jax.random.key(run.train.seed))
            pspecs = shd.param_specs(abstract, self.mesh)
            self.param_sharding = shd.to_shardings(pspecs, self.mesh)
            init = jax.jit(self.model.init, out_shardings=self.param_sharding)
            self.params = init(jax.random.key(run.train.seed))
            # the moments start on their parameters' shardings, which is
            # also the layout the step returns: its second call then does
            # not recompile
            def init_opt(p):
                return adamw.init_state(self.opt_cfg, p)
            ospecs = shd.opt_state_specs(
                jax.eval_shape(init_opt, abstract), abstract, pspecs)
            self.opt_state = jax.jit(
                init_opt, out_shardings=shd.to_shardings(ospecs, self.mesh))(
                    self.params)
            step_fn = make_train_step(self.model, run, self.opt_cfg, self.mesh)
            batch_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                         for k, v in self.pipeline.batch(0).items()}
            batch_specs = shd.batch_specs(batch_abs, self.mesh)
            self._step_fn = self._jit_step(step_fn, batch_specs)
        self.step = 0

    def _jit_step(self, step_fn, batch_specs):
        # params must come back on their declared shardings: without
        # out_shardings GSPMD may commit an output leaf to a different
        # layout, and the next call rejects it against in_shardings
        # (surfaces on any mesh bigger than 1x1).  The optimizer state is
        # donated: its successor takes its buffers, so the step does not
        # hold two copies of the moments (8 bytes a parameter).
        return jax.jit(
            step_fn,
            in_shardings=(self.param_sharding, None,
                          shd.to_shardings(batch_specs, self.mesh)),
            out_shardings=(self.param_sharding, None, None),
            donate_argnums=(1,))

    # ------------------------------------------------------------------
    def _save_checkpoint(self, blocking: bool = False):
        with span("train.checkpoint", step=self.step):
            tree = {"params": self.params, "opt": self.opt_state,
                    "step": np.asarray(self.step)}
            self.ckpt.save(self.step, tree, blocking=blocking)

    def _restore_checkpoint(self):
        template = {"params": self.params, "opt": self.opt_state,
                    "step": np.asarray(self.step)}
        s, tree = self.ckpt.restore(template)
        with jc.set_mesh(self.mesh):
            self.params = jax.tree.map(
                lambda a, sh: jax.device_put(a, sh), tree["params"],
                self.param_sharding)
            # onto the live state's shardings: an optimizer state placed
            # differently from what the step returns recompiles the step
            self.opt_state = jax.tree.map(
                lambda a, live: jax.device_put(a, live.sharding), tree["opt"],
                self.opt_state)
        self.step = int(tree["step"])
        return s

    # ------------------------------------------------------------------
    def _handle_fault(self, fault: Fault, at_step: int):
        """The C4D pipeline: telemetry -> verdict -> isolate -> restore."""
        t0 = time.perf_counter()
        actions = []
        windows = 0
        while not actions and windows < 4:
            win = self.telemetry.window(window_id=windows, faults=[fault])
            actions = self.c4d.ingest(win)
            windows += 1
        detection_s = windows * self.c4d.window_period_s
        replaced = []
        for a in actions:
            repl, steer_s = self.steering.execute(a.node_id, t=at_step,
                                                  reason=a.verdicts[0].syndrome)
            replaced.append((a.node_id, repl))
        # elastic restart: rebuild over the (same-sized) healthy host set.
        # On real hardware the mesh device list changes; the shardings and
        # the jitted step are rebuilt identically.
        self._build_after_restart()
        restored = self._restore_checkpoint()
        self.report.restarts += 1
        self.report.detections.append({
            "fault": fault.kind, "at_step": at_step,
            "verdicts": [v.syndrome for a in actions for v in a.verdicts],
            "isolated": replaced, "detection_windows": windows,
            "detection_s_model": detection_s,
            "restored_step": restored,
            "wall_s": time.perf_counter() - t0,
        })
        self.report.downtime_steps += max(at_step - restored, 0)
        log.warning("fault %s handled: restored step %d, swapped %s",
                    fault.kind, restored, replaced)

    def _build_after_restart(self):
        # re-jit against the (possibly new) device set
        with jc.set_mesh(self.mesh):
            step_fn = make_train_step(self.model, self.run, self.opt_cfg, self.mesh)
            batch_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                         for k, v in self.pipeline.batch(0).items()}
            batch_specs = shd.batch_specs(batch_abs, self.mesh)
            self._step_fn = self._jit_step(step_fn, batch_specs)

    # ------------------------------------------------------------------
    def train(self, n_steps: int,
              injector: Optional[FaultInjector] = None) -> TrainerReport:
        run = self.run
        self._save_checkpoint(blocking=True)  # step-0 baseline
        target = self.step + n_steps
        while self.step < target:
            fault = injector.check(self.step) if injector else None
            if fault is not None:
                # remove from schedule so the retried step does not re-fault
                injector.schedule.pop(self.step, None)
                with span("train.fault", step=self.step, kind=fault.kind):
                    self._handle_fault(fault, self.step)
                continue
            with step_span("train.step", self.step):
                with span("train.batch"):
                    batch = {k: jnp.asarray(v) for k, v in
                             self.pipeline.batch(self.step).items()}
                self.monitor.start()
                with jc.set_mesh(self.mesh):
                    with span("train.dispatch"):
                        self.params, self.opt_state, metrics = self._step_fn(
                            self.params, self.opt_state, batch)
                    # the host waits here for the step: the BSP boundary
                    # the StepMonitor anchors on; one fetch of the scalars
                    with span("train.loss_sync"):
                        synced = jax.device_get(
                            {k: metrics[k] for k in SYNCED if k in metrics})
                self.monitor.stop(self.step)
                self.report.losses.append(float(synced["loss"]))
                self.report.grad_norms.append(float(synced["grad_norm"]))
                if "moe_rows_kept" in synced:
                    count("moe.rows_kept", int(synced["moe_rows_kept"]))
                    count("moe.rows_dropped", int(synced["moe_rows_dropped"]))
                self.report.steps_run += 1
                self.step += 1
                if self.step % run.train.checkpoint_every == 0:
                    self._save_checkpoint()
        self.ckpt.wait()
        return self.report
