"""Training entry point.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --smoke --steps 20 --workdir /tmp/run1

``--smoke`` uses the reduced same-family config (CPU-runnable); the full
configs are exercised via the dry-run.  ``--inject-fault KIND:STEP`` runs
the C4D detect -> isolate -> restore loop mid-training.
"""
from __future__ import annotations

import argparse
import json
import logging

from repro.common.compile_cache import enable_compile_cache
from repro.common.config import SHAPES, ShapeSpec
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.core.faults import Fault
from repro.launch.mesh import make_local_mesh
from repro.train.trainer import FaultInjector, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--workdir", default="/tmp/repro_train")
    ap.add_argument("--shape", default=None,
                    help="shape grid name; default = config's train shape")
    ap.add_argument("--inject-fault", default=None, metavar="KIND:STEP",
                    help="e.g. slow_src:7 or crash:5")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()

    logging.basicConfig(level=logging.INFO)
    run = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.shape:
        shape = SHAPES[args.shape]
    else:
        shape = ShapeSpec("train", run.train.seq_len, run.train.global_batch, "train")
    mesh = make_local_mesh(args.data, args.model)
    trainer = Trainer(run, shape, workdir=args.workdir, mesh=mesh)

    injector = None
    if args.inject_fault:
        kind, step = args.inject_fault.split(":")
        injector = FaultInjector({int(step): Fault(kind, rank=3)})

    report = trainer.train(args.steps, injector=injector)
    out = {
        "arch": run.model.name,
        "steps_run": report.steps_run,
        "restarts": report.restarts,
        "first_loss": report.losses[0] if report.losses else None,
        "last_loss": report.losses[-1] if report.losses else None,
        "detections": report.detections,
        "step_stats": trainer.monitor.summary(),
        "checkpoints_saved": trainer.ckpt.save_count,
    }
    print(json.dumps(out, indent=1, default=str))


if __name__ == "__main__":
    main()
