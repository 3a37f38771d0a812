"""The comparison that decides ``correct`` in MoE training cells must fail
when it should (``test_controls.py`` does the same for the other cells).

* The control: the plain reference put in the program's place, computed
  one precision step below the configuration's (float8 products for
  bfloat16), must come out not correct.
* Faults planted in the timed path must make a run come out not correct: a
  step that returns its state unchanged, half of the batch left out, the
  drop rule's protected rows ignored.

The tests run at small sizes on the CPU.  At a cell's own size, on the
chip, the same readings come from

    python chipbench/tests/test_controls_moe.py --workload <cell> --seeds 11 12

which prints, for each seed, the numbers the comparison reads for the
control (the half-batch fault, which compiles the reference for a second
shape, is read at the small size only).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import pytest

if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402
from chipbench.drivers import train_moe  # noqa: E402

CELL = "deepseek-v2-lite.train_4k_ep8"


def moe_readings(cfg, mix, seed, half_batch: bool = True):
    """Per seed: the control (float8 products) and the half-batch fault,
    each read against the float32 reference as a run is."""
    want = train_moe.reference(cfg, mix, seed)
    half = dict(mix, global_batch=mix["global_batch"] // 2)
    runs = [("control_fp8", lambda: train_moe.reference(cfg, mix, seed, "fp8"))]
    if half_batch:
        runs.append(("fault_half_batch", lambda: train_moe.reference(cfg, half, seed)))
    out = {}
    for label, follow in runs:
        got = follow()
        checks = train_moe.compare(cfg, mix, seed, got["losses"], got["first_grad"],
                                   got["change"], want=want)
        out[label] = {c.name: c.value for c in checks}
    return out


def _cell():
    from chipbench.tests.test_rehearsal import small_cell
    return small_cell(CELL)


def test_moe_control_fails():
    cell = _cell()
    readings = moe_readings(cell.config, cell.mix, seed=6)
    limits = cell.config["correct"]
    for label in ("control_fp8", "fault_half_batch"):
        assert any(v > limits[k] for k, v in readings[label].items()), label


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "unprotected"])
def test_moe_faults_fail(monkeypatch, fault):
    import repro.train.trainer as trainer_mod
    from chipbench.tests.test_rehearsal import drive
    cell = _cell()
    if fault == "unprotected":
        # a budget that drops most rows, one protected row in each
        # microbatch, and a program that ranks every row by affinity alone:
        # protected rows are dropped where the reference keeps them
        cell.config = dict(cell.config,
                           expert_budget=dict(cell.config["expert_budget"],
                                              device_capacity_factor=0.05,
                                              protected_every=2))
        cell.mix = dict(cell.mix, seq_len=512)
        real = train_moe.program_config

        def unprotected(*a, **kw):
            import dataclasses
            run, shape = real(*a, **kw)
            moe = dataclasses.replace(run.model.moe, protected_every=0)
            return run.replace(model=dataclasses.replace(run.model, moe=moe)), shape
        monkeypatch.setattr(train_moe, "program_config", unprotected)
    else:
        make = trainer_mod.make_train_step

        def broken_make(*a, **kw):
            step = make(*a, **kw)

            def broken(params, opt_state, batch):
                if fault == "half_batch":
                    half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
                    return step(params, opt_state, half)
                _, _, metrics = step(params, opt_state, batch)
                return params, opt_state, metrics
            return broken

        monkeypatch.setattr(trainer_mod, "make_train_step", broken_make)
    r, out = drive(cell)
    assert harness.result_line(r, out)["correct"] is False


# ---------------------------------------------------------------------------
# readings at a cell's own size (on the chip)
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.Cell.load(harness.load_json(harness.ROOT / "BENCHMARK.json"),
                             args.workload)
    for seed in args.seeds:
        t = time.perf_counter()
        out = moe_readings(cell.config, cell.mix, seed, half_batch=False)
        print(json.dumps({"workload": cell.name, "seed": seed, **out,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
