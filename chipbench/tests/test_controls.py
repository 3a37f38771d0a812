"""The comparisons that decide ``correct`` must fail when they should.

* The control: the plain reference put in the program's place, computed
  one precision step below the configuration's (C4D: float32 for float64;
  SmolLM: float8 products for bfloat16), must come out not correct.
* Faults planted in the timed path must make a run come out not correct:
  an answer altered where it is produced, a step that returns its state
  unchanged, half of the batch left out.

The tests run at small sizes on the CPU.  At a cell's own size, on the
chip, the same readings come from

    python chipbench/tests/test_controls.py --workload <cell> --seeds 11 12 13

which prints, for each seed, the numbers the comparison reads for the
control and for the faults that need a run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402
from chipbench.drivers import c4d as c4d_driver  # noqa: E402
from chipbench.drivers import train as train_driver  # noqa: E402
from chipbench.reference import c4d as ref_c4d  # noqa: E402
from chipbench.reference import smollm as ref_lm  # noqa: E402
from chipbench.streams import episode_stream  # noqa: E402


# ---------------------------------------------------------------------------
# readings (shared by the tests and the chip-size command)
# ---------------------------------------------------------------------------

def c4d_control(cfg, mix, seed, windows):
    """The float32 reference in the program's place, compared as a run is."""
    master = ref_c4d.Master(cfg["n_ranks"], cfg["ranks_per_node"],
                            ref_c4d.Thresholds(**cfg["thresholds"]),
                            dtype=np.float32)
    answers = []
    for planned, _ in zip(episode_stream(cfg, mix, seed), range(windows)):
        v, a, _ = master.ingest(planned.window)
        answers.append((v, a))
    bad, gap, _ = c4d_driver.compare(cfg, mix, seed, answers)
    return {"windows_verdicts_or_actions_differ": float(sum(bad)),
            "score_rel_gap": gap}


def smollm_readings(cfg, mix, seed):
    """Per seed: the control (float8 products) and the half-batch fault,
    each read against the float32 reference as a run is."""
    n = cfg["reference_steps"]
    b = mix["global_batch"]
    k = cfg["parallel"]["microbatches"]
    batches = [ref_lm.batch_tokens(seed, s, b, mix["seq_len"], cfg["vocab_size"])
               for s in range(n)]

    def follow(precision, rows_of_batch=b):
        return ref_lm.train_steps(
            cfg, cfg["optimizer"], seed, [t[:rows_of_batch] for t in batches],
            cfg["reference_rows"], precision,
            loss_rows=(rows_of_batch - rows_of_batch // k, rows_of_batch))

    want = follow("f32")
    out = {}
    for label, got in (("control_fp8", follow("fp8")),
                       ("fault_half_batch", follow("f32", b // 2))):
        checks = train_driver.compare(cfg, mix, seed, got["losses"],
                                      got["first_grad"], got["change"],
                                      want=want)
        out[label] = {c.name: c.value for c in checks}
    return out


# ---------------------------------------------------------------------------
# tests at small sizes
# ---------------------------------------------------------------------------

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")


def _cell(name):
    from chipbench.tests.test_rehearsal import small_cell
    return small_cell(name)


def test_c4d_control_fails():
    cell = _cell("c4d-fleet-day.incident_stream")
    readings = c4d_control(cell.config, cell.mix, 4, windows=24)
    limits = cell.config["correct"]
    assert (readings["windows_verdicts_or_actions_differ"] > limits["windows_differ"]
            or readings["score_rel_gap"] > limits["score_rel_gap"])


@pytest.mark.parametrize("fault", ["answer_altered", "state_unchanged",
                                   "half_the_window"])
def test_c4d_faults_fail(monkeypatch, fault):
    from chipbench.tests.test_rehearsal import drive
    original = c4d_driver.Program.ingest

    def broken(self, w):
        if fault == "half_the_window":
            keep = w.tr_src % 2 == 0
            w = type(w)(**{**w.__dict__, **{c: getattr(w, c)[keep] for c in (
                "tr_src", "tr_dst", "tr_bytes", "tr_post", "tr_start", "tr_end")}})
        if fault == "state_unchanged":
            self.master._pending.clear()
        verdicts, actions = original(self, w)
        if fault == "answer_altered" and verdicts:
            s, rank, link, score = verdicts[0]
            verdicts[0] = (s, rank, link, score * (1 + 1e-6))
        return verdicts, actions

    monkeypatch.setattr(c4d_driver.Program, "ingest", broken)
    r, out = drive(_cell("c4d-fleet-day.incident_stream"))
    assert harness.result_line(r, out)["correct"] is False


def test_smollm_control_fails():
    cell = _cell("smollm-135m.train_4k")
    readings = smollm_readings(cell.config, cell.mix, seed=6)
    limits = cell.config["correct"]
    for label in ("control_fp8", "fault_half_batch"):
        assert any(v > limits[k] for k, v in readings[label].items()), label


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_smollm_faults_fail(monkeypatch, fault):
    import repro.train.trainer as trainer_mod
    from chipbench.tests.test_rehearsal import drive
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
    r, out = drive(_cell("smollm-135m.train_4k"))
    assert harness.result_line(r, out)["correct"] is False


# ---------------------------------------------------------------------------
# readings at a cell's own size (on the chip)
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--windows", type=int, default=120,
                    help="C4D: windows a run scores (its set-up's and its window's)")
    args = ap.parse_args(argv)
    cell = harness.Cell.load(BENCH, args.workload)
    for seed in args.seeds:
        t = time.perf_counter()
        if cell.config["driver"] == "c4d":
            out = {"control_f32": c4d_control(cell.config, cell.mix, seed,
                                              args.windows)}
        else:
            out = smollm_readings(cell.config, cell.mix, seed)
        print(json.dumps({"workload": cell.name, "seed": seed, **out,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
