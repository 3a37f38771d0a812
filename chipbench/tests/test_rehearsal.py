"""CPU rehearsal of the benchmark: each cell's traffic, driver, comparison,
metric readers and result line, end to end at small sizes under
``JAX_PLATFORMS=cpu``, by calling the harness's functions (the command
itself refuses any device but a TPU)."""
import gzip
import importlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from chipbench import harness, tracefold

ROOT = harness.ROOT
BENCH = harness.load_json(ROOT / "BENCHMARK.json")
TRACE = Path(__file__).parent / "data" / "c4d_1024.xplane.pb.gz"


class V5e:
    """A stand-in device that names the chip the peaks table knows."""
    platform, device_kind = "tpu", "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def small_cell(name):
    cell = harness.Cell.load(BENCH, name)
    if cell.config["driver"] == "c4d":
        cell.config = dict(cell.config, n_ranks=512)
    else:
        cell.config = dict(
            cell.config, num_hidden_layers=2, hidden_size=72,
            num_attention_heads=3, num_key_value_heads=3, head_dim=24,
            intermediate_size=128, vocab_size=512, reference_rows=2,
            parallel=dict(cell.config["parallel"], remat="none"),
            correct={"loss_rel_gap": 1e-3, "first_grad_leaf_gap": 1e-2,
                     "update_leaf_gap": 0.05})
        cell.mix = dict(cell.mix, seq_len=32, global_batch=4)
    return cell


def drive(cell, seconds=1.5, seed=2**31 + 17):
    import jax
    r = harness.Run(cell=cell, seed=seed, seconds=seconds, trace=False,
                    t0=time.perf_counter(), devices=jax.devices()[:1])
    r.count_compiles()
    driver = importlib.import_module(f"chipbench.drivers.{cell.config['driver']}")
    return r, driver.run(r)


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_end_to_end(name, tmp_path):
    cell = small_cell(name)
    r, out = drive(cell)
    line = harness.result_line(r, out)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["counters"]["in_window"] == 0
    assert list(line)[-1] == "checks"
    json.dumps(line)

    # the per-layer readers, on a recorded TPU trace
    trace = tmp_path / "c4d.xplane.pb"
    with gzip.open(TRACE, "rb") as f:
        trace.write_bytes(f.read())
    r.trace, r.devices = True, [V5e()]
    r.fold = tracefold.fold(tracefold.load(trace))
    traced = harness.result_line(r, out)
    names = {m["name"] for m in cell.per_layer}
    assert set(traced["metrics"]) <= names
    for m, v in traced["metrics"].items():
        assert v["value"] >= 0, m
        if m.endswith("roofline") or "mfu" in m or m.startswith("idle_share"):
            assert v["value"] <= 100, m
    assert traced["device"]["busy_s"] > 0 and traced["device"]["window_s"] > 0
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}


def test_command_refuses_a_host_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "chipbench/run.py", "--workload",
           "c4d-fleet-day.incident_stream", "--seed", "1", "--seconds", "1"]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr

    # nor does it run from the benchmark's own files alone
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
