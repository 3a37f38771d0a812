"""The trace reduction: interval arithmetic on hand-made device lines, and
the whole fold on a small trace recorded on a TPU v5e (a 1,024-rank C4D
window stream, ``data/c4d_1024.xplane.pb.gz``)."""
import gzip
import shutil
from pathlib import Path

import pytest

from chipbench import tracefold as tf

DATA = Path(__file__).parent / "data" / "c4d_1024.xplane.pb.gz"


def _trace(ops, modules=(), spans=()):
    return tf.Trace(devices={"/device:TPU:0": tf.DeviceTrace(
        ops=list(ops), modules=list(modules))}, spans=sorted(spans))


def test_union_subtract_gaps():
    busy = tf.union([(0, 10, "a"), (5, 15, "b"), (20, 30, "c")], 2, 25)
    assert busy == [(2, 15), (20, 25)]
    assert tf.length(busy) == 18
    assert tf.subtract([(0, 30)], busy) == [(0, 2), (15, 20), (25, 30)]
    assert tf.gaps(busy, 0, 30) == [(0, 2), (15, 20), (25, 30)]


def test_busy_counts_nested_ops_once():
    dev = tf.DeviceTrace(ops=[(0, 100, "%while.1 = s32[] while()"),
                              (10, 40, "%fusion.2 = f32[8] fusion()"),
                              (50, 60, "%fusion.3 = f32[8] fusion()"),
                              (150, 160, "%copy.4 = f32[8] copy()")])
    assert tf.length(tf.union(dev.ops, 0, 200)) == 110
    # self time: the while loop holds 100 - 30 - 10 of its own
    top = dict(tf.top_ops(dev, 0, 200))
    assert top["while.1 s32[]"] == pytest.approx(60e-9)
    assert top["fusion.2 f32[8]"] == pytest.approx(30e-9)


def test_exposed_collectives():
    dev = tf.DeviceTrace(ops=[
        (0, 100, "%all-gather.1 = bf16[4] all-gather()"),
        (20, 50, "%fusion.2 = bf16[4] fusion()"),
        (200, 240, "%all-reduce.3 = f32[4] all-reduce()"),
    ])
    # 100 - 30 overlapped + 40 alone
    assert tf.exposed_collective_ns(dev, 0, 300) == 110
    # an asynchronous gather in flight over [240, 300) with compute in [250, 260)
    dev.async_ops.append((240, 300, "%all-gather-start.5 = (bf16[4]) all-gather-start()"))
    dev.ops.append((250, 260, "%fusion.6 = bf16[4] fusion()"))
    assert tf.exposed_collective_ns(dev, 0, 300) == 110 + 50


def test_modules_and_labelled_gaps():
    t = _trace(
        ops=[(10, 20, "%a = f32[1] add()"), (60, 70, "%b = f32[1] add()")],
        modules=[(10, 20, "jit_fused_window_kernel(123)"),
                 (60, 70, "jit_slow_fold_kernel(456)")],
        spans=[(0, 100, "window"), (0, 80, "ingest"), (80, 100, "synthesis")])
    f = tf.fold(t)
    assert (f.lo, f.hi) == (0, 100)
    assert f.module_s("fused_window_kernel") == pytest.approx(10e-9)
    assert f.module_s("slow_fold_kernel") == pytest.approx(10e-9)
    assert f.busy_s == pytest.approx(20e-9)
    # idle [0,10] and [20,60] fall in ingest, [70,100] in synthesis
    gaps = [(label, round(s * 1e9)) for label, s in f.breakdown()["idle_gaps"]]
    assert gaps == [("ingest", 40), ("synthesis", 30), ("ingest", 10)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "c4d.xplane.pb"
    with gzip.open(DATA, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tf.fold(tf.load(path))


def test_recorded_trace(recorded):
    f = recorded
    assert "/device:TPU:0" in f.trace.devices
    assert 0 < f.busy_s < f.window_s
    # every scored window ran the fused kernel inside its ingest span
    ingests = f.spans("ingest")
    dev = f.trace.devices["/device:TPU:0"]
    assert ingests
    assert tf.module_runs(dev, "fused_window_kernel", f.lo, f.hi) == len(ingests)
    assert 0 < f.module_s("fused_window_kernel") < f.window_s
    inside = tf.span_busy_overlap(ingests, f.busy["/device:TPU:0"])
    assert inside == pytest.approx(f.busy_s * 1e9, rel=0.05)
    bd = f.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert {label for label, _ in bd["idle_gaps"]} <= {"ingest", "synthesis",
                                                       "outside spans"}
