"""Spans and counters of the C4D master and the Trainer (docs/tracing.md).

* the tracing module: spans are free without a profiler session and never
  import jax, names carry the ``repro.`` prefix, counters add and snapshot;
* a small ``C4DMaster`` moves each C4D counter by exactly what it did;
* a CPU profiler capture around a small ingest and around a Trainer step
  finds every span on the host plane, nested as documented;
* every span, counter and named scope in ``src/`` is documented, and
  nothing else is.
"""
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.common import tracing
from repro.core.c4d.agent import prefilter_arrays
from repro.core.c4d.master import C4DMaster
from repro.core.faults import Fault, RingJobTelemetry

ROOT = Path(__file__).resolve().parents[1]
N = 32

#: every span the program writes -> the span it opens inside (None: a root)
SPAN_TREE = {
    "c4d.ingest": None,
    "c4d.prefilter": "c4d.ingest",
    "c4d.prefilter.groups": "c4d.prefilter",
    "c4d.prefilter.node_stats": "c4d.prefilter",
    "c4d.prefilter.edge_medians": "c4d.prefilter",
    "c4d.detect": "c4d.ingest",
    "c4d.layout": "c4d.detect",
    "c4d.pack": "c4d.detect",
    "c4d.fused": "c4d.detect",
    "c4d.hang_verdicts": "c4d.detect",
    "c4d.center_scale": "c4d.detect",
    "c4d.fold": "c4d.detect",
    "c4d.act": "c4d.ingest",
    "train.step": None,
    "train.batch": "train.step",
    "train.dispatch": "train.step",
    "train.loss_sync": "train.step",
    "train.checkpoint": None,
    "train.fault": None,
}
COUNTERS = {"c4d.windows", "c4d.transports_in", "c4d.transports_kept",
            "c4d.layout_hits", "c4d.layout_misses", "c4d.hang_windows",
            "c4d.fold_windows", "c4d.node_actions",
            "c4d.prefilter.row_sorts", "c4d.prefilter.lexsort_fallbacks",
            "moe.rows_kept", "moe.rows_dropped",
            "attention.kernel_layers", "attention.flash_fwd_layers",
            "attention.chunked_layers"}
#: ``jax.named_scope`` names of the device ops the benchmark selects
SCOPES = {"moe.route", "moe.dispatch", "moe.experts", "moe.combine",
          "mla.attend", "attention"}

FAULT_FREE = []
HANG = [Fault("comm_hang", rank=11)]


def _windows():
    tel = RingJobTelemetry(n_ranks=N, seed=3)
    return [tel.window_arrays(0, FAULT_FREE), tel.window_arrays(1, HANG)]


def _ingest(master, windows, batched):
    if batched:
        return [a for acts in master.ingest_batch(windows) for a in acts]
    return [a for w in windows for a in master.ingest(w)]


def _delta(before):
    after = tracing.counters()
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


# ---------------------------------------------------------------------------
# the module
# ---------------------------------------------------------------------------

def test_spans_are_null_and_jax_free_before_jax_is_imported():
    """Without jax no profiler session can be open: a span is a null
    context, and the NumPy C4D path never imports jax through it."""
    code = (
        "import contextlib, sys\n"
        "from repro.common import tracing\n"
        "from repro.core.c4d.master import C4DMaster\n"
        "from repro.core.faults import RingJobTelemetry\n"
        "assert isinstance(tracing.span('c4d.ingest', window_id=1),"
        " contextlib.nullcontext)\n"
        "assert isinstance(tracing.step_span('train.step', 0),"
        " contextlib.nullcontext)\n"
        "tel = RingJobTelemetry(n_ranks=32, seed=1)\n"
        "m = C4DMaster(n_ranks=32, backend='numpy')\n"
        "m.ingest(tel.window_arrays(0))\n"
        "m.ingest(tel.window(1))\n"
        "assert tracing.counters()['c4d.windows'] == 2\n"
        "assert 'jax' not in sys.modules, 'the NumPy path imported jax'\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env={"PYTHONPATH": str(ROOT / "src"),
                            "PATH": "/usr/bin:/bin"},
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def test_span_without_a_session_is_free_and_records_nothing(tmp_path):
    import jax  # noqa: F401  (a session can only be opened through jax)
    with tracing.span("test.unrecorded", window_id=5):
        pass
    with tracing.step_span("test.unrecorded_step", 3):
        pass
    names = _captured(tmp_path, lambda: None)
    assert not any(n.startswith("test.unrecorded") for n in names)


def test_span_names_carry_the_prefix(tmp_path):
    def work():
        with tracing.span("test.outer", window_id=7):
            with tracing.step_span("test.step", 2):
                pass
    names = _captured(tmp_path, work)
    assert {"test.outer", "test.step"} <= names


def test_counters_add_and_snapshot():
    before = tracing.counters()
    tracing.count("test.counted")
    tracing.count("test.counted", 4)
    snap = tracing.counters()
    assert snap["test.counted"] - before.get("test.counted", 0) == 5
    snap["test.counted"] = -1                  # a copy, not the registry
    assert tracing.counters()["test.counted"] != -1


# ---------------------------------------------------------------------------
# C4D counters
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batched", [False, True], ids=["ingest", "batch"])
def test_c4d_counters_move_by_what_the_master_did(batched):
    windows = _windows()
    kept = sum(int(prefilter_arrays(w, 8, n_ranks=N).tr_src.size)
               for w in windows)
    master = C4DMaster(n_ranks=N, ranks_per_node=8, backend="jax")
    before = tracing.counters()
    actions = _ingest(master, windows, batched)
    moved = _delta(before)
    assert moved.pop("c4d.windows") == 2
    assert moved.pop("c4d.transports_in") == sum(int(w.tr_src.size)
                                                 for w in windows)
    assert moved.pop("c4d.transports_kept") == kept
    # four grouped medians a window, all on the padded row sort
    assert moved.pop("c4d.prefilter.row_sorts") == 4 * len(windows)
    # both windows reach the layout: each is a hit or a miss
    assert (moved.pop("c4d.layout_hits", 0)
            + moved.pop("c4d.layout_misses", 0)) == 2
    assert moved.pop("c4d.hang_windows") == 1
    assert moved.pop("c4d.fold_windows") == 1
    assert actions, "the hang must be acted on"
    assert moved.pop("c4d.node_actions") == len(actions)
    assert moved == {}


def test_c4d_counters_on_the_numpy_backend():
    master = C4DMaster(n_ranks=N, ranks_per_node=8, backend="numpy")
    before = tracing.counters()
    actions = _ingest(master, _windows(), batched=False)
    moved = _delta(before)
    assert moved["c4d.windows"] == 2
    assert moved["c4d.hang_windows"] == moved["c4d.fold_windows"] == 1
    assert moved["c4d.node_actions"] == len(actions)
    assert "c4d.layout_hits" not in moved and "c4d.layout_misses" not in moved


# ---------------------------------------------------------------------------
# spans on a CPU profiler capture
# ---------------------------------------------------------------------------

def _capture(tmp_path, fn):
    """Run ``fn`` under a CPU profiler session; the program's spans on the
    host plane as (start_ns, end_ns, name without the prefix)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracing.PREFIX):
                    s = float(ev.start_ns)
                    spans.append((s, s + float(ev.duration_ns),
                                  ev.name[len(tracing.PREFIX):]))
    return spans


def _captured(tmp_path, fn):
    return {name for _, _, name in _capture(tmp_path, fn)}


def _assert_nested(spans):
    for s, e, name in spans:
        parent = SPAN_TREE[name]
        if parent is not None:
            assert any(ps <= s and e <= pe for ps, pe, pn in spans
                       if pn == parent), f"{name} outside {parent}"


@pytest.mark.parametrize("batched", [False, True], ids=["ingest", "batch"])
def test_c4d_spans_on_a_capture(tmp_path, batched):
    windows = _windows()
    master = C4DMaster(n_ranks=N, ranks_per_node=8, backend="jax")
    _ingest(master, windows, batched)                  # compile outside
    spans = _capture(tmp_path, lambda: _ingest(master, windows, batched))
    want = {n for n in SPAN_TREE if n.startswith("c4d.")}
    assert {name for _, _, name in spans} == want
    _assert_nested(spans)
    roots = [sp for sp in spans if sp[2] == "c4d.ingest"]
    assert len(roots) == (1 if batched else 2)


def test_trainer_spans_on_a_capture(tmp_path):
    from repro.common.config import ShapeSpec
    from repro.configs import get_smoke_config
    from repro.train.trainer import FaultInjector, Trainer
    run = get_smoke_config("smollm-135m")
    shape = ShapeSpec("t", run.train.seq_len, run.train.global_batch, "train")
    tr = Trainer(run, shape, workdir=str(tmp_path / "ckpt"),
                 checkpoint_async=False)
    # a crash before the first step: detect -> isolate -> restore, then
    # the step itself
    inj = FaultInjector({0: Fault("crash", rank=9)})
    spans = _capture(tmp_path / "trace", lambda: tr.train(1, injector=inj))
    names = {name for _, _, name in spans}
    assert {n for n in SPAN_TREE if n.startswith("train.")} <= names
    # the fault handler drives the C4D master
    assert {"c4d.ingest", "c4d.prefilter", "c4d.detect", "c4d.act"} <= names
    _assert_nested([sp for sp in spans if sp[2].startswith("train.")])
    assert tr.report.restarts == 1 and np.isfinite(tr.report.losses[-1])


# ---------------------------------------------------------------------------
# trace-time counts of the attention path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["dense", "mla", "gemma2"])
@pytest.mark.parametrize("path", ["chunked", "kernel"])
def test_attention_path_counted_once_per_layer(monkeypatch, family, path):
    """Tracing a small model's train step, its layers unrolled so that each
    traces its own attention call, counts one call a layer: on the jnp path
    here, and on the splash kernel with the platform check steered as a TPU
    would answer it (tracing only; nothing is compiled). gemma2's
    alternating window is traced, which the splash kernel cannot take and
    the forward-only kernel cannot differentiate: it trains on the jnp
    path on a TPU too."""
    from dataclasses import replace

    import jax
    from repro.common.config import MLAConfig, ShapeSpec
    from repro.configs import get_smoke_config
    from repro.kernels import ops
    from repro.models.model import build_model, synthetic_batch
    from repro.optim import adamw
    from repro.train.steps import make_train_step
    if path == "kernel":
        monkeypatch.setattr(ops, "_kernel_path", lambda use_kernel: use_kernel)
    run = get_smoke_config("gemma2-2b" if family == "gemma2" else "smollm-135m")
    model_cfg = replace(run.model, n_layers=3)
    if family == "mla":
        model_cfg = replace(model_cfg, mla=MLAConfig(
            kv_lora_rank=32, q_lora_rank=0, rope_head_dim=8, nope_head_dim=16,
            v_head_dim=16))
    run = run.replace(model=model_cfg,
                      parallel=replace(run.parallel, remat="none", microbatches=2),
                      train=replace(run.train, seq_len=128, global_batch=4))
    model = build_model(run, use_kernel=True)
    model.unroll = True
    opt_cfg = adamw.OptimizerConfig()
    params = jax.eval_shape(model.init, jax.random.key(0))
    opt_state = jax.eval_shape(lambda p: adamw.init_state(opt_cfg, p), params)
    batch = synthetic_batch(run.model, ShapeSpec("t", 128, 4, "train"))
    step = make_train_step(model, run, opt_cfg)
    before = tracing.counters()
    jax.eval_shape(step, params, opt_state, batch)
    taken = "kernel" if path == "kernel" and family != "gemma2" else "chunked"
    assert _delta(before) == {f"attention.{taken}_layers": 3}


# ---------------------------------------------------------------------------
# the documented names are the program's names
# ---------------------------------------------------------------------------

def test_every_span_and_counter_is_documented():
    src = "\n".join(p.read_text() for p in (ROOT / "src").rglob("*.py"))
    spans = set(re.findall(r"\b(?:step_)?span\(\s*\"([a-z0-9_.]+)\"", src))
    counts = set(re.findall(r"\bcount\(\s*\"([a-z0-9_.]+)\"", src))
    scopes = set(re.findall(r"\bnamed_scope\(\s*\"([a-z0-9_.]+)\"", src))
    assert spans == set(SPAN_TREE)
    assert counts == COUNTERS
    assert scopes == SCOPES
    doc = (ROOT / "docs" / "tracing.md").read_text()
    documented = set(re.findall(
        r"`((?:c4d|train|moe|mla|attention)\.[a-z0-9_.]+|attention)`", doc))
    assert documented == spans | counts | scopes
