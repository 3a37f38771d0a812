"""Shared machinery of the benchmark: loading a cell's files by name,
spans, the traced window, compile counting, the result line and the
correctness checks.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``configs/<config>.json``: the configuration as it is run; its
  ``driver`` key names ``drivers/<driver>.py``, which runs the cell;
* ``traffic/<traffic>.json``: the traffic mix that driver's generator reads;
* ``metrics/<metric>.py``: a per-layer metric's reader, ``read(ctx)``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
#: JAX's persistent compilation cache: a fixed directory inside the
#: checkout, so that only a cell's first run in a checkout compiles
CACHE_DIR = ROOT / ".jax_cache"
#: JAX's monitoring events for a backend compile (or cache load) and for a
#: persistent-cache miss
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class BenchError(RuntimeError):
    """The cell cannot run here (no chip, too few chips, a missing file)."""


def load_json(path: Path) -> Any:
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise BenchError(f"no such file: {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of BENCHMARK.json with its files loaded."""
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @classmethod
    def load(cls, bench: dict, name: str) -> "Cell":
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
        w = cells[name]

        def applies(m):
            return "workloads" not in m or name in m["workloads"]

        return cls(
            name=name, chips=w["chips"],
            config=load_json(HERE / "configs" / f"{w['config']}.json"),
            mix=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
            end_to_end=[m for m in bench["end_to_end"] if applies(m)],
            per_layer=[m for m in bench["per_layer"] if applies(m)])


# ---------------------------------------------------------------------------
# run context: spans, the measured window, compile counts
# ---------------------------------------------------------------------------

@dataclass
class Check:
    """One number the correctness comparison reads, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t0: float                                   # process start
    devices: list = field(default_factory=list)
    setup_s: Optional[float] = None
    facts: Dict[str, Any] = field(default_factory=dict)
    fold: Any = None
    compiles: Dict[str, int] = field(default_factory=dict)
    _in_window: bool = False
    _trace_dir: Optional[str] = None
    _window_span: Any = None

    # -- spans ----------------------------------------------------------
    def span(self, name: str):
        """A benchmark span, written into the profiler trace when tracing
        (``chipbench.<name>``) and free otherwise."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"chipbench.{name}")

    def end_setup(self) -> None:
        self.setup_s = time.perf_counter() - self.t0

    @contextlib.contextmanager
    def window(self):
        """The measured window; traced, with a ``window`` span, when
        ``--trace 1``.  Compilations inside it are counted."""
        self.start_window()
        try:
            yield
        finally:
            self.stop_window()

    def start_window(self) -> None:
        import jax
        if self.trace:
            self._trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
            self._window_span = jax.profiler.TraceAnnotation("chipbench.window")
            self._window_span.__enter__()
        self._in_window = True

    def stop_window(self) -> None:
        import jax
        self._in_window = False
        if self._trace_dir is None:          # not tracing, or never started
            return
        try:
            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            from chipbench import tracefold
            paths = sorted(Path(self._trace_dir).rglob("*.xplane.pb"))
            if not paths:
                raise BenchError("the profiler wrote no trace")
            self.fold = tracefold.fold(
                tracefold.load(paths[-1]),
                devices=[f"/device:TPU:{d.id}" for d in self.devices])
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None

    def count_compiles(self) -> None:
        """Count, inside the window, the programs JAX compiles or loads
        (``in_window``) and the persistent-cache misses among them."""
        import jax

        def on_duration(event, duration, **kw):
            if self._in_window and event == COMPILE_EVENT:
                self.compiles["in_window"] += 1

        def on_event(event, **kw):
            if self._in_window and event == CACHE_MISS_EVENT:
                self.compiles["cache_misses_in_window"] += 1

        self.compiles = {"in_window": 0, "cache_misses_in_window": 0}
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def memory_peak_bytes(self) -> int:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks) if peaks else 0


@dataclass
class Outcome:
    """What a driver hands back after its window and its comparison."""
    e2e: Dict[str, float]
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: int


def read_metric(run: Run, name: str) -> Optional[float]:
    """A per-layer metric from its reader, ``metrics/<name>.py``."""
    mod = load_module(HERE / "metrics" / f"{name}.py",
                      "chipbench_metric_" + name.replace(".", "_"))
    return mod.read(run)


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

def result_line(run: Run, out: Outcome) -> dict:
    import jax
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": out.memory_peak_bytes}
    metrics: Dict[str, dict] = {}
    if run.trace:
        device["busy_s"] = run.fold.busy_s
        device["window_s"] = run.fold.window_s
        for m in run.cell.per_layer:
            value = read_metric(run, m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in run.cell.end_to_end:
            value = run.setup_s if m["name"] == "setup_s" else out.e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = all(c.ok for c in out.checks) and out.failed == 0
    line = {"correct": correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if run.trace:
        line["breakdown"] = run.fold.breakdown()
    line["counters"] = dict(run.compiles, **run.facts.get("counters", {}))
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def report_checks(checks: List[Check]) -> None:
    """The compared numbers beside their limits, as the last lines on
    standard error."""
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
