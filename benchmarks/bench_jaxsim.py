"""jaxsim kernel scaling — the jit detection & flow kernels vs NumPy.

Row families (docs/jaxsim.md "Measured scaling"):

  * ``jaxsim/detect_<n>`` — steady-state wall-clock of one full jax-backend
    ``C4DDetector.analyze`` pass over a clean window at ``n`` ranks
    (1k / 16k / 100k; the 100k row is the ISSUE's scaling anchor and runs
    in quick mode too).  At 1024 ranks ``derived`` carries the NumPy
    detector's wall-clock and the speedup; beyond that the dense NumPy
    matrices no longer fit and the jax sparse path stands alone.
  * ``jaxsim/detect_batched_<n>`` — ``score_windows_batched`` (vmap over
    trials) vs the same windows through per-window ``analyze`` calls;
    ``derived.per_window_ms`` is the amortised cost campaigns see.
  * ``jaxsim/waterfill_fig2`` — ``FlowSet.max_min(backend="jax")`` vs the
    NumPy engine on the Fig. 2 multi-job fabric (amortised, FlowSet
    factored once), rate agreement included.
  * ``jaxsim/ewma_scan`` — the windows-as-``lax.scan`` baseline update
    (the PR 6 winsorized EWMA replayed over W windows in one dispatch).

All rows are emitted only when jax imports; otherwise a single
``jaxsim/unavailable`` row records the skip (the CI perf gate budgets only
the rows above, so a jax-less local run still completes).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, timeit


def _detect_rows(quick: bool) -> None:
    from repro.core.c4d.detector import C4DDetector
    from repro.core.faults import RingJobTelemetry

    sizes = (1024, 16384, 100000)
    for n in sizes:
        tel = RingJobTelemetry(n_ranks=n, seed=3)
        w = tel.window_arrays(0, [])
        det = C4DDetector(backend="jax")
        det.analyze(w, n)  # compile + warm the bucket
        repeats = 1 if (quick or n >= 16384) else 3
        us = timeit(lambda: det.analyze(w, n), repeats=repeats)
        derived = {"ranks": n, "transports": int(w.tr_src.size),
                   "ms": f"{us / 1e3:.0f}"}
        if n <= 1024:
            ref = C4DDetector()
            us_np = timeit(lambda: ref.analyze(w, n), repeats=repeats)
            derived["numpy_ms"] = f"{us_np / 1e3:.0f}"
            derived["speedup"] = f"{us_np / max(us, 1e-9):.2f}"
        emit(f"jaxsim/detect_{n}", us, derived)


def _batched_rows(quick: bool) -> None:
    from repro.core.c4d.detector import C4DDetector, DetectorConfig
    from repro.core.faults import Fault, RingJobTelemetry
    from repro.core.jaxsim.detectors import score_windows_batched

    n, b = 1024, 8
    cfg = DetectorConfig()
    tel = RingJobTelemetry(n_ranks=n, seed=7)
    wins = [tel.window_arrays(i, [Fault("slow_src", rank=5)] if i % 2 else [])
            for i in range(b)]
    score_windows_batched(wins, cfg, n)  # compile
    repeats = 1 if quick else 3
    us = timeit(lambda: score_windows_batched(wins, cfg, n),
                repeats=repeats)
    det = C4DDetector(backend="jax")
    det.analyze(wins[0], n)
    us_loop = timeit(lambda: [det.analyze(w, n) for w in wins],
                     repeats=repeats)
    emit(f"jaxsim/detect_batched_{n}", us, {
        "ranks": n, "windows": b,
        "per_window_ms": f"{us / b / 1e3:.1f}",
        "per_trial_loop_ms": f"{us_loop / 1e3:.0f}",
        "batch_gain": f"{us_loop / max(us, 1e-9):.2f}",
    })


def _waterfill_row(quick: bool) -> None:
    from benchmarks.bench_netsim_engine import FABRIC, fig2_flows
    from repro.core.flowset import FlowSet
    from repro.core.topology import ClosTopology

    topo = ClosTopology(**FABRIC)
    flows = fig2_flows(topo)
    fs = FlowSet(topo, flows)
    ref = fs.max_min()
    jx = fs.max_min(backend="jax")  # compile
    drift = float(np.max(np.abs(ref.flow_rate - jx.flow_rate)))
    repeats = 2 if quick else 5
    us = timeit(lambda: fs.max_min(backend="jax"), repeats=repeats)
    us_np = timeit(lambda: fs.max_min(), repeats=repeats)
    emit("jaxsim/waterfill_fig2", us, {
        "n_flows": len(flows),
        "numpy_us": f"{us_np:.0f}",
        "speedup": f"{us_np / max(us, 1e-9):.2f}",
        "max_rate_drift_gbps": f"{drift:.2e}",
    })


def _ewma_row(quick: bool) -> None:
    from repro.core.c4d.baseline import AdaptiveBaseline
    from repro.common.jax_compat import enable_x64
    from repro.core.jaxsim.kernels import ewma_scan_kernel

    windows, cells = (16, 4096) if quick else (64, 16384)
    rng = np.random.default_rng(0)
    values = rng.normal(10.0, 1.0, size=(windows, cells))
    values[rng.random(values.shape) < 0.1] = np.nan
    base = AdaptiveBaseline(n_ranks=2)
    alpha, clip = base.alpha, base.clip_sigma
    zeros = np.zeros(cells)

    def scan():
        import jax
        with enable_x64():
            out = ewma_scan_kernel(values, zeros, zeros,
                                   np.zeros(cells, np.int64), alpha, clip)
            jax.block_until_ready(out)

    scan()  # compile
    us = timeit(scan, repeats=2 if quick else 5)
    emit("jaxsim/ewma_scan", us, {
        "windows": windows, "cells": cells,
        "us_per_window": f"{us / windows:.0f}",
    })


def run(quick: bool = False) -> None:
    try:
        import jax  # noqa: F401
    except Exception as e:  # pragma: no cover - exercised on jax-less hosts
        emit("jaxsim/unavailable", 0.0, {"reason": type(e).__name__})
        return
    _detect_rows(quick)
    _batched_rows(quick)
    _waterfill_row(quick)
    _ewma_row(quick)
    _cache_info_row()


def _cache_info_row() -> None:
    """Zero-cost debug row: factory/layout cache occupancy after the suite —
    the ``jaxsim.cache_info()`` helper surfaced in ``--json`` artifacts
    (a long fleet run growing these without bound was the bug the bounded
    factories fixed)."""
    from repro.core.jaxsim import cache_info

    info = cache_info()
    lay = info["window_layouts"]
    emit("jaxsim/cache_info", 0.0, {
        "factory_maxsize": info["factory_maxsize"],
        "factory_entries": sum(s["size"] for s in info["factories"].values()),
        "layouts": f"{lay['entries']}/{lay['max_entries']}",
        "layout_hit_rate":
            f"{lay['hits'] / max(lay['hits'] + lay['misses'], 1):.2f}",
    })
