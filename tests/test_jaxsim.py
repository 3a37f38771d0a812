"""JAX backend vs the NumPy references — identity, tolerance, and gate tests.

Three layers of pinning (docs/jaxsim.md "Correctness contract"):

  * *bit identity* for everything the detectors decide on: jax-backend
    ``C4DDetector.analyze`` must return the NumPy composite's Verdict list
    field-for-field (score floats and detail strings included) on the
    Table-3 golden windows, and a jax-backend streaming master must leave
    the adaptive baseline bit-equal to the NumPy master's window for
    window;
  * *1e-6 rate agreement* for the water-filling loop (segment-sum
    association order differs from ``np.bincount``);
  * *~1e-9* for the winsorized EWMA scan (fused multiply-adds on device).

The backend registry and the perf-gate row checker are plain-Python and
run without jax; everything else skips cleanly when jax is absent.
"""
import json

import numpy as np
import pytest

from repro.core.c4d.detector import C4DDetector, DetectorConfig
from repro.core.c4d.master import C4DMaster, OperatingPoint
from repro.core.c4d.telemetry import delay_matrix, grouped_median, wait_matrix
from repro.core.faults import RingJobTelemetry
from repro.core.flowset import FlowSet
from repro.core.jaxsim import (AUTO_DETECT_RANKS, AUTO_MEDIAN_ELEMENTS,
                               BackendError, cache_info, effective_backend,
                               jax_available, resolve_backend, use_backend)

from tests.test_c4d_vectorized import GOLDEN_FAULTS, N
from tests.test_netsim_perf import FABRIC_1024GPU, _random_scenario

requires_jax = pytest.mark.skipif(not jax_available(),
                                  reason="jax not installed")


# ---------------------------------------------------------------------------
# backend registry (no jax required)
# ---------------------------------------------------------------------------

def test_registry_default_and_scopes(monkeypatch):
    monkeypatch.delenv("REPRO_SIM_BACKEND", raising=False)
    assert resolve_backend(None) == "numpy"
    assert resolve_backend("jax") == "jax"
    with use_backend("jax"):
        assert resolve_backend(None) == "jax"
        with use_backend("numpy"):
            assert resolve_backend(None) == "numpy"
        assert resolve_backend(None) == "jax"
    assert resolve_backend(None) == "numpy"
    # a None scope is a no-op passthrough (spec.backend=None)
    with use_backend(None):
        assert resolve_backend(None) == "numpy"


def test_registry_env_var(monkeypatch):
    monkeypatch.setenv("REPRO_SIM_BACKEND", "jax")
    assert resolve_backend(None) == "jax"
    monkeypatch.setenv("REPRO_SIM_BACKEND", "bogus")
    with pytest.raises(BackendError):
        resolve_backend(None)


def test_registry_rejects_unknown():
    with pytest.raises(BackendError):
        resolve_backend("tpu")
    with pytest.raises(BackendError):
        with use_backend("bogus"):
            pass


def test_auto_backend_validates_without_jax():
    # "auto" must be requestable on numpy-only installs (it just resolves
    # to numpy everywhere) — unlike "jax", which raises when missing
    assert resolve_backend("auto") == "auto"
    with use_backend("auto"):
        assert resolve_backend(None) == "auto"


def test_effective_backend_size_dispatch():
    assert effective_backend("numpy", ranks=10 ** 6) == "numpy"
    if jax_available():
        assert effective_backend("jax", ranks=1) == "jax"
        assert effective_backend("auto",
                                 ranks=AUTO_DETECT_RANKS - 1) == "numpy"
        assert effective_backend("auto", ranks=AUTO_DETECT_RANKS) == "jax"
        assert effective_backend(
            "auto", elements=AUTO_MEDIAN_ELEMENTS) == "jax"
        assert effective_backend(
            "auto", elements=AUTO_MEDIAN_ELEMENTS - 1) == "numpy"
        # CPU water-filling never crosses over; no hint at all -> numpy
        assert effective_backend("auto", flows=10 ** 6) == "numpy"
        assert effective_backend("auto") == "numpy"
    else:
        assert effective_backend("auto", ranks=10 ** 6) == "numpy"


def test_cache_info_shape():
    info = cache_info()
    if not jax_available():
        assert info == {"available": False}
        return
    assert info["available"]
    assert info["factory_maxsize"] > 0
    for stats in info["factories"].values():
        assert stats["maxsize"] == info["factory_maxsize"]
        assert stats["size"] <= stats["maxsize"]
    assert "fused_window_kernel" in info["jit_entries"]
    lay = info["window_layouts"]
    assert lay["entries"] <= lay["max_entries"]


# ---------------------------------------------------------------------------
# perf-gate row checker (no jax required)
# ---------------------------------------------------------------------------

def _rows():
    return [{"name": "jaxsim/detect_1024", "us_per_call": 90_000.0},
            {"name": "netsim/max_min", "us_per_call": 4_000.0}]


def test_check_rows_passes_within_budget():
    from benchmarks.run import check_rows
    budgets = {"jaxsim/detect_1024": {"max_us": 100_000},
               "netsim/max_min": {"max_us": 10_000}}
    assert check_rows(_rows(), budgets) == []


def test_check_rows_flags_regression_and_missing():
    from benchmarks.run import check_rows
    budgets = {"jaxsim/detect_1024": {"max_us": 50_000},
               "jaxsim/detect_100000": {"max_us": 1}}
    out = check_rows(_rows(), budgets)
    assert len(out) == 2
    assert any("exceeds budget" in v for v in out)
    assert any("missing" in v for v in out)


def test_check_rows_only_filters_by_tag():
    from benchmarks.run import check_rows
    budgets = {"jaxsim/detect_1024": {"max_us": 1},
               "netsim/max_min": {"max_us": 10_000}}
    assert check_rows(_rows(), budgets, only="netsim") == []
    assert len(check_rows(_rows(), budgets, only="jaxsim")) == 1


def test_committed_baselines_cover_the_jaxsim_rows():
    with open("benchmarks/baselines.json") as f:
        budgets = json.load(f)["budgets"]
    for name in ("jaxsim/detect_1024", "jaxsim/detect_16384",
                 "jaxsim/detect_100000", "jaxsim/detect_batched_1024",
                 "jaxsim/waterfill_fig2", "jaxsim/ewma_scan",
                 "runtime/stream_tick_1024", "runtime/stream_tick_10240"):
        assert name in budgets and budgets[name]["max_us"] > 0, name


# ---------------------------------------------------------------------------
# grouped medians + matrices
# ---------------------------------------------------------------------------

@requires_jax
def test_grouped_median_backend_identity():
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 40, 1000)
    vals = rng.normal(size=1000)
    uk0, m0 = grouped_median(keys, vals)
    uk1, m1 = grouped_median(keys, vals, backend="jax")
    assert np.array_equal(uk0, uk1)
    assert np.array_equal(m0, m1)


@requires_jax
@pytest.mark.parametrize("faults", GOLDEN_FAULTS[:4])
def test_matrices_backend_identity(faults):
    w = RingJobTelemetry(n_ranks=N, seed=3).window_arrays(0, faults)
    for fn in (delay_matrix, wait_matrix):
        ref = fn(w, N)
        jx = fn(w, N, backend="jax")
        assert np.array_equal(ref, jx, equal_nan=True)


# ---------------------------------------------------------------------------
# detector verdict identity (the tentpole contract)
# ---------------------------------------------------------------------------

@requires_jax
@pytest.mark.parametrize("faults", GOLDEN_FAULTS)
def test_single_window_verdicts_identical(faults):
    w = RingJobTelemetry(n_ranks=N, seed=9).window_arrays(0, faults)
    ref = C4DDetector().analyze(w, N)
    jx = C4DDetector(backend="jax").analyze(w, N)
    assert ref == jx


@requires_jax
@pytest.mark.parametrize("op", [None, OperatingPoint(mad_threshold=5.0,
                                                     confirm_streak=2)])
def test_streaming_master_and_baseline_identical(op):
    """Windowed ingest: actions identical every window, adaptive baseline
    (mean/dev/count, all kinds) bit-equal after the stream."""
    for faults in GOLDEN_FAULTS:
        a = RingJobTelemetry(n_ranks=N, seed=5)
        b = RingJobTelemetry(n_ranks=N, seed=5)
        if op is None:
            ma = C4DMaster(n_ranks=N, ranks_per_node=8)
            mb = C4DMaster(n_ranks=N, ranks_per_node=8, backend="jax")
        else:
            ma = C4DMaster.from_operating_point(op, n_ranks=N)
            mb = C4DMaster.from_operating_point(op, n_ranks=N, backend="jax")
        for wid in range(4):
            ra = ma.ingest(a.window_arrays(wid, faults))
            rb = mb.ingest(b.window_arrays(wid, faults))
            assert ra == rb, (faults, wid)
        if ma.baseline is not None:
            for k in ("delay", "wait", "hb"):
                assert np.array_equal(ma.baseline._mean[k],
                                      mb.baseline._mean[k])
                assert np.array_equal(ma.baseline._dev[k],
                                      mb.baseline._dev[k])
                assert np.array_equal(ma.baseline._count[k],
                                      mb.baseline._count[k])


#: ring sizes landing the window's transport count (and n_pad) in three
#: different power-of-two pad buckets — the fused kernels recompile per
#: bucket, so equivalence must hold in each
PAD_BUCKET_RANKS = (N, 48, 96)


@requires_jax
@pytest.mark.parametrize("n", PAD_BUCKET_RANKS)
@pytest.mark.parametrize("faults", GOLDEN_FAULTS)
def test_fused_equals_per_kernel_equals_numpy(faults, n):
    """The tentpole contract, per golden window and pad bucket: the fused
    single-dispatch pipeline, the PR 7 per-kernel path, and the NumPy
    composite return the same Verdict list field-for-field (hang
    pre-emption included)."""
    from repro.core.jaxsim.detectors import (analyze_arrays,
                                             analyze_arrays_reference)
    cfg = DetectorConfig()
    w = RingJobTelemetry(n_ranks=n, seed=9).window_arrays(0, faults)
    ref = C4DDetector().analyze(w, n)
    fused = analyze_arrays(w, cfg, n_ranks=n)
    per_kernel = analyze_arrays_reference(w, cfg, n_ranks=n)
    assert fused == ref
    assert per_kernel == ref


@requires_jax
def test_batched_scorer_matches_per_window_verdicts():
    """vmap-batched scoring returns the exact per-window Verdict lists on a
    mixed batch of clean, slow and hang windows (hang windows take the
    batched hang branch; the rest share the vmapped fold)."""
    from repro.core.jaxsim.detectors import (analyze_arrays,
                                             score_windows_batched)
    cfg = DetectorConfig()
    tel = RingJobTelemetry(n_ranks=N, seed=11)
    wins = [tel.window_arrays(i, GOLDEN_FAULTS[i % len(GOLDEN_FAULTS)])
            for i in range(12)]
    batched = score_windows_batched(wins, cfg, n_ranks=N)
    assert len(batched) == len(wins)
    for i, w in enumerate(wins):
        assert batched[i] == analyze_arrays(w, cfg, n_ranks=N), i


@requires_jax
def test_master_ingest_batch_bit_identical():
    """``ingest_batch`` == sequential ``ingest`` — actions, order, and the
    persistent confirmation streak state — and both equal the NumPy
    master's actions window for window."""
    cfgs = dict(n_ranks=N, ranks_per_node=8)
    seq_np = C4DMaster(**cfgs)
    seq_jx = C4DMaster(**cfgs, backend="jax")
    bat_jx = C4DMaster(**cfgs, backend="jax")
    tel_a = RingJobTelemetry(n_ranks=N, seed=13)
    tel_b = RingJobTelemetry(n_ranks=N, seed=13)
    tel_c = RingJobTelemetry(n_ranks=N, seed=13)
    faults_per_win = [GOLDEN_FAULTS[i % len(GOLDEN_FAULTS)] for i in range(8)]
    wins_a = [tel_a.window_arrays(i, f) for i, f in enumerate(faults_per_win)]
    wins_b = [tel_b.window_arrays(i, f) for i, f in enumerate(faults_per_win)]
    wins_c = [tel_c.window_arrays(i, f) for i, f in enumerate(faults_per_win)]
    ref = [seq_np.ingest(w) for w in wins_a]
    seq = [seq_jx.ingest(w) for w in wins_b]
    bat = bat_jx.ingest_batch(wins_c)
    assert bat == seq == ref
    assert bat_jx._pending == seq_jx._pending == seq_np._pending


def test_kernel_factory_caches_are_bounded():
    if not jax_available():
        pytest.skip("jax not installed")
    from repro.core.jaxsim import kernels
    assert kernels.FACTORY_CACHE_SIZE > 0
    ci = kernels.batched_slow_fold_kernel.cache_info()
    assert ci.maxsize == kernels.FACTORY_CACHE_SIZE


# ---------------------------------------------------------------------------
# water-filling
# ---------------------------------------------------------------------------

@requires_jax
def test_waterfill_matches_numpy_on_random_topologies():
    rng = np.random.default_rng(7)
    for i in range(8):
        topo, flows = _random_scenario(rng, fail_links=bool(i % 2))
        fs = FlowSet(topo, flows)
        ref = fs.max_min()
        jx = fs.max_min(backend="jax")
        assert np.allclose(ref.flow_rate, jx.flow_rate, atol=1e-6, rtol=1e-6)
        assert np.allclose(ref.link_util, jx.link_util, atol=1e-6, rtol=1e-6)
        assert np.allclose(ref.conn_rate, jx.conn_rate, atol=1e-6, rtol=1e-6)


@requires_jax
def test_waterfill_matches_numpy_with_jitter_and_1024gpu_fabric():
    from benchmarks.bench_netsim_engine import fig2_flows
    from repro.core.topology import ClosTopology
    topo = ClosTopology(**FABRIC_1024GPU)
    fs = FlowSet(topo, fig2_flows(topo))
    ref = fs.max_min(cnp_jitter=0.05, seed=3)
    jx = fs.max_min(cnp_jitter=0.05, seed=3, backend="jax")
    # the jitter RNG stream is host-side and shared, so rates agree to the
    # usual tolerance even with randomized capacities
    assert np.allclose(ref.flow_rate, jx.flow_rate, atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# EWMA scan
# ---------------------------------------------------------------------------

@requires_jax
def test_ewma_scan_matches_adaptive_baseline():
    from repro.core.c4d.baseline import AdaptiveBaseline
    from repro.common.jax_compat import enable_x64
    from repro.core.jaxsim.kernels import ewma_scan_kernel
    n = 6
    rng = np.random.default_rng(2)
    base = AdaptiveBaseline(n_ranks=n)
    windows = []
    for _ in range(10):
        m = rng.normal(10.0, 1.0, size=(n, n))
        m[rng.random((n, n)) < 0.2] = np.nan
        windows.append(m)
        base.update("delay", m)
    with enable_x64():
        mean, dev, count = ewma_scan_kernel(
            np.stack([m.ravel() for m in windows]),
            np.zeros(n * n), np.zeros(n * n), np.zeros(n * n, np.int64),
            base.alpha, base.clip_sigma)
    assert np.array_equal(np.asarray(count).reshape(n, n),
                          base._count["delay"])
    assert np.allclose(np.asarray(mean).reshape(n, n),
                       base._mean["delay"], atol=1e-9, rtol=1e-9,
                       equal_nan=True)
    assert np.allclose(np.asarray(dev).reshape(n, n),
                       base._dev["delay"], atol=1e-9, rtol=1e-9,
                       equal_nan=True)


# ---------------------------------------------------------------------------
# campaigns: the jax backend reproduces the fleet report
# ---------------------------------------------------------------------------

@requires_jax
def test_campaign_backend_equivalence():
    """A seeded mini-campaign run under backend='jax' reports identical
    detection precision/recall (verdict identity propagated through the
    full engine) — the ISSUE's campaign-level acceptance check."""
    import dataclasses

    from repro.scenarios import montecarlo
    spec = montecarlo.get("fleet_smoke", n_trials=2)
    ref = montecarlo.run_campaign(spec).to_json()
    jx = montecarlo.run_campaign(
        dataclasses.replace(spec, backend="jax")).to_json()
    d_ref, d_jx = ref["aggregates"]["detection"], jx["aggregates"]["detection"]
    for k in ("precision", "recall", "n_faults", "true_positives",
              "false_positives"):
        assert d_ref.get(k) == d_jx.get(k), k
    # backend is recorded in the campaign config, everything else matches
    assert ref["aggregates"]["overhead"] == jx["aggregates"]["overhead"]
