"""Vectorized C4D path vs the pinned scalar reference.

The struct-of-arrays pipeline (RingJobTelemetry.window_arrays ->
prefilter_arrays -> vectorized detectors) must be *bit-identical* to the
scalar dataclass pipeline on the golden fault windows: same RNG stream,
same matrices, same verdicts, same master actions.  Any divergence is a
bug in the vectorized path — the scalar implementations are the spec.
"""
from dataclasses import replace

import numpy as np
import pytest

from repro.common import tracing
from repro.core.c4d.agent import C4Agent, prefilter_arrays, reports_to_window
from repro.core.c4d.detector import (C4DDetector, DelayMatrixDetector,
                                     DetectorConfig, HangDetector,
                                     RingWaitDetector,
                                     delay_verdicts_reference,
                                     hang_verdicts_reference,
                                     ring_wait_verdicts_reference)
from repro.core.c4d.master import C4DMaster
from repro.core.c4d.telemetry import (TelemetryArrays, delay_matrix,
                                      grouped_median, wait_matrix)
from repro.core.faults import Fault, RingJobTelemetry

N = 32

# the golden windows: one per syndrome family plus compound populations
GOLDEN_FAULTS = [
    [],
    [Fault("slow_src", rank=5)],
    [Fault("slow_dst", rank=7)],
    [Fault("slow_link", link=(3, 4))],
    [Fault("straggler", rank=9, severity=20)],
    [Fault("comm_hang", rank=11)],
    [Fault("noncomm_hang", rank=2)],
    [Fault("crash", rank=30)],
    [Fault("comm_hang", rank=1), Fault("slow_src", rank=6)],
    [Fault("slow_src", rank=3), Fault("slow_link", link=(10, 11)),
     Fault("straggler", rank=20, severity=25)],
]


# ---------------------------------------------------------------------------
# window synthesis: identical stream, identical columns
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("faults", GOLDEN_FAULTS)
def test_window_arrays_bit_identical(faults):
    a = RingJobTelemetry(n_ranks=N, seed=3)
    b = RingJobTelemetry(n_ranks=N, seed=3)
    ref = TelemetryArrays.from_window(a.window(0, faults))
    vec = b.window_arrays(0, faults)
    # both paths must consume the jitter RNG stream identically
    assert a.rng.bit_generator.state == b.rng.bit_generator.state
    for f in ("tr_src", "tr_dst", "tr_bytes", "tr_post", "tr_start",
              "tr_end", "hb_rank", "hb_seq", "hb_t", "op_rank", "op_seq"):
        x, y = getattr(ref, f), getattr(vec, f)
        assert x.shape == y.shape and np.array_equal(x, y), f


def test_window_arrays_interleaves_with_scalar():
    """One telemetry instance can serve both paths alternately."""
    a = RingJobTelemetry(n_ranks=N, seed=1)
    b = RingJobTelemetry(n_ranks=N, seed=1)
    fault = [Fault("slow_src", rank=4)]
    wins_a = [a.window(0, fault), a.window(1, fault)]
    aw0 = b.window_arrays(0, fault)
    w1 = b.window(1, fault)
    assert np.array_equal(TelemetryArrays.from_window(wins_a[0]).tr_end,
                          aw0.tr_end)
    assert np.array_equal(TelemetryArrays.from_window(wins_a[1]).tr_end,
                          TelemetryArrays.from_window(w1).tr_end)


def test_arrays_roundtrip():
    tel = RingJobTelemetry(n_ranks=N, seed=0)
    aw = tel.window_arrays(0, [Fault("slow_src", rank=5)])
    back = TelemetryArrays.from_window(aw.to_window())
    for f in ("tr_src", "tr_dst", "tr_bytes", "tr_post", "tr_start",
              "tr_end", "hb_rank", "hb_seq", "hb_t"):
        assert np.array_equal(getattr(aw, f), getattr(back, f)), f


# ---------------------------------------------------------------------------
# matrices + grouped median
# ---------------------------------------------------------------------------

def test_grouped_median_matches_numpy():
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 40, 1000)
    vals = rng.normal(size=1000)
    uk, med = grouped_median(keys, vals)
    assert np.array_equal(uk, np.unique(keys))
    for k, m in zip(uk, med):
        assert m == np.median(vals[keys == k])   # bit-identical, incl. even n


@pytest.mark.parametrize("faults", GOLDEN_FAULTS)
def test_matrices_bit_identical(faults):
    tel = RingJobTelemetry(n_ranks=N, seed=7)
    win = tel.window(0, faults)
    aw = TelemetryArrays.from_window(win)
    assert np.array_equal(delay_matrix(win, N), delay_matrix(aw, N),
                          equal_nan=True)
    assert np.array_equal(wait_matrix(win, N), wait_matrix(aw, N),
                          equal_nan=True)
    assert np.array_equal(delay_matrix(win, N, use_bandwidth=True),
                          delay_matrix(aw, N, use_bandwidth=True),
                          equal_nan=True)


# ---------------------------------------------------------------------------
# detectors vs their scalar references
# ---------------------------------------------------------------------------

def _planted_matrices():
    rng = np.random.default_rng(42)
    for _ in range(12):
        n = int(rng.integers(6, 24))
        d = rng.uniform(0.9, 1.1, (n, n))
        d[rng.random((n, n)) < 0.3] = np.nan     # sparse observations
        kind = rng.integers(0, 3)
        if kind == 0:
            d[int(rng.integers(0, n)), :] = 60.0
        elif kind == 1:
            d[:, int(rng.integers(0, n))] = 60.0
        else:
            d[int(rng.integers(0, n)), int(rng.integers(0, n))] = 60.0
        yield d


def test_delay_matrix_detector_matches_reference():
    det = DelayMatrixDetector(DetectorConfig())
    for d in _planted_matrices():
        assert det.analyze(d) == delay_verdicts_reference(d, det.cfg)


@pytest.mark.parametrize("faults", GOLDEN_FAULTS)
def test_ring_wait_and_hang_match_reference(faults):
    tel = RingJobTelemetry(n_ranks=N, seed=5)
    win = tel.window(0, faults)
    cfg = DetectorConfig()
    assert RingWaitDetector(cfg).analyze(win, N) == \
        ring_wait_verdicts_reference(win, cfg, N)
    assert HangDetector(cfg).analyze(win) == hang_verdicts_reference(win, cfg)


@pytest.mark.parametrize("faults", GOLDEN_FAULTS)
def test_composite_detector_arrays_equivalent(faults):
    tel = RingJobTelemetry(n_ranks=N, seed=9)
    win = tel.window(0, faults)
    aw = TelemetryArrays.from_window(win)
    det = C4DDetector()
    assert det.analyze(win, N) == det.analyze(aw, N)


# ---------------------------------------------------------------------------
# agent prefilter + full master pipeline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("faults", GOLDEN_FAULTS)
def test_prefilter_arrays_equivalent_matrices(faults):
    tel = RingJobTelemetry(n_ranks=N, seed=11)
    win = tel.window(0, faults)
    agents = [C4Agent(n, range(n * 8, (n + 1) * 8)) for n in range(N // 8)]
    merged_ref = reports_to_window([a.collect(win) for a in agents], win)
    merged_vec = prefilter_arrays(TelemetryArrays.from_window(win), 8,
                                  n_ranks=N)
    assert np.array_equal(delay_matrix(merged_ref, N),
                          delay_matrix(merged_vec, N), equal_nan=True)
    assert np.array_equal(wait_matrix(merged_ref, N),
                          wait_matrix(merged_vec, N), equal_nan=True)


def _lexsort_groups(keys, values):
    """The grouped median as one lexsort by (key, value): sorted unique
    keys, medians, counts, and each record's group."""
    order = np.lexsort((values, keys))
    k, v = keys[order], values[order]
    starts = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
    counts = np.diff(np.r_[starts, k.size])
    med = 0.5 * (v[starts + (counts - 1) // 2] + v[starts + counts // 2])
    inverse = np.empty(k.size, np.int64)
    inverse[order] = np.repeat(np.arange(starts.size), counts)
    return k[starts], med, counts, inverse


def _lexsort_prefilter(w, ranks_per_node, n, suspect_z=3.0):
    """The prefilter's transport columns with four lexsort grouped medians
    (node median, node MAD, edge transfer, edge wait): the oracle the
    shared key sort of ``prefilter_arrays`` is pinned to."""
    transfer, wait = w.tr_transfer(), w.tr_wait()
    node = w.tr_src // ranks_per_node
    _, node_med, _, idx = _lexsort_groups(node, transfer)
    _, node_mad, _, _ = _lexsort_groups(node,
                                        np.abs(transfer - node_med[idx]))
    mad = node_mad * 1.4826 + 1e-12
    sus = (transfer - node_med[idx]) / mad[idx] > suspect_z
    uk, med_t, counts, edge_of = _lexsort_groups(w.tr_src * n + w.tr_dst,
                                                 transfer)
    _, med_w, _, _ = _lexsort_groups(w.tr_src * n + w.tr_dst, wait)
    byte_sum = np.zeros(uk.size, np.int64)
    np.add.at(byte_sum, edge_of, w.tr_bytes)
    return {"tr_src": np.r_[uk // n, w.tr_src[sus]],
            "tr_dst": np.r_[uk % n, w.tr_dst[sus]],
            "tr_bytes": np.r_[byte_sum // counts, w.tr_bytes[sus]],
            "tr_post": np.r_[np.zeros(uk.size), w.tr_post[sus]],
            "tr_start": np.r_[med_w, w.tr_start[sus]],
            "tr_end": np.r_[med_w + med_t, w.tr_end[sus]]}


def _transports(w, keep):
    """``w`` with the transport records ``keep`` (a mask or an index)."""
    return replace(w, **{f: getattr(w, f)[keep]
                         for f in ("tr_src", "tr_dst", "tr_bytes",
                                   "tr_post", "tr_start", "tr_end")})


def _prefilter_case(case, faults):
    """(window, rank count) of one case of the oracle test."""
    if case == "golden":
        return RingJobTelemetry(n_ranks=N, seed=11).window_arrays(0, faults), N
    rng = np.random.default_rng(17)
    w = RingJobTelemetry(n_ranks=N, seed=13).window_arrays(0)
    if case == "dropped":
        # each edge keeps its records with a probability of its own
        edge = w.tr_src * N + w.tr_dst
        w = _transports(w, rng.random(edge.size)
                        < rng.random(N * N)[edge])
        counts = np.unique(w.tr_src * N + w.tr_dst, return_counts=True)[1]
        assert {1, 10} <= set(counts)
        return w, N
    if case == "nan_wait":
        # one edge left with two records, one of them with no post time:
        # its median wait is NaN
        edge = w.tr_src * N + w.tr_dst
        mine = np.flatnonzero(edge == edge[0])
        w = _transports(w, np.setdiff1d(np.arange(edge.size), mine[2:]))
        w.tr_post[0] = np.nan
        return w, N
    # skewed: node 0 of eight carries ~50x the records of each other node
    n = 64
    w = RingJobTelemetry(n_ranks=n, seed=13).window_arrays(0)
    mine = np.flatnonzero(w.tr_src // 8 == 0)
    extra = _transports(w, np.tile(mine, 49))
    extra.tr_end = extra.tr_end + rng.uniform(0, 1e-3, extra.tr_end.size)
    w = replace(w, **{f: np.r_[getattr(w, f), getattr(extra, f)]
                      for f in ("tr_src", "tr_dst", "tr_bytes", "tr_post",
                                "tr_start", "tr_end")})
    return w, n


#: (case, faults, grouped medians on the padded row sort, lexsort fallbacks)
PREFILTER_CASES = (
    [pytest.param("golden", f, 4, 0, id=f"golden{i}")
     for i, f in enumerate(GOLDEN_FAULTS)]
    + [pytest.param("dropped", [], 4, 0, id="dropped"),
       pytest.param("nan_wait", [], 4, 0, id="nan_wait"),
       pytest.param("skewed", [], 0, 4, id="skewed")])


@pytest.mark.parametrize("case, faults, row_sorts, fallbacks",
                         PREFILTER_CASES)
def test_prefilter_arrays_bit_identical_to_lexsort_oracle(case, faults,
                                                          row_sorts,
                                                          fallbacks):
    """The shared key sort and per-group row sorts return the merged
    window of four lexsort grouped medians, bit for bit and in order."""
    w, n = _prefilter_case(case, faults)
    want = _lexsort_prefilter(w, 8, n)
    before = tracing.counters()
    got = prefilter_arrays(w, 8, n_ranks=n)
    after = tracing.counters()
    for f, x in want.items():
        y = getattr(got, f)
        assert (y.dtype, y.shape) == (x.dtype, x.shape), f
        assert y.tobytes() == x.tobytes(), f
    assert got.hb_rank is w.hb_rank and got.hb_t is w.hb_t
    assert got.train is w.train
    if case == "nan_wait":
        assert np.isnan(got.tr_start).sum() == 1
    moved = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("c4d.prefilter.row_sorts",
                       "c4d.prefilter.lexsort_fallbacks")}
    assert moved == {"c4d.prefilter.row_sorts": row_sorts,
                     "c4d.prefilter.lexsort_fallbacks": fallbacks}


@pytest.mark.parametrize("faults", GOLDEN_FAULTS)
def test_master_actions_identical_across_paths(faults):
    """The pinned contract: scalar and vectorized ingest agree action-for-
    action (including confirmation-streak state across windows)."""
    a = RingJobTelemetry(n_ranks=N, seed=5)
    b = RingJobTelemetry(n_ranks=N, seed=5)
    ma = C4DMaster(n_ranks=N, ranks_per_node=8)
    mb = C4DMaster(n_ranks=N, ranks_per_node=8)
    for wid in range(3):
        assert ma.ingest(a.window(wid, faults)) == \
            mb.ingest(b.window_arrays(wid, faults))


def test_vectorized_pipeline_scales_past_scalar_sizes():
    """Sanity at campaign scale: a 1024-rank window detects the planted
    fault on the arrays path (wall-clock guard lives in the benchmark)."""
    tel = RingJobTelemetry(n_ranks=1024, seed=0)
    master = C4DMaster(n_ranks=1024, ranks_per_node=8)
    fault = [Fault("slow_src", rank=321, severity=9.0)]
    acts = []
    for wid in range(3):
        acts = master.ingest(tel.window_arrays(wid, faults=fault))
        if acts:
            break
    assert acts and acts[0].node_id == 321 // 8
