"""Host adapters: TelemetryArrays windows -> jit kernels -> Verdict lists.

``analyze_arrays`` is the jax-backend body of ``C4DDetector.analyze`` —
same composite semantics (hang analysis pre-empts slow analysis; the
adaptive baseline advances only on hang-free windows), same Verdict
objects field-for-field (tests/test_jaxsim.py pins equality on the Table-3
golden windows, score floats and detail strings included).  It is the
B = 1 case of ``score_windows_batched`` — every consumer (streaming
master ingest, campaigns, benches) runs through the same fused pipeline.

The fused pipeline per window (two device dispatches total):

  1. host: group the transport keys (``_WindowLayout`` — a radix
     ``np.argsort`` plus run-length extents) and scatter delay/wait
     values into the ``(2, g_pad, m_pad)`` per-group matrix;
  2. device (``fused_window_kernel``): segmented pair medians (row sorts)
     + heartbeat hang scoring, one jit boundary;
  3. host: hang pre-emption, then the per-group z centers/scales
     (``_mixed_center_scale`` — MAD math stays in NumPy so XLA's FMA
     contraction cannot shift the last ulp; see kernels.py);
  4. device (``slow_fold_kernel``): z folds -> row/col/point/wait verdict
     bits;
  5. host: the small Verdict list, and the NumPy ``AdaptiveBaseline``
     advance (``update_cells`` — the same winsorized math, so a
     jax-backend streaming master stays bit-compatible with the NumPy one
     window for window).

The MAD center/scale step is why the pipeline is two dispatches rather
than one: it must run in NumPy for bit identity, and it consumes the
medians, so a single fused boundary would put ``a*b + c`` chains back on
the exact path.  Everything around it is fused.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from repro.common.jax_compat import enable_x64
from repro.common.tracing import count, span
from repro.core.c4d.baseline import MEANAD_TO_SIGMA, AdaptiveBaseline
from repro.core.c4d.detector import (COMM_HANG, COMM_SLOW_DST, COMM_SLOW_LINK,
                                     COMM_SLOW_SRC, DetectorConfig,
                                     NONCOMM_HANG, NONCOMM_SLOW, Verdict)
from repro.core.c4d.telemetry import TelemetryArrays
from repro.core.jaxsim.kernels import (PAD_KEY, batched_fused_window_kernel,
                                       batched_slow_fold_kernel,
                                       fused_window_kernel, pad_len,
                                       slow_fold_kernel)

import jax.numpy as jnp


# ---------------------------------------------------------------------------
# window layouts and padding (host side; everything lands in power-of-two
# buckets)
# ---------------------------------------------------------------------------

class _WindowLayout:
    """Group structure of one window's transport key array.

    ``scatter`` maps each transport (original order) to its flat slot in
    the ``(g_pad, m_pad)`` per-group value matrix:
    ``mat.reshape(-1)[scatter] = values``."""

    __slots__ = ("g_pad", "m_pad", "scatter", "gkey", "counts", "gvalid")

    def __init__(self, keys: np.ndarray):
        t = keys.size
        order = np.argsort(keys, kind="stable")   # radix sort on int64 keys
        sk = keys[order]
        if t:
            starts = np.flatnonzero(np.r_[True, sk[1:] != sk[:-1]])
            counts = np.diff(np.r_[starts, t])
        else:
            starts = np.zeros(0, np.int64)
            counts = np.zeros(0, np.int64)
        g = starts.size
        self.g_pad = pad_len(g)
        self.m_pad = pad_len(int(counts.max()) if g else 1)
        gid = np.repeat(np.arange(g, dtype=np.int64), counts)
        col = np.arange(t, dtype=np.int64) - np.repeat(starts, counts)
        scatter = np.empty(t, np.int64)
        scatter[order] = gid * self.m_pad + col
        self.scatter = scatter
        self.gkey = np.full(self.g_pad, PAD_KEY, np.int64)
        self.gkey[:g] = sk[starts]
        self.counts = np.zeros(self.g_pad, np.int64)
        self.counts[:g] = counts
        self.gvalid = np.zeros(self.g_pad, bool)
        self.gvalid[:g] = True


def _pad_index(values: np.ndarray, size: int) -> np.ndarray:
    out = np.zeros(size, np.int64)
    out[:values.size] = values
    return out


class _PackedWindow:
    """One window's fused-kernel inputs (layout + scatter matrix + padded
    heartbeats + per-rank deficit offsets)."""

    __slots__ = ("layout", "vmat", "hb_rank", "hb_seq", "hb_valid",
                 "offsets")

    def __init__(self, window: TelemetryArrays, n: int, n_pad: int,
                 baseline: Optional[AdaptiveBaseline]):
        t = int(window.tr_src.size)
        with span("c4d.layout"):
            keys = (window.tr_src * n + window.tr_dst if t
                    else np.zeros(0, np.int64))
            lay = _WindowLayout(keys)
        with span("c4d.pack"):
            vmat = np.full((2, lay.g_pad, lay.m_pad), np.inf)
            if t:
                flat = vmat.reshape(2, -1)
                transfer = window.tr_transfer()
                flat[0, lay.scatter] = transfer / np.maximum(window.tr_bytes, 1)
                flat[1, lay.scatter] = window.tr_wait()
            h = int(window.hb_rank.size)
            hp = pad_len(h)
            self.layout = lay
            self.vmat = vmat
            self.hb_rank = _pad_index(window.hb_rank, hp)
            self.hb_seq = _pad_index(window.hb_seq, hp)
            self.hb_valid = np.zeros(hp, bool)
            self.hb_valid[:h] = True
            self.offsets = np.zeros(n_pad)
            if baseline is not None and n:
                self.offsets[:n] = baseline.deficit_offset(np.arange(n))

    def bucket(self):
        """Static-shape signature: windows in the same bucket vmap
        together."""
        return (self.layout.g_pad, self.layout.m_pad, self.hb_rank.size)


def _mixed_center_scale(values: np.ndarray, valid: np.ndarray,
                        gkey: np.ndarray, n: int,
                        baseline: Optional[AdaptiveBaseline], kind: str):
    """Per-group z normalisers for ``z = (median - center) / scale``.

    Cross-sectional center/scale come from the window's own group medians
    (``detector._robust_z``'s formula verbatim); where an attached baseline
    is warm, the cell's EWMA mean and MEANAD-scaled dev take over
    (``AdaptiveBaseline.z``).  All of it is NumPy on purpose — these are
    the only multiply-add chains on the exact path, and XLA would contract
    them into FMAs (kernels.py module docstring)."""
    size = values.size
    center = np.zeros(size)
    scale = np.ones(size)
    vals = values[valid]
    if vals.size == 0:
        return center, scale
    med = np.median(vals)
    mad = np.median(np.abs(vals - med))
    cs = 1.4826 * mad + 1e-12 * max(abs(med), 1e-12) + 1e-30
    c = np.full(vals.size, med)
    s = np.full(vals.size, cs)
    if baseline is not None:
        rows = gkey[valid] // n
        cols = gkey[valid] % n
        bm, bd, bc = baseline.cell_stats(kind, rows, cols)
        bscale = (MEANAD_TO_SIGMA * bd
                  + 1e-12 * np.maximum(np.abs(bm), 1e-12) + 1e-30)
        use = bc >= baseline.warm_windows
        c = np.where(use, bm, c)
        s = np.where(use, bscale, s)
    center[valid] = c
    scale[valid] = s
    return center, scale


# ---------------------------------------------------------------------------
# Verdict builders (shared by the single-window and batched paths)
# ---------------------------------------------------------------------------

def _hang_verdict_list(hung: np.ndarray, seqs: np.ndarray, med: float,
                       is_src: np.ndarray) -> List[Verdict]:
    out = []
    for r in np.flatnonzero(hung):
        s = int(seqs[r])
        syndrome = COMM_HANG if is_src[r] else NONCOMM_HANG
        out.append(Verdict(syndrome, rank=int(r), score=float(med - s),
                           detail=f"seq {s} vs median {med:.0f}"))
    return out


def _fold_verdict_list(res: dict, gkey: np.ndarray, n: int) -> List[Verdict]:
    verdicts: List[Verdict] = []
    row_sel = np.asarray(res["row_sel"])[:n]
    row_score = np.asarray(res["row_score"])
    row_hot = np.asarray(res["row_hot"])
    row_obs = np.asarray(res["row_obs"])
    for i in np.flatnonzero(row_sel):
        verdicts.append(Verdict(
            COMM_SLOW_SRC, rank=int(i), score=float(row_score[i]),
            detail=f"row {i}: {int(row_hot[i])}/{int(row_obs[i])} hot"))
    col_sel = np.asarray(res["col_sel"])[:n]
    col_score = np.asarray(res["col_score"])
    col_hot = np.asarray(res["col_hot"])
    col_obs = np.asarray(res["col_obs"])
    for j in np.flatnonzero(col_sel):
        verdicts.append(Verdict(
            COMM_SLOW_DST, rank=int(j), score=float(col_score[j]),
            detail=f"col {j}: {int(col_hot[j])}/{int(col_obs[j])} hot"))
    point = np.asarray(res["point"])
    zd = np.asarray(res["zd"])
    for g in np.flatnonzero(point):
        i, j = divmod(int(gkey[g]), n)
        verdicts.append(Verdict(COMM_SLOW_LINK, link=(i, j),
                                score=float(zd[g]),
                                detail=f"point ({i},{j})"))
    wait_sel = np.asarray(res["wait_sel"])[:n]
    wait_score = np.asarray(res["wait_score"])
    for i in np.flatnonzero(wait_sel):
        verdicts.append(Verdict(NONCOMM_SLOW, rank=int(i),
                                score=float(wait_score[i]),
                                detail="receiver wait w/ healthy transfer"))
    return verdicts


# ---------------------------------------------------------------------------
# the composite analysis (drop-in for C4DDetector.analyze on arrays windows)
# ---------------------------------------------------------------------------

def analyze_arrays(window: TelemetryArrays, cfg: DetectorConfig,
                   n_ranks: Optional[int] = None,
                   baseline: Optional[AdaptiveBaseline] = None
                   ) -> List[Verdict]:
    """One window through the fused pipeline — the B = 1 case of
    ``score_windows_batched``."""
    return score_windows_batched([window], cfg, n_ranks=n_ranks,
                                 baseline=baseline)[0]


def _score_single(window: TelemetryArrays, cfg: DetectorConfig, n: int,
                  n_pad: int, baseline: Optional[AdaptiveBaseline]
                  ) -> List[Verdict]:
    """Fused scoring of one window (two dispatches), baseline advance
    included — the unit the sequential paths share."""
    pw = _PackedWindow(window, n, n_pad, baseline)
    lay = pw.layout
    with enable_x64():
        with span("c4d.fused"):
            res = fused_window_kernel(
                pw.vmat, lay.counts, lay.gkey, lay.gvalid, pw.hb_rank,
                pw.hb_seq, pw.hb_valid, jnp.asarray(pw.offsets),
                cfg.hang_grace, n=n, n_pad=n_pad)
            hung = np.asarray(res["hung"])
        if hung.any():
            # hangs pre-empt slow analysis and freeze the baseline —
            # identical to the NumPy composite
            count("c4d.hang_windows")
            with span("c4d.hang_verdicts"):
                return _hang_verdict_list(hung, np.asarray(res["seqs"]),
                                          float(res["med"]),
                                          np.asarray(res["is_src"]))
        count("c4d.fold_windows")
        with span("c4d.center_scale"):
            dmed = np.asarray(res["dmed"])
            wmed = np.asarray(res["wmed"])
            cd, sd = _mixed_center_scale(dmed, lay.gvalid, lay.gkey, n,
                                         baseline, "delay")
            cw, sw = _mixed_center_scale(wmed, lay.gvalid, lay.gkey, n,
                                         baseline, "wait")
        with span("c4d.fold"):
            fold = slow_fold_kernel(lay.gkey, lay.gvalid, dmed, wmed, cd, sd,
                                    cw, sw, cfg.mad_threshold,
                                    cfg.row_col_fraction,
                                    cfg.min_observations, n=n, n_pad=n_pad)
            verdicts = _fold_verdict_list(fold, lay.gkey, n)
    if baseline is not None:
        _advance_baseline(window, cfg, n, baseline, lay.gkey, lay.gvalid,
                          dmed, wmed)
    return verdicts


def score_windows_batched(windows: Sequence[TelemetryArrays],
                          cfg: DetectorConfig,
                          n_ranks: Optional[int] = None,
                          baseline: Optional[AdaptiveBaseline] = None
                          ) -> List[List[Verdict]]:
    """Score B windows end to end; returns one full Verdict list per
    window (hang pre-emption included) in input order.

    Windows sharing a static-shape bucket (group/pad/heartbeat sizes) are
    scored as ONE vmapped fused dispatch, then the hang-free survivors
    share one vmapped fold dispatch per bucket — the campaign/streaming
    batch entry.  With an adaptive ``baseline`` the windows are scored
    sequentially instead: the EWMA advances between windows, so window i+1
    is not independent of window i and batching would change verdicts (the
    legacy default master is baseline-free, which is where the batch path
    applies)."""
    wins = list(windows)
    if not wins:
        return []
    n = n_ranks or wins[0].n_ranks()
    n_pad = pad_len(n)
    if baseline is not None or len(wins) == 1:
        return [_score_single(w, cfg, n, n_pad, baseline) for w in wins]

    packs = [_PackedWindow(w, n, n_pad, None) for w in wins]
    buckets: dict = {}
    for i, pw in enumerate(packs):
        buckets.setdefault(pw.bucket(), []).append(i)

    results: List[Optional[List[Verdict]]] = [None] * len(wins)
    slow: dict = {}          # g_pad -> [(index, dmed, wmed)]
    with enable_x64():
        fused_fn = batched_fused_window_kernel(n, n_pad)
        for idxs in buckets.values():
            with span("c4d.fused", windows=len(idxs)):
                res = fused_fn(
                    np.stack([packs[i].vmat for i in idxs]),
                    np.stack([packs[i].layout.counts for i in idxs]),
                    np.stack([packs[i].layout.gkey for i in idxs]),
                    np.stack([packs[i].layout.gvalid for i in idxs]),
                    np.stack([packs[i].hb_rank for i in idxs]),
                    np.stack([packs[i].hb_seq for i in idxs]),
                    np.stack([packs[i].hb_valid for i in idxs]),
                    np.stack([packs[i].offsets for i in idxs]),
                    cfg.hang_grace)
                res = {k: np.asarray(v) for k, v in res.items()}
            for b, i in enumerate(idxs):
                hung = res["hung"][b]
                if hung.any():
                    count("c4d.hang_windows")
                    with span("c4d.hang_verdicts"):
                        results[i] = _hang_verdict_list(
                            hung, res["seqs"][b], float(res["med"][b]),
                            res["is_src"][b])
                else:
                    slow.setdefault(packs[i].layout.g_pad, []).append(
                        (i, res["dmed"][b], res["wmed"][b]))

        fold_fn = batched_slow_fold_kernel(n, n_pad)
        for entries in slow.values():
            count("c4d.fold_windows", len(entries))
            with span("c4d.center_scale", windows=len(entries)):
                gkey = np.stack([packs[i].layout.gkey for i, _, _ in entries])
                valid = np.stack([packs[i].layout.gvalid
                                  for i, _, _ in entries])
                dmed = np.stack([d for _, d, _ in entries])
                wmed = np.stack([w for _, _, w in entries])
                cd = np.empty_like(dmed)
                sd = np.empty_like(dmed)
                cw = np.empty_like(wmed)
                sw = np.empty_like(wmed)
                for b, (i, _, _) in enumerate(entries):
                    cd[b], sd[b] = _mixed_center_scale(
                        dmed[b], valid[b], gkey[b], n, None, "delay")
                    cw[b], sw[b] = _mixed_center_scale(
                        wmed[b], valid[b], gkey[b], n, None, "wait")
            with span("c4d.fold", windows=len(entries)):
                fold = fold_fn(gkey, valid, dmed, wmed, cd, sd, cw, sw,
                               cfg.mad_threshold, cfg.row_col_fraction,
                               cfg.min_observations)
                fold = {k: np.asarray(v) for k, v in fold.items()}
                for b, (i, _, _) in enumerate(entries):
                    results[i] = _fold_verdict_list(
                        {k: v[b] for k, v in fold.items()}, gkey[b], n)
    return results        # type: ignore[return-value]


def _advance_baseline(window, cfg, n, baseline, gkey, valid, dmed, wmed):
    """Fold the hang-free window into the EWMA history — the sparse twin of
    ``C4DDetector._advance_baseline`` (same cells, same order, same
    winsorized math via ``AdaptiveBaseline.update_cells``)."""
    if valid.any():
        rows = gkey[valid] // n
        cols = gkey[valid] % n
        baseline.update_cells("delay", rows, cols, dmed[valid])
        baseline.update_cells("wait", rows, cols, wmed[valid])
    if window.hb_rank.size:
        ranks, inv = np.unique(window.hb_rank, return_inverse=True)
        seqs = np.full(ranks.size, np.iinfo(np.int64).min)
        np.maximum.at(seqs, inv, window.hb_seq)
        deficit = np.median(seqs) - seqs
        adj = deficit - baseline.deficit_offset(ranks)
        baseline.update_deficit(ranks, deficit.astype(float),
                                exclude=adj >= cfg.hang_grace)
