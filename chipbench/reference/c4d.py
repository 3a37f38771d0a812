"""Plain reference of C4D's per-window scoring and the master's fold.

The same semantics as the system under test, written independently and
straightforwardly in NumPy over the sparse (src, dst) pairs, with no
dense n x n matrices (at 10,240 ranks a dense matrix is 840 MB):

1. agents' prefilter: per node, the median and MAD of the transfer
   latencies flag raw suspects above ``suspect_z``; per edge, the median
   transfer and wait become one summary record (start = median wait,
   end = median wait + median transfer, bytes = total // count), and the
   suspects follow raw;
2. hang analysis: the last heartbeat sequence per rank; a rank at least
   ``hang_grace`` below the median is hung (comm hang if it sent any
   transport, else non-comm hang), and hangs pre-empt the slow path;
3. slow path: per pair, the median per-byte transfer latency (D) and
   wait (W); robust z over all pairs (median, 1.4826 MAD); a row of D
   with at least 2 and ``row_col_fraction`` of its pairs hot implicates
   the source, a column the destination, a remaining hot pair the link;
   a hot wait over a healthy transfer implicates the sender;
4. master: verdicts fold to nodes (a link to its source's node); a hang
   acts at once, a slow syndrome on its ``confirm_windows``-th
   consecutive window; a node absent from a window loses its streak.

``dtype`` sets the floating type of every computed value; float64 is
the configuration's precision, float32 is the control one step below.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

COMM_SLOW_SRC = "comm_slow_source"
COMM_SLOW_DST = "comm_slow_destination"
COMM_SLOW_LINK = "comm_slow_link"
NONCOMM_SLOW = "noncomm_slow"
COMM_HANG = "comm_hang"
NONCOMM_HANG = "noncomm_hang"
IMMEDIATE = (COMM_HANG, NONCOMM_HANG)


@dataclass(frozen=True)
class Thresholds:
    suspect_z: float = 3.0
    mad_threshold: float = 5.0
    row_col_fraction: float = 0.6
    hang_grace: float = 3.0
    min_observations: int = 1
    confirm_windows: int = 2


Verdict = Tuple[str, Optional[int], Optional[Tuple[int, int]], float]


def group_median(keys: np.ndarray, values: np.ndarray):
    """(unique keys, median per key, count per key, group of each element)
    — the median of an even group is the mean of its two middle values."""
    by_value = np.argsort(values)
    order = by_value[np.argsort(keys[by_value], kind="stable")]
    k, v = keys[order], values[order]
    starts = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
    counts = np.diff(np.r_[starts, k.size])
    med = (v[starts + (counts - 1) // 2] + v[starts + counts // 2]) * v.dtype.type(0.5)
    group = np.empty(k.size, np.int64)
    group[order] = np.repeat(np.arange(starts.size), counts)
    return k[starts], med, counts, group


@dataclass
class Merged:
    """The master-side window after the agents' prefilter."""
    src: np.ndarray
    dst: np.ndarray
    nbytes: np.ndarray
    post: np.ndarray
    start: np.ndarray
    end: np.ndarray
    hb_rank: np.ndarray
    hb_seq: np.ndarray


def prefilter(w, n: int, ranks_per_node: int, th: Thresholds,
              dtype=np.float64) -> Merged:
    post = w.tr_post.astype(dtype)
    start = w.tr_start.astype(dtype)
    end = w.tr_end.astype(dtype)
    transfer = np.maximum(end - start, dtype(1e-9))
    wait = start - post
    node = w.tr_src // ranks_per_node
    _, node_med, _, g = group_median(node, transfer)
    dev = np.abs(transfer - node_med[g])
    _, node_mad, _, _ = group_median(node, dev)
    mad = node_mad * dtype(1.4826) + dtype(1e-12)
    suspect = (transfer - node_med[g]) / mad[g] > th.suspect_z

    key = w.tr_src * n + w.tr_dst
    uk, med_t, counts, edge = group_median(key, transfer)
    _, med_w, _, _ = group_median(key, wait)
    byte_sum = np.zeros(uk.size, np.int64)
    np.add.at(byte_sum, edge, w.tr_bytes)
    return Merged(
        src=np.r_[uk // n, w.tr_src[suspect]],
        dst=np.r_[uk % n, w.tr_dst[suspect]],
        nbytes=np.r_[byte_sum // np.maximum(counts, 1), w.tr_bytes[suspect]],
        post=np.r_[np.zeros(uk.size, dtype), post[suspect]],
        start=np.r_[med_w, start[suspect]],
        end=np.r_[med_w + med_t, end[suspect]],
        hb_rank=w.hb_rank, hb_seq=w.hb_seq)


def _robust_z(values: np.ndarray) -> np.ndarray:
    t = values.dtype.type
    med = np.median(values)
    mad = np.median(np.abs(values - med))
    scale = t(1.4826) * mad + t(1e-12) * max(abs(med), t(1e-12)) + t(1e-30)
    return (values - med) / scale


def score(m: Merged, n: int, th: Thresholds) -> List[Verdict]:
    """The composite verdicts of one merged window, in the order the
    system lists them."""
    ranks, inv = np.unique(m.hb_rank, return_inverse=True)
    last = np.full(ranks.size, np.iinfo(np.int64).min)
    np.maximum.at(last, inv, m.hb_seq)
    med = np.median(last)
    hung = np.flatnonzero(med - last >= th.hang_grace)
    if hung.size:
        senders = np.unique(m.src)
        return [(COMM_HANG if np.isin(ranks[i], senders) else NONCOMM_HANG,
                 int(ranks[i]), None, float(med - last[i])) for i in hung]

    dtype = m.start.dtype.type
    transfer = np.maximum(m.end - m.start, dtype(1e-9))
    per_byte = transfer / np.maximum(m.nbytes, 1).astype(m.start.dtype)
    key = m.src * n + m.dst
    pk, dmed, _, _ = group_median(key, per_byte)
    _, wmed, _, _ = group_median(key, m.start - m.post)
    zd = _robust_z(dmed)
    zw = _robust_z(wmed)
    src, dst = pk // n, pk % n
    hot = zd > th.mad_threshold

    def axis(idx):
        hot_n = np.bincount(idx, weights=hot, minlength=n)
        obs_n = np.bincount(idx, minlength=n)
        sel = ((obs_n >= th.min_observations)
               & (hot_n >= np.maximum(1, th.row_col_fraction * obs_n))
               & (hot_n >= 2))
        best = np.full(n, -np.inf)
        np.maximum.at(best, idx, zd)
        return sel, best

    row_sel, row_best = axis(src)
    col_sel, col_best = axis(dst)
    out: List[Verdict] = []
    out += [(COMM_SLOW_SRC, int(i), None, float(row_best[i]))
            for i in np.flatnonzero(row_sel)]
    out += [(COMM_SLOW_DST, int(j), None, float(col_best[j]))
            for j in np.flatnonzero(col_sel)]
    point = hot & ~row_sel[src] & ~col_sel[dst]
    out += [(COMM_SLOW_LINK, None, (int(src[g]), int(dst[g])), float(zd[g]))
            for g in np.flatnonzero(point)]
    wmask = (zw > th.mad_threshold) & ~hot
    wbest = np.full(n, -np.inf)
    np.maximum.at(wbest, src[wmask], zw[wmask])
    out += [(NONCOMM_SLOW, int(i), None, float(wbest[i]))
            for i in np.flatnonzero(np.isfinite(wbest))]
    return out


class Master:
    """The legacy fold of verdicts into node actions."""

    def __init__(self, n_ranks: int, ranks_per_node: int,
                 th: Thresholds = Thresholds(), dtype=np.float64):
        self.n = n_ranks
        self.rpn = ranks_per_node
        self.th = th
        self.dtype = dtype
        self.pending: Dict[int, int] = {}

    def ingest(self, window) -> Tuple[List[Verdict], list, Merged]:
        merged = prefilter(window, self.n, self.rpn, self.th, self.dtype)
        verdicts = score(merged, self.n, self.th)
        by_node: Dict[int, List[Verdict]] = {}
        for v in verdicts:
            rank = v[1] if v[1] is not None else v[2][0]
            by_node.setdefault(rank // self.rpn, []).append(v)
        actions = []
        for node, vs in by_node.items():
            streak = self.pending.get(node, 0) + 1
            if (any(v[0] in IMMEDIATE for v in vs)
                    or streak >= self.th.confirm_windows):
                actions.append((node, [v[:3] for v in vs]))
                self.pending.pop(node, None)
            else:
                self.pending[node] = streak
        for node in list(self.pending):
            if node not in by_node:
                self.pending.pop(node)
        return verdicts, actions, merged
