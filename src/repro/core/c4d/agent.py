"""C4a — the per-node C4 agent (paper Fig. 4).

The agent is the intermediary between the enhanced CCL (which emits raw
records on every rank of the node) and the central C4D master.  To keep the
monitoring cost low it batches records per window and *prefilters*: healthy
transport records are aggregated into per-edge summaries, while suspicious
records (robust z-score above a loose local threshold) are forwarded raw.

``prefilter_arrays`` is the vectorized fleet-scale equivalent: it runs the
per-node batching + prefiltering of *every* agent in one pass over a
struct-of-arrays window and emits the master-side merged window directly,
producing the same per-edge medians and raw suspects as ``C4Agent.collect``
+ ``reports_to_window`` (equivalence pinned in
tests/test_c4d_vectorized.py).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.common.tracing import count, span
from repro.core.c4d.telemetry import (Heartbeat, TelemetryArrays,
                                      TelemetryWindow, TransportRecord)


@dataclass
class EdgeSummary:
    src_rank: int
    dst_rank: int
    count: int
    median_transfer: float
    median_wait: float
    max_transfer: float
    total_bytes: int


@dataclass
class AgentReport:
    node_id: int
    window_id: int
    summaries: List[EdgeSummary] = field(default_factory=list)
    raw_suspects: List[TransportRecord] = field(default_factory=list)
    heartbeats: List[Heartbeat] = field(default_factory=list)
    ops_count: int = 0


class C4Agent:
    """Per-node batching + prefiltering agent (paper §3.1, Fig. 4).

    ``suspect_z`` is the loose *local* robust-z threshold: records above it
    are forwarded raw to the master (the tight decision threshold lives in
    ``detector.DetectorConfig.mad_threshold``); everything else collapses
    into per-edge medians, keeping monitoring overhead sub-1 %."""

    def __init__(self, node_id: int, ranks: Sequence[int],
                 suspect_z: float = 3.0):
        self.node_id = node_id
        self.ranks = set(ranks)
        self.suspect_z = suspect_z

    def collect(self, window: TelemetryWindow) -> AgentReport:
        """Batch this node's records for one window."""
        mine_t = [t for t in window.transports if t.src_rank in self.ranks]
        mine_h = [h for h in window.heartbeats if h.rank in self.ranks]
        mine_o = [o for o in window.ops if o.rank in self.ranks]
        report = AgentReport(self.node_id, window.window_id,
                             heartbeats=mine_h, ops_count=len(mine_o))
        by_edge: Dict[Tuple[int, int], List[TransportRecord]] = {}
        for t in mine_t:
            by_edge.setdefault((t.src_rank, t.dst_rank), []).append(t)
        transfers = np.array([t.transfer for t in mine_t]) if mine_t else np.array([1.0])
        med = float(np.median(transfers))
        mad = float(np.median(np.abs(transfers - med))) * 1.4826 + 1e-12
        for (s, r), recs in sorted(by_edge.items()):
            ts = np.array([t.transfer for t in recs])
            ws = np.array([t.wait for t in recs])
            report.summaries.append(EdgeSummary(
                s, r, len(recs), float(np.median(ts)), float(np.median(ws)),
                float(ts.max()), int(sum(t.msg_bytes for t in recs))))
            for t in recs:
                if (t.transfer - med) / mad > self.suspect_z:
                    report.raw_suspects.append(t)
        return report


def reports_to_window(reports: Sequence[AgentReport],
                      template: TelemetryWindow) -> TelemetryWindow:
    """Master-side reassembly: summaries become representative transport
    records (median latency per edge), suspects are kept raw."""
    win = TelemetryWindow(window_id=template.window_id, comms=template.comms,
                          t_begin=template.t_begin, t_end=template.t_end,
                          train=template.train)
    for rep in reports:
        win.heartbeats.extend(rep.heartbeats)
        for s in rep.summaries:
            win.transports.append(TransportRecord(
                iteration=-1, src_rank=s.src_rank, dst_rank=s.dst_rank,
                msg_bytes=s.total_bytes // max(s.count, 1),
                t_post=0.0, t_start=s.median_wait,
                t_end=s.median_wait + s.median_transfer))
        win.transports.extend(rep.raw_suspects)
    return win


#: the padded ``(groups, width)`` matrix of ``_KeyGroups`` is used while it
#: holds at most this many slots per record; past it (a few groups far
#: larger than the rest) one lexsort by (group, value) takes its place
_ROW_SORT_SLOTS_PER_RECORD = 4


class _KeyGroups:
    """Groups of a key-ordered record array, as contiguous runs.

    ``median(values)`` takes one value per record, in key order, and
    returns each group's median, bit-identical to ``grouped_median``: it
    reads the same two order statistics of the same multiset and averages
    them as ``0.5 * (lo + hi)``.  Regular groups are scattered into a
    NaN-padded ``(groups, width)`` matrix sorted along its rows (NaN sorts
    last there, as in ``lexsort``, so a NaN value reads the same);
    skewed ones fall back to one lexsort by (group, value)."""

    def __init__(self, sorted_keys: np.ndarray):
        t = sorted_keys.size
        self.starts = np.flatnonzero(
            np.r_[True, sorted_keys[1:] != sorted_keys[:-1]])
        self.counts = np.diff(np.r_[self.starts, t])
        g = self.starts.size
        self.width = int(self.counts.max())
        if g * self.width <= _ROW_SORT_SLOTS_PER_RECORD * t:
            first = np.arange(g, dtype=np.int64) * self.width
            self.slot = np.arange(t, dtype=np.int64) + np.repeat(
                first - self.starts, self.counts)
        else:
            first = self.starts
            self.slot = None
            self.keys = sorted_keys
        self.lo = first + (self.counts - 1) // 2
        self.hi = first + self.counts // 2

    def median(self, values: np.ndarray) -> np.ndarray:
        if self.slot is not None:
            count("c4d.prefilter.row_sorts")
            mat = np.full(self.starts.size * self.width, np.nan)
            mat[self.slot] = values
            mat.reshape(-1, self.width).sort(axis=1)
        else:
            count("c4d.prefilter.lexsort_fallbacks")
            mat = values[np.lexsort((values, self.keys))]
        return 0.5 * (mat[self.lo] + mat[self.hi])


def prefilter_arrays(window: TelemetryArrays, ranks_per_node: int,
                     suspect_z: float = 3.0,
                     n_ranks: Optional[int] = None) -> TelemetryArrays:
    """All agents' collect + master reassembly, vectorized (paper Fig. 4).

    One pass over the struct-of-arrays window:

      1. one stable sort of the edge key ``src * n + dst`` groups the
         records by edge, and so by node (``src // ranks_per_node``), whose
         records form contiguous runs of the same order,
      2. per-node robust statistics (median / MAD of the node's transfer
         latencies) flag raw suspects above ``suspect_z``,
      3. per-edge grouped medians become the representative summary records
         (``t_start = median wait``, ``t_end = median wait + median
         transfer``, bytes = total // count — the exact reassembly
         arithmetic of ``reports_to_window``),
      4. heartbeats pass through untouched.

    Returns the merged master-side window; downstream detection on it is
    verdict-identical to the scalar agent path.
    """
    n = n_ranks or window.n_ranks()

    if window.tr_src.size:
        with span("c4d.prefilter.groups"):
            key = window.tr_src * n + window.tr_dst
            order = np.argsort(key, kind="stable")
            sk = key[order]
            edges = _KeyGroups(sk)
            nodes = _KeyGroups(sk // n // ranks_per_node)
            transfer = window.tr_transfer()[order]

        with span("c4d.prefilter.node_stats"):
            # per-node median / MAD, mapped back onto each record
            node_med = np.repeat(nodes.median(transfer), nodes.counts)
            mad = nodes.median(np.abs(transfer - node_med)) * 1.4826 + 1e-12
            suspect = np.empty(order.size, bool)
            suspect[order] = ((transfer - node_med)
                              / np.repeat(mad, nodes.counts) > suspect_z)

        with span("c4d.prefilter.edge_medians"):
            uk = sk[edges.starts]
            med_t = edges.median(transfer)
            med_w = edges.median(window.tr_wait()[order])
            byte_sum = np.add.reduceat(window.tr_bytes[order], edges.starts)

        m_src = np.r_[uk // n, window.tr_src[suspect]]
        m_dst = np.r_[uk % n, window.tr_dst[suspect]]
        m_bytes = np.r_[byte_sum // edges.counts,
                        window.tr_bytes[suspect]]
        m_post = np.r_[np.zeros(uk.size), window.tr_post[suspect]]
        m_start = np.r_[med_w, window.tr_start[suspect]]
        m_end = np.r_[med_w + med_t, window.tr_end[suspect]]
    else:
        m_src = m_dst = np.empty(0, np.int64)
        m_bytes = np.empty(0, np.int64)
        m_post = m_start = m_end = np.empty(0)

    return TelemetryArrays(
        window_id=window.window_id, comms=list(window.comms),
        tr_src=m_src, tr_dst=m_dst, tr_bytes=m_bytes,
        tr_post=m_post, tr_start=m_start, tr_end=m_end,
        hb_rank=window.hb_rank, hb_seq=window.hb_seq, hb_t=window.hb_t,
        t_begin=window.t_begin, t_end=window.t_end,
        # train signals ride past the prefilter untouched: they are already
        # one summary row per rank, there is nothing to batch
        train=window.train)
