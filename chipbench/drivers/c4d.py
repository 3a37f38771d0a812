"""Driver of C4D cells: one streaming ``C4DMaster`` scores a stream of
telemetry windows in a closed loop, one window in flight.

Set-up builds the master as the fleet service builds it and scores the
mix's warm-up episodes, which run every path the window runs (fault-free,
slow-path fold with confirmation, hang pre-emption) and compile their
programs.  The window then synthesises a window, hands it to
``C4DMaster.ingest`` and waits for its actions, until ``--seconds`` have
passed.  Synthesis sits between windows: its time is inside the measured
wall time but outside every metric.  No window is handed over twice.

After the window every window of the stream (warm-up included) is scored
again by the plain reference (``reference/c4d.py``) from the same seed,
and the verdicts, their scores and the node actions are compared.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from chipbench import harness, work
from chipbench.reference import c4d as ref
from chipbench.streams import episode_stream


def _verdicts(vs) -> list:
    return [(v.syndrome, v.rank, tuple(v.link) if v.link is not None else None,
             float(v.score)) for v in vs]


def _actions(acts) -> list:
    return [(a.node_id, [(v.syndrome, v.rank,
                          tuple(v.link) if v.link is not None else None)
                         for v in a.verdicts]) for a in acts]


class Program:
    """The system under test, fed the benchmark's windows."""

    def __init__(self, cfg: dict):
        from repro.core.c4d.master import C4DMaster
        from repro.core.c4d.telemetry import CommunicatorInfo, TelemetryArrays
        n = cfg["n_ranks"]
        self.master = C4DMaster(n_ranks=n, ranks_per_node=cfg["ranks_per_node"],
                                backend=cfg["backend"])
        self._arrays = TelemetryArrays
        self._comms = [CommunicatorInfo(comm_id=0, n_ranks=n,
                                        ranks=tuple(range(n)))]

    def ingest(self, w):
        window = self._arrays(
            window_id=w.window_id, comms=self._comms,
            tr_src=w.tr_src, tr_dst=w.tr_dst, tr_bytes=w.tr_bytes,
            tr_post=w.tr_post, tr_start=w.tr_start, tr_end=w.tr_end,
            hb_rank=w.hb_rank, hb_seq=w.hb_seq, hb_t=w.hb_t,
            op_rank=w.op_rank, op_seq=w.op_seq,
            t_begin=w.t_begin, t_end=w.t_end)
        actions = self.master.ingest(window)
        return _verdicts(self.master.offline_log[-1][1]), _actions(actions)


def compare(cfg: dict, mix: dict, seed: int, answers: list):
    """Score the stream's first ``len(answers)`` windows with the reference
    and compare.  Returns (per-window mismatch flags, largest relative
    score gap, the windows' problem sizes)."""
    n = cfg["n_ranks"]
    master = ref.Master(n, cfg["ranks_per_node"],
                        ref.Thresholds(**cfg["thresholds"]))
    stream = episode_stream(cfg, mix, seed)
    bad: List[bool] = []
    gap = 0.0
    sizes = []
    for (got_v, got_a), planned in zip(answers, stream):
        want_v, want_a, merged = master.ingest(planned.window)
        keys_ok = [v[:3] for v in got_v] == [v[:3] for v in want_v]
        bad.append(not keys_ok or got_a != want_a)
        if keys_ok:
            for g, w in zip(got_v, want_v):
                gap = max(gap, abs(g[3] - w[3]) / max(abs(w[3]), 1e-300))
        hang = bool(want_v) and want_v[0][0] in ref.IMMEDIATE
        sizes.append(work.WindowSizes(
            transports=int(merged.src.size),
            groups=int(np.unique(merged.src * n + merged.dst).size),
            heartbeats=int(merged.hb_rank.size), ranks=n, fold=not hang))
    return bad, gap, sizes


def run(r: harness.Run) -> harness.Outcome:
    cfg, mix = r.cell.config, r.cell.mix
    program = Program(cfg)
    stream = episode_stream(cfg, mix, r.seed)
    answers = []
    planned = next(stream)
    while planned.warmup:
        answers.append(program.ingest(planned.window))
        planned = next(stream)
    n_warm = len(answers)
    r.end_setup()

    latency: List[float] = []
    faulty = 0
    with r.window():
        deadline = time.perf_counter() + r.seconds
        while True:
            with r.span("ingest"):
                t = time.perf_counter()
                answers.append(program.ingest(planned.window))
                latency.append(time.perf_counter() - t)
            faulty += planned.fault is not None
            if time.perf_counter() >= deadline:
                break
            with r.span("synthesis"):
                planned = next(stream)
    memory = r.memory_peak_bytes()

    bad, gap, sizes = compare(cfg, mix, r.seed, answers)
    timed = bad[n_warm:]
    lat = np.asarray(latency)
    r.facts.update(
        windows=len(latency), ingest_s=latency, sizes=sizes[n_warm:],
        n_ranks=cfg["n_ranks"],
        counters={"windows_checked": len(bad),
                  "windows_with_fault": faulty})
    limits = cfg["correct"]
    checks = [
        harness.Check("windows_verdicts_or_actions_differ", float(sum(bad)),
                      limits["windows_differ"]),
        harness.Check("score_rel_gap", gap, limits["score_rel_gap"]),
    ]
    return harness.Outcome(
        e2e={"detect_p90_ms": float(np.percentile(lat, 90) * 1e3),
             "ranks_scored_per_s": cfg["n_ranks"] * lat.size / float(lat.sum())},
        checks=checks, attempted=len(timed), failed=int(sum(timed)),
        memory_peak_bytes=memory)
