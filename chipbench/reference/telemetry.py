"""The benchmark's own telemetry generator: a ring-allreduce job's
enhanced-CCL records for one monitoring window, with fault signatures.

A copy of the synthesiser the program ships (``RingJobTelemetry``'s
struct-of-arrays path and the Table-1 fault taxonomy of the C4 paper,
arXiv:2406.04594), kept here so that no change to the program can change
the traffic it is measured on.  For one seed it emits the same records,
bit for bit, as the program's synthesiser (pinned by
``chipbench/tests/test_reference.py``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import numpy as np


@dataclass(frozen=True)
class Fault:
    kind: str                     # slow_src | slow_dst | slow_link | straggler
    rank: Optional[int] = None    # | comm_hang | noncomm_hang | crash
    link: Optional[Tuple[int, int]] = None
    severity: float = 8.0


@dataclass
class Window:
    """One window's records as columns (transports, heartbeats)."""
    window_id: int
    tr_src: np.ndarray
    tr_dst: np.ndarray
    tr_bytes: np.ndarray
    tr_post: np.ndarray
    tr_start: np.ndarray
    tr_end: np.ndarray
    hb_rank: np.ndarray
    hb_seq: np.ndarray
    hb_t: np.ndarray
    op_rank: np.ndarray
    op_seq: np.ndarray
    t_begin: float
    t_end: float


def fault_for_class(syndrome: str, rank: int, n_ranks: int,
                    rng: np.random.Generator) -> Fault:
    """A concrete telemetry fault for a Table-1 error class's syndrome."""
    if syndrome in ("crash", "comm_hang"):
        return Fault(syndrome, rank=rank)
    if syndrome == "comm_slow":
        return Fault("slow_src", rank=rank, severity=float(rng.uniform(5, 15)))
    if syndrome == "link_slow":
        return Fault("slow_link", link=(rank, (rank + 1) % n_ranks),
                     severity=float(rng.uniform(5, 15)))
    raise ValueError(f"unknown syndrome {syndrome!r}")


class RingTelemetry:
    """Synthetic telemetry of a BSP ring-allreduce job (multi-channel NCCL
    rings: channel ``s`` sends rank ``r`` to ``(r + s) % n``)."""

    def __init__(self, n_ranks: int, iters_per_window: int = 10,
                 base_transfer_s: float = 0.010, base_wait_s: float = 0.0015,
                 msg_bytes: int = 64 << 20, jitter: float = 0.04,
                 seed: int = 0, channel_strides: Sequence[int] = (1, 3, 5, 7)):
        self.n = n_ranks
        self.iters = iters_per_window
        self.base_transfer = base_transfer_s
        self.base_wait = base_wait_s
        self.msg_bytes = msg_bytes
        self.jitter = jitter
        self.rng = np.random.default_rng(seed)
        # a stride that shares a factor with n does not form one ring
        self.strides = [s for s in channel_strides
                        if np.gcd(s, n_ranks) == 1] or [1]

    def window(self, window_id: int, faults: Sequence[Fault] = ()) -> Window:
        n, S, I = self.n, len(self.strides), self.iters
        hang = {f.rank for f in faults if f.kind in ("comm_hang", "crash")}
        nc_hang = {f.rank for f in faults if f.kind == "noncomm_hang"}
        op_period = self.base_transfer * 2.2
        act = np.array([r for r in range(n)
                        if r not in hang and r not in nc_hang], np.int64)
        m = act.size
        # one draw per (iteration, channel, active rank): transfer then wait
        jit = self.rng.standard_normal(I * S * m * 2).reshape(I, S, m, 2)
        transfer = np.abs(self.base_transfer * (1 + self.jitter * jit[..., 0])) + 1e-6
        wait = np.abs(self.base_wait * (1 + self.jitter * jit[..., 1]))
        dst = (act[None, :] + np.asarray(self.strides, np.int64)[:, None]) % n

        src_mult = np.ones(n)
        dst_mult = np.ones(n)
        link_mult = np.ones((S, m))
        wait_add = np.zeros(n)
        for f in faults:
            if f.kind == "slow_src":
                src_mult[f.rank] = f.severity
            elif f.kind == "slow_dst":
                dst_mult[f.rank] = f.severity
            elif f.kind == "slow_link":
                a, b = f.link
                link_mult[(act[None, :] == a) & (dst == b)] = f.severity
            elif f.kind == "straggler":
                wait_add[f.rank] = self.base_transfer * f.severity
        transfer = ((transfer * src_mult[act][None, None, :])
                    * dst_mult[dst][None, :, :]) * link_mult[None, :, :]
        wait = wait + wait_add[act][None, None, :]

        t_post = np.broadcast_to((np.arange(I) * op_period)[:, None, None],
                                 (I, S, m))
        t_start = t_post + wait
        t_end = t_start + transfer
        tr_src = np.broadcast_to(act[None, None, :], (I, S, m)).ravel()
        tr_dst = np.broadcast_to(dst[None, :, :], (I, S, m)).ravel()
        op_rank = tr_src.copy()
        seq_at = np.arange(I)[:, None] * S + np.arange(S)[None, :]
        op_seq = np.broadcast_to(seq_at[:, :, None], (I, S, m)).ravel()
        hb_rank = np.broadcast_to(act[None, :], (I, m)).ravel()
        hb_seq = np.broadcast_to(((np.arange(I) + 1) * S)[:, None], (I, m)).ravel()
        hb_t = np.broadcast_to(((np.arange(I) + 1) * op_period)[:, None],
                               (I, m)).ravel()
        t_post, t_start, t_end = t_post.ravel(), t_start.ravel(), t_end.ravel()

        # a comm hang froze inside the collective (one transport, heartbeat
        # at seq 1); a non-comm hang never reached it (heartbeat at seq 0)
        ch = np.asarray(list(hang), np.int64)
        nc = np.asarray(list(nc_hang), np.int64)
        if ch.size:
            tr_src = np.r_[tr_src, ch]
            tr_dst = np.r_[tr_dst, (ch + 1) % n]
            t_post = np.r_[t_post, np.zeros(ch.size)]
            t_start = np.r_[t_start, np.full(ch.size, self.base_wait)]
            t_end = np.r_[t_end, np.full(ch.size,
                                         self.base_wait + self.base_transfer)]
        if ch.size or nc.size:
            hb_rank = np.r_[hb_rank, ch, nc]
            hb_seq = np.r_[hb_seq, np.ones(ch.size, np.int64),
                           np.zeros(nc.size, np.int64)]
            hb_t = np.r_[hb_t, np.full(ch.size + nc.size, op_period)]
        return Window(
            window_id=window_id, tr_src=tr_src, tr_dst=tr_dst,
            tr_bytes=np.full(tr_src.size, self.msg_bytes, np.int64),
            tr_post=t_post, tr_start=t_start, tr_end=t_end,
            hb_rank=hb_rank, hb_seq=hb_seq, hb_t=hb_t,
            op_rank=op_rank, op_seq=op_seq, t_begin=0.0, t_end=I * op_period)
