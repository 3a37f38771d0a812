"""Host adapter for the jax max-min water-filling kernel.

``waterfill_rates`` is the jax-backend body of ``FlowSet.max_min``: it
takes the FlowSet's COO incidence (pair_flow, pair_link), per-flow weights
and aliveness, and the per-link capacities (jitter already applied by the
caller — RNG draws stay in NumPy so the determinism contract is
backend-independent), pads everything to power-of-two buckets, and runs
the ``lax.while_loop`` progressive filling under x64.  Returns the
unpadded (flow rates, remaining link capacity); the caller keeps the
slowest-QP connection aggregation and utilisation bookkeeping in NumPy —
those are O(F) epilogues, not the hot loop.

Agreement contract: within 1e-6 of ``max_min_rates_reference`` on the
randomized topologies of tests/test_netsim_perf.py (the same tolerance the
NumPy FlowSet is held to).  Exact bit-identity is not promised — segment
sums may associate additions differently than ``np.bincount`` — which is
why the flow backend defaults to NumPy wherever goldens are pinned.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from repro.common.jax_compat import enable_x64
from repro.core.jaxsim.kernels import pad_len, waterfill_kernel


def waterfill_rates(pair_flow: np.ndarray, pair_link: np.ndarray,
                    weights: np.ndarray, alive: np.ndarray,
                    cap: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Run the filling loop on the jax backend.

    ``weights`` must already be floored (``np.maximum(w, 1e-9)``) exactly
    as the NumPy loop does; ``cap`` is the per-link capacity after any CNP
    jitter draw."""
    n_flows = int(weights.size)
    n_links = int(cap.size)
    n_pairs = int(pair_flow.size)
    fp, lp, pp = pad_len(n_flows), pad_len(n_links), pad_len(n_pairs)

    pf = np.zeros(pp, np.int64)
    pl = np.zeros(pp, np.int64)
    pw = np.zeros(pp)
    active = np.zeros(pp, bool)
    pf[:n_pairs] = pair_flow
    pl[:n_pairs] = pair_link
    pw[:n_pairs] = weights[pair_flow]
    active[:n_pairs] = True

    w_pad = np.zeros(fp)
    w_pad[:n_flows] = weights
    alive_pad = np.zeros(fp, bool)
    alive_pad[:n_flows] = alive
    cap_pad = np.zeros(lp)
    cap_pad[:n_links] = cap

    with enable_x64():
        rate, remaining = waterfill_kernel(pf, pl, pw, active,
                                           w_pad, alive_pad, cap_pad)
    return (np.asarray(rate)[:n_flows].copy(),
            np.asarray(remaining)[:n_links].copy())
