"""jit-compiled detection & flow kernels (the simulator's JAX hot paths).

Design rules (docs/jaxsim.md):

**Sparse pairs, not dense matrices.**  The NumPy detectors reason over the
dense ``(n, n)`` delay/wait matrices; at 100k ranks that is ~80 GB, so the
JAX ports operate on the *grouped per-pair arrays* those matrices are
scattered from — ``(src, dst, median)`` triples plus per-rank segment
folds.  Every dense reduction has an exact sparse equivalent (a matrix
cell is finite iff its pair group exists), so the two formulations are
mathematically identical on the cells the detectors actually read.

**Padded static shapes.**  Inputs are padded to power-of-two buckets
(``pad_len``) with an invalid sentinel so ``jit`` compiles once per bucket,
not once per window.  Padding elements carry ``PAD_KEY`` (sorts after all
real pair keys) or an explicit validity mask and never contribute to a
reduction.

**float64 under ``enable_x64``.**  Callers (``detectors``/``waterfill``)
run every kernel inside ``jax_compat.enable_x64()`` so the medians, MAD
scales and z-scores are float64, as in the NumPy references — verdict
identity (score floats included) is pinned on the CPU backend by
tests/test_jaxsim.py.  A TPU has no 64-bit units: XLA emulates float64 and
int64 there and refuses some 64-bit ops outright (an f64 -> s64 bitcast
does not lower), so the kernels use only ops its 64-bit rewrite accepts —
the samples are sorted as floats, never as their bit patterns.  The x64
flag participates in the jit cache key, so scoping it per call is free
after the first trace.

**No ``a*b + c`` on the exact path.**  XLA's CPU backend contracts
multiply-add chains into FMAs (and ``lax.optimization_barrier`` does not
survive to the LLVM level), which shifts the last ulp versus NumPy's
round-per-op semantics.  So the detection kernels only run contraction-safe
ops — sorts, segment folds, subtract/divide/compare — and the z-score
*center/scale* vectors (the only MAD-style ``a*b + c`` expressions) are
computed host-side in NumPy (``detectors._mixed_center_scale``), where the
rounding is the reference rounding by construction.  Kernels that are
pinned with a tolerance rather than bit-exactly (``waterfill_kernel``,
``ewma_scan_kernel``) keep their arithmetic fused on device.

Only this module and its siblings import jax; the backend registry
(``jaxsim.__init__``) and every numpy-backend code path stay importable
without it.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.c4d.baseline import MEANAD_TO_SIGMA

#: sentinel pair key for padding slots; int64-max sorts after any real
#: ``src * n + dst`` key.
PAD_KEY = np.iinfo(np.int64).max

_I64_MIN = np.iinfo(np.int64).min


def pad_len(n: int, minimum: int = 16) -> int:
    """Next power-of-two bucket >= n (>= ``minimum``), the static shape the
    kernels compile against."""
    m = max(int(n), minimum)
    return 1 << (m - 1).bit_length()


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def _masked_median(x, valid):
    """Median over ``x[valid]`` — equals ``np.median`` on the compacted
    array (sort with invalids as +inf, average the two middles)."""
    s = jnp.sort(jnp.where(valid, x, jnp.inf))
    c = jnp.sum(valid)
    lo = s[jnp.maximum((c - 1) // 2, 0)]
    hi = s[jnp.minimum(c // 2, s.shape[0] - 1)]
    return 0.5 * (lo + hi)


def _grouped_median(keys, values):
    """Per-distinct-key median, all static shapes.

    Returns (group_key, group_median, group_count) of the same length as
    the input; group ``g`` occupies slot ``g`` (groups are contiguous ids
    from the sorted order), trailing slots have count 0.  Groups emerge in
    ascending key order, which is exactly the row-major cell order the
    dense reference reads."""
    t = keys.shape[0]
    order = jnp.lexsort((values, keys))
    k = keys[order]
    v = values[order]
    is_start = jnp.concatenate(
        [jnp.ones(1, dtype=bool), k[1:] != k[:-1]])
    gid = jnp.cumsum(is_start) - 1
    idx = jnp.arange(t)
    starts = jax.ops.segment_min(idx, gid, num_segments=t)
    counts = jax.ops.segment_sum(jnp.ones(t, jnp.int64), gid, num_segments=t)
    safe_start = jnp.where(counts > 0, starts, 0)
    lo = v[safe_start + jnp.maximum(counts - 1, 0) // 2]
    hi = v[jnp.minimum(safe_start + counts // 2, t - 1)]
    med = 0.5 * (lo + hi)
    gkey = k[safe_start]
    return gkey, med, counts


@partial(jax.jit, static_argnames=())
def grouped_median_kernel(keys, values):
    """Standalone grouped median (the ``TelemetryArrays`` fold): valid
    groups are those with count > 0 and a non-sentinel key."""
    gkey, med, counts = _grouped_median(keys, values)
    valid = (counts > 0) & (gkey != PAD_KEY)
    return gkey, med, counts, valid


# ---------------------------------------------------------------------------
# slow-path detection: grouped medians, then z folds
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=())
def pair_median_kernel(keys, dvals, wvals):
    """Grouped delay + wait medians over one window's transport pairs.

    ``keys`` = ``src * n + dst`` per transport (PAD_KEY on padding); both
    value arrays group under the same keys.  First stage of the slow-path
    analysis — the host compacts the per-group representatives, turns the
    medians into z centers/scales (the FMA-sensitive part), then
    ``slow_fold_kernel`` finishes on the much smaller group bucket.

    Built for the 100k-rank windows (millions of transports):

      * one two-key ``lax.sort`` per value array orders the transports by
        (pair key, value), so each group's samples are contiguous and
        ascending;
      * group extents come from cumulative scans over the sorted keys, not
        from segment scatters (XLA CPU scatter is serial and dominates at
        ~4M elements with ~T segments).

    Returns *element-aligned* arrays over the sorted transports:
    ``(sorted_key, group_delay_median, group_wait_median, group_count,
    rep, valid)`` where every element carries its group's stats and ``rep``
    marks one representative (the first) element per real group, in
    ascending key order — exactly the row-major cell order the dense
    reference reads."""
    k, d = lax.sort((keys, dvals), num_keys=2)
    _, w = lax.sort((keys, wvals), num_keys=2)
    t = keys.shape[0]
    idx = jnp.arange(t, dtype=jnp.int64)
    brk = k[1:] != k[:-1]
    one = jnp.ones(1, bool)
    is_start = jnp.concatenate([one, brk])
    is_end = jnp.concatenate([brk, one])
    start = lax.cummax(jnp.where(is_start, idx, 0))
    end = lax.cummin(jnp.where(is_end, idx, t - 1), reverse=True)
    cnt = end - start + 1
    # 0.5 * (lo + hi) is a lone multiply of an add — no a*b+c to contract —
    # and equals np.median's mean-of-middles bit for bit.
    dmed = 0.5 * (d[start + (cnt - 1) // 2] + d[start + cnt // 2])
    wmed = 0.5 * (w[start + (cnt - 1) // 2] + w[start + cnt // 2])
    valid = k != PAD_KEY
    rep = is_start & valid
    return k, dmed, wmed, cnt, rep, valid


@partial(jax.jit, static_argnames=("n", "n_pad"))
def slow_fold_kernel(gkey, valid, dmed, wmed,
                     center_d, scale_d, center_w, scale_w,
                     mad_threshold, row_col_fraction,
                     min_observations, *, n: int, n_pad: int):
    """Delay-matrix + ring-wait folds over the grouped medians.

    ``center_*``/``scale_*`` are the per-group z normalisers (adaptive
    where the baseline is warm, cross-sectional elsewhere) computed
    host-side; in-kernel z is then pure subtract/divide, which XLA cannot
    re-round.  Returns per-rank fold arrays (length ``n_pad``) and
    per-group point data from which the host builds the exact Verdict list
    of the dense reference."""
    zd = (dmed - center_d) / scale_d
    zw = (wmed - center_w) / scale_w

    safe_key = jnp.where(valid, gkey, 0)
    gsrc = jnp.where(valid, safe_key // n, n_pad - 1)
    gdst = jnp.where(valid, safe_key % n, n_pad - 1)

    hot = valid & (zd > mad_threshold)
    neg = jnp.full_like(zd, -jnp.inf)

    def fold(seg):
        hot_n = jax.ops.segment_sum(hot.astype(jnp.int64), seg,
                                    num_segments=n_pad)
        obs_n = jax.ops.segment_sum(valid.astype(jnp.int64), seg,
                                    num_segments=n_pad)
        sel = ((obs_n >= min_observations)
               & (hot_n >= jnp.maximum(1.0, row_col_fraction * obs_n))
               & (hot_n >= 2))
        score = jax.ops.segment_max(jnp.where(valid, zd, neg), seg,
                                    num_segments=n_pad)
        return sel, score, hot_n, obs_n

    row_sel, row_score, row_hot, row_obs = fold(gsrc)
    col_sel, col_score, col_hot, col_obs = fold(gdst)
    point = hot & ~row_sel[gsrc] & ~col_sel[gdst]

    # ring-wait (paper Case 2): hot receiver wait over a healthy transfer
    hot_wait = valid & (zw > mad_threshold)
    healthy = ~(valid & (zd > mad_threshold))
    wmask = hot_wait & healthy
    wait_score = jax.ops.segment_max(jnp.where(wmask, zw, neg), gsrc,
                                     num_segments=n_pad)
    wait_any = jax.ops.segment_sum(wmask.astype(jnp.int64), gsrc,
                                   num_segments=n_pad) > 0

    return dict(
        zd=zd, zw=zw,
        row_sel=row_sel, row_score=row_score, row_hot=row_hot,
        row_obs=row_obs, col_sel=col_sel, col_score=col_score,
        col_hot=col_hot, col_obs=col_obs, point=point,
        wait_sel=wait_any, wait_score=wait_score)


# ---------------------------------------------------------------------------
# fused window scoring: segmented pair medians + hang scoring, one dispatch
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n", "n_pad"))
def fused_window_kernel(vmat, counts, gkey, gvalid,
                        hb_rank, hb_seq, hb_valid, offsets, hang_grace,
                        *, n: int, n_pad: int):
    """Window -> (pair medians, hang scoring) in ONE device dispatch.

    The segmented replacement for ``pair_median_kernel`` + ``hang_kernel``.
    The host pre-groups the window's transports (``detectors._layout_for``
    — a 31 ms radix ``np.argsort`` even at 3M transports, and cached across
    windows with identical key layouts) and scatters the delay/wait values
    into ``vmat``: shape ``(2, g_pad, m_pad)``, one row per (src, dst) pair
    group, +inf padding.  The kernel then sorts *rows* instead of the whole
    transport array: ``T log m`` comparator work (m = samples per pair,
    ~16) instead of the two global two-key sorts' ``2 T log T`` — at 100k
    ranks that drops the sort floor from ~3.2 s to ~0.3 s, and XLA can
    vectorize the independent tiny rows where one monolithic sort cannot.

    Exact-path rules preserved (module docstring): rows are sorted as
    float64 (+inf padding sorts last); the median is the same
    ``0.5 * (lo + hi)`` mean-of-middles; per-row lo/hi indices clamp with
    the same formulas the element-aligned kernel used, so every real
    group's median is bit-identical.  Hang scoring is ``hang_kernel``'s
    math verbatim, with ``is_src`` folded from the group keys instead of
    the raw transport sources (a rank has a transport iff some valid group
    has it as src — the same predicate over a G-sized array instead of a
    T-sized one).

    Returns only group-/rank-sized arrays: at 100k ranks the host transfer
    shrinks from six element-aligned 4M arrays (~190 MB) to ~10 MB."""
    m_pad = vmat.shape[-1]
    srt = lax.sort(vmat, dimension=-1)
    lo_i = jnp.maximum((counts - 1) // 2, 0)
    hi_i = jnp.minimum(counts // 2, m_pad - 1)
    lo = jnp.take_along_axis(srt, lo_i[None, :, None], axis=2)[:, :, 0]
    hi = jnp.take_along_axis(srt, hi_i[None, :, None], axis=2)[:, :, 0]
    # 0.5 * (lo + hi): a lone multiply of an add — no a*b+c to contract
    med = 0.5 * (lo + hi)
    seqs = jax.ops.segment_max(jnp.where(hb_valid, hb_seq, _I64_MIN),
                               hb_rank, num_segments=n_pad)
    present = jax.ops.segment_sum(hb_valid.astype(jnp.int64), hb_rank,
                                  num_segments=n_pad) > 0
    seqs_f = seqs.astype(jnp.float64)
    hmed = _masked_median(seqs_f, present)
    deficit = hmed - seqs_f
    hung = present & ((deficit - offsets) >= hang_grace)
    gsrc = jnp.where(gvalid, gkey // n, n_pad - 1)
    is_src = jax.ops.segment_sum(gvalid.astype(jnp.int64), gsrc,
                                 num_segments=n_pad) > 0
    return dict(dmed=med[0], wmed=med[1], present=present, seqs=seqs,
                med=hmed, deficit=deficit, hung=hung, is_src=is_src)


# ---------------------------------------------------------------------------
# hang detection: heartbeat-deficit scoring
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_pad",))
def hang_kernel(hb_rank, hb_seq, hb_valid, src_rank, src_valid,
                offsets, hang_grace, *, n_pad: int):
    """Last-seq per rank, median progress, per-rank deficit and hang mask.

    ``offsets`` is the learned per-rank heartbeat deficit
    (``AdaptiveBaseline.deficit_offset``; zeros without a baseline).
    ``deficit`` is the raw ``median - seq`` (the verdict score); the hang
    decision uses the offset-adjusted value, matching the NumPy
    ``HangDetector``."""
    seqs = jax.ops.segment_max(jnp.where(hb_valid, hb_seq, _I64_MIN),
                               hb_rank, num_segments=n_pad)
    present = jax.ops.segment_sum(hb_valid.astype(jnp.int64), hb_rank,
                                  num_segments=n_pad) > 0
    seqs_f = seqs.astype(jnp.float64)
    med = _masked_median(seqs_f, present)
    deficit = med - seqs_f
    hung = present & ((deficit - offsets) >= hang_grace)
    is_src = jax.ops.segment_sum(src_valid.astype(jnp.int64), src_rank,
                                 num_segments=n_pad) > 0
    return dict(present=present, seqs=seqs, med=med, deficit=deficit,
                hung=hung, is_src=is_src)


# ---------------------------------------------------------------------------
# EWMA baseline update as a scan over windows
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=())
def ewma_scan_kernel(values, mean0, dev0, count0, alpha, clip_sigma):
    """The PR 6 winsorized EWMA baseline update, scanned over windows.

    ``values`` is ``(W, E)`` — one row per window, one column per tracked
    cell, NaN where a cell was unobserved that window.  Replays
    ``AdaptiveBaseline.update`` (first-observation population seeding, then
    clip-at-``clip_sigma`` winsorized updates) for all W windows in one
    device computation; used by the batched campaign scorer and pinned
    against the NumPy class in tests/test_jaxsim.py."""

    def step(carry, vals):
        mean, dev, count = carry
        finite = jnp.isfinite(vals)
        nf = jnp.sum(finite)
        pool_med = _masked_median(vals, finite)
        seed_dev = (jnp.sum(jnp.where(finite, jnp.abs(vals - pool_med), 0.0))
                    / jnp.maximum(nf, 1))
        first = finite & (count == 0)
        mean = jnp.where(first, vals, mean)
        dev = jnp.where(first, seed_dev, dev)
        rest = finite & (count > 0)
        lim = clip_sigma * (MEANAD_TO_SIGMA * dev
                            + 1e-12 * jnp.maximum(jnp.abs(mean), 1e-12)
                            + 1e-30)
        delta = jnp.clip(jnp.where(rest, vals, mean) - mean, -lim, lim)
        dev = jnp.where(rest, (1.0 - alpha) * dev + alpha * jnp.abs(delta),
                        dev)
        mean = jnp.where(rest, mean + alpha * delta, mean)
        count = count + finite.astype(count.dtype)
        return (mean, dev, count), None

    (mean, dev, count), _ = jax.lax.scan(step, (mean0, dev0, count0), values)
    return mean, dev, count


# ---------------------------------------------------------------------------
# FlowSet max-min water-filling
# ---------------------------------------------------------------------------

@jax.jit
def waterfill_kernel(pair_flow, pair_link, pair_w, pair_active,
                     w, alive, cap):
    """Weighted progressive filling over the padded COO incidence.

    The direct port of ``FlowSet.max_min``'s while-loop: per round, per-link
    unfrozen weight by segment-sum, global bottleneck share by an array
    min, joint freeze of every flow on a share-tied link, one more
    segment-sum to return capacity.  A ``lax.while_loop`` with a done flag
    bounds the rounds (each round retires at least one eligible link, so
    the loop terminates in <= L+1 trips; the round counter is a backstop).

    Padding convention: padded pair slots carry ``pair_active = False``;
    padded flow slots have ``alive = False`` / weight 0; padded link slots
    have capacity 0 and never become the finite bottleneck share."""
    n_flows = w.shape[0]
    n_links = cap.shape[0]

    def cond(carry):
        unfrozen, rate, remaining, done, rounds = carry
        return (~done) & unfrozen.any() & (rounds <= n_links + 1)

    def body(carry):
        unfrozen, rate, remaining, done, rounds = carry
        contrib = jnp.where(pair_active & unfrozen[pair_flow], pair_w, 0.0)
        load_w = jax.ops.segment_sum(contrib, pair_link,
                                     num_segments=n_links)
        share = jnp.where(load_w > 0.0, remaining / jnp.where(
            load_w > 0.0, load_w, 1.0), jnp.inf)
        m = share.min()
        finite = jnp.isfinite(m)
        sel = pair_active & (share[pair_link] == m) & unfrozen[pair_flow]
        newly = (jax.ops.segment_sum(sel.astype(jnp.int64), pair_flow,
                                     num_segments=n_flows) > 0) & finite
        rate = jnp.where(newly, m * w, rate)
        unfrozen = unfrozen & ~newly
        dec = jax.ops.segment_sum(
            jnp.where(pair_active & newly[pair_flow], rate[pair_flow], 0.0),
            pair_link, num_segments=n_links)
        remaining = jnp.maximum(remaining - dec, 0.0)
        return unfrozen, rate, remaining, ~finite, rounds + 1

    unfrozen0 = alive
    rate0 = jnp.zeros(n_flows)
    carry = (unfrozen0, rate0, cap, jnp.asarray(False),
             jnp.asarray(0, jnp.int64))
    unfrozen, rate, remaining, _, _ = jax.lax.while_loop(cond, body, carry)
    return rate, remaining


# ---------------------------------------------------------------------------
# batched (vmap) entry points — campaign trials as one device computation
# ---------------------------------------------------------------------------

#: pad-bucket factory cache bound.  Buckets are power-of-two (n, n_pad)
#: combinations, so a long multi-tenant fleet mixing several job sizes
#: touches a handful of buckets — 32 entries cover every fleet shipped
#: while keeping the worst case (adversarial bucket churn) bounded instead
#: of growing a jit cache per window size forever.
FACTORY_CACHE_SIZE = 32


@lru_cache(maxsize=FACTORY_CACHE_SIZE)
def batched_pair_median_kernel():
    """``pair_median_kernel`` vmapped over a leading trial axis."""
    return jax.jit(jax.vmap(pair_median_kernel, in_axes=(0, 0, 0)))


@lru_cache(maxsize=FACTORY_CACHE_SIZE)
def batched_slow_fold_kernel(n: int, n_pad: int):
    """``slow_fold_kernel`` vmapped over a leading trial axis (one padding
    bucket); the scalar thresholds broadcast, everything else is mapped.
    Cached per bucket so repeat calls reuse the traced computation."""
    fn = partial(slow_fold_kernel, n=n, n_pad=n_pad)
    return jax.jit(jax.vmap(
        fn, in_axes=(0,) * 8 + (None,) * 3))


@lru_cache(maxsize=FACTORY_CACHE_SIZE)
def batched_hang_kernel(n_pad: int):
    """``hang_kernel`` vmapped over a leading trial axis."""
    fn = partial(hang_kernel, n_pad=n_pad)
    return jax.jit(jax.vmap(fn, in_axes=(0,) * 6 + (None,)))


@lru_cache(maxsize=FACTORY_CACHE_SIZE)
def batched_fused_window_kernel(n: int, n_pad: int):
    """``fused_window_kernel`` vmapped over a leading window axis (the
    scalar ``hang_grace`` broadcasts)."""
    fn = partial(fused_window_kernel, n=n, n_pad=n_pad)
    return jax.jit(jax.vmap(fn, in_axes=(0,) * 8 + (None,)))


# ---------------------------------------------------------------------------
# cache introspection (the jaxsim.cache_info() debug surface)
# ---------------------------------------------------------------------------

_FACTORIES = (batched_pair_median_kernel, batched_slow_fold_kernel,
              batched_hang_kernel, batched_fused_window_kernel)

_JITTED = {"fused_window_kernel": fused_window_kernel,
           "pair_median_kernel": pair_median_kernel,
           "slow_fold_kernel": slow_fold_kernel,
           "hang_kernel": hang_kernel,
           "grouped_median_kernel": grouped_median_kernel,
           "ewma_scan_kernel": ewma_scan_kernel,
           "waterfill_kernel": waterfill_kernel}


def cache_info() -> dict:
    """Kernel-cache occupancy: the bounded vmap-factory LRUs plus each jit
    kernel's traced-computation count.  Surfaced by ``jaxsim.cache_info()``
    and stamped into ``benchmarks.run --json`` artifacts so a fleet-scale
    run can prove pad-bucket growth stayed bounded."""
    factories = {}
    for fn in _FACTORIES:
        ci = fn.cache_info()
        factories[fn.__name__] = {
            "hits": ci.hits, "misses": ci.misses,
            "size": ci.currsize, "maxsize": ci.maxsize}
    jit_entries = {}
    for name, fn in _JITTED.items():
        size_fn = getattr(fn, "_cache_size", None)
        jit_entries[name] = int(size_fn()) if callable(size_fn) else None
    return {"factory_maxsize": FACTORY_CACHE_SIZE,
            "factories": factories,
            "jit_entries": jit_entries}
