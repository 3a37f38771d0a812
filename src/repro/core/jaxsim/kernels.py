"""The C4D detection kernels: ``fused_window_kernel`` and ``slow_fold_kernel``.

``detectors`` runs them per window, and their vmapped factories
(``batched_*``) for ``score_windows_batched``.  Design rules
(docs/jaxsim.md):

**Sparse pairs, not dense matrices.**  The NumPy detectors reason over the
dense ``(n, n)`` delay/wait matrices; at 100k ranks that is ~80 GB, so the
kernels operate on the *grouped per-pair arrays* those matrices are
scattered from — ``(src, dst, median)`` triples plus per-rank segment
folds.  Every dense reduction has an exact sparse equivalent (a matrix
cell is finite iff its pair group exists), so the two formulations are
mathematically identical on the cells the detectors actually read.

**Padded static shapes.**  Inputs are padded to power-of-two buckets
(``pad_len``) with an invalid sentinel so ``jit`` compiles once per bucket,
not once per window.  Padding elements carry ``PAD_KEY`` (sorts after all
real pair keys) or an explicit validity mask and never contribute to a
reduction.

**float64 under ``enable_x64``.**  ``detectors`` runs both kernels inside
``jax_compat.enable_x64()`` so the medians, MAD scales and z-scores are
float64, as in the NumPy references — verdict identity (score floats
included) is pinned on the CPU backend by tests/test_jaxsim.py.  A TPU has
no 64-bit units: XLA emulates float64 and int64 there and refuses some
64-bit ops outright (an f64 -> s64 bitcast does not lower), so the kernels
use only ops its 64-bit rewrite accepts — the samples are sorted as
floats, never as their bit patterns.  The x64 flag participates in the jit
cache key, so scoping it per call is free after the first trace.

**No ``a*b + c`` on the exact path.**  XLA's CPU backend contracts
multiply-add chains into FMAs (and ``lax.optimization_barrier`` does not
survive to the LLVM level), which shifts the last ulp versus NumPy's
round-per-op semantics.  So the kernels only run contraction-safe
ops — sorts, segment folds, subtract/divide/compare — and the z-score
*center/scale* vectors (the only MAD-style ``a*b + c`` expressions) are
computed host-side in NumPy (``detectors._mixed_center_scale``), where the
rounding is the reference rounding by construction.

Only this module and ``detectors`` import jax; the backend registry
(``jaxsim.__init__``) and every numpy-backend code path stay importable
without it.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

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


# ---------------------------------------------------------------------------
# slow-path detection: z folds over the grouped medians
# ---------------------------------------------------------------------------

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

    The host pre-groups the window's transports (``detectors._WindowLayout``,
    one radix ``np.argsort``) and scatters the delay/wait values into
    ``vmat``: shape ``(2, g_pad, m_pad)``, one row per (src, dst) pair
    group, +inf padding.  The kernel then sorts *rows* instead of the whole
    transport array: ``T log m`` comparator work (m = samples per pair,
    ~16) instead of a global two-key sort's ``T log T``, and XLA can
    vectorize the independent tiny rows where one monolithic sort cannot.

    Exact-path rules (module docstring): rows are sorted as float64 (+inf
    padding sorts last) and the median is ``np.median``'s mean of the two
    middles, ``0.5 * (lo + hi)``, so every real group's median is
    bit-identical to the NumPy composite's.  Hang scoring is
    ``HangDetector``'s: last seq per rank, the median over present ranks,
    and the offset-adjusted deficit against ``hang_grace`` (``offsets`` is
    ``AdaptiveBaseline.deficit_offset``, zeros without a baseline).
    ``is_src`` is folded from the group keys: a rank has a transport iff
    some valid group has it as src.

    Returns only group-/rank-sized arrays."""
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
# batched (vmap) entry points — several windows as one device computation
# ---------------------------------------------------------------------------

#: pad-bucket factory cache bound.  Buckets are power-of-two (n, n_pad)
#: combinations, so a long multi-tenant fleet mixing several job sizes
#: touches a handful of buckets — 32 entries cover every fleet shipped
#: while keeping the worst case (adversarial bucket churn) bounded instead
#: of growing a jit cache per window size forever.
FACTORY_CACHE_SIZE = 32


@lru_cache(maxsize=FACTORY_CACHE_SIZE)
def batched_slow_fold_kernel(n: int, n_pad: int):
    """``slow_fold_kernel`` vmapped over a leading window axis (one padding
    bucket); the scalar thresholds broadcast, everything else is mapped.
    Cached per bucket so repeat calls reuse the traced computation."""
    fn = partial(slow_fold_kernel, n=n, n_pad=n_pad)
    return jax.jit(jax.vmap(
        fn, in_axes=(0,) * 8 + (None,) * 3))


@lru_cache(maxsize=FACTORY_CACHE_SIZE)
def batched_fused_window_kernel(n: int, n_pad: int):
    """``fused_window_kernel`` vmapped over a leading window axis (the
    scalar ``hang_grace`` broadcasts)."""
    fn = partial(fused_window_kernel, n=n, n_pad=n_pad)
    return jax.jit(jax.vmap(fn, in_axes=(0,) * 8 + (None,)))


# ---------------------------------------------------------------------------
# cache introspection (the jaxsim.cache_info() debug surface)
# ---------------------------------------------------------------------------

_FACTORIES = (batched_slow_fold_kernel, batched_fused_window_kernel)


def cache_info() -> dict:
    """Kernel-cache occupancy: the bounded vmap-factory LRUs.  Surfaced by
    ``jaxsim.cache_info()`` and stamped into ``benchmarks.run --json``
    artifacts so a fleet-scale run can prove pad-bucket growth stayed
    bounded."""
    factories = {}
    for fn in _FACTORIES:
        ci = fn.cache_info()
        factories[fn.__name__] = {
            "hits": ci.hits, "misses": ci.misses,
            "size": ci.currsize, "maxsize": ci.maxsize}
    return {"factory_maxsize": FACTORY_CACHE_SIZE,
            "factories": factories}
