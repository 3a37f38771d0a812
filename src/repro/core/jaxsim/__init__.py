"""The C4D detection device path + the simulator backend switch.

C4D window detection (grouped pair medians, the delay/wait/hang
detectors) exists twice:

  * the NumPy composite in ``core/c4d`` — the pinned reference every
    golden test is written against, and the small-window path;
  * one device pipeline in this package (``kernels``, ``detectors``):
    ``fused_window_kernel`` -> NumPy centre/scale -> ``slow_fold_kernel``,
    with padded static shapes and a vmapped batch of the same two kernels
    (docs/jaxsim.md).

This module is the *switch*: it resolves which backend a call should use
without importing jax.  That matters because several CI jobs (and any
numpy-only install) run the scenario/campaign stack without jax present —
the kernels are imported lazily, on the first call that actually resolves
to ``"jax"``.

Resolution order for ``resolve_backend(None)``:

  1. an explicit ``use_backend(...)`` / ``set_default_backend(...)`` scope
     (the scenario engine wraps each run in the spec's backend),
  2. the ``REPRO_SIM_BACKEND`` environment variable,
  3. ``"numpy"`` — so every pinned golden keeps running bit-identically
     unless a caller opts in.
"""
from __future__ import annotations

import contextlib
import importlib.util
import os
from typing import Iterator, Optional, Tuple

#: the selectable simulator backends (docs/jaxsim.md).  ``"auto"`` picks
#: by window size: NumPy below the measured crossover, jax above (and
#: NumPy everywhere when jax is not installed).
BACKENDS: Tuple[str, ...] = ("numpy", "jax", "auto")

#: environment override consulted when no explicit scope is active.
BACKEND_ENV = "REPRO_SIM_BACKEND"

_default_backend: Optional[str] = None       # set_default_backend / use_backend


class BackendError(ValueError):
    """Unknown or unavailable simulator backend."""


def jax_available() -> bool:
    """True when jax is importable (without importing it)."""
    return importlib.util.find_spec("jax") is not None


def _validate(name: str) -> str:
    name = name.strip().lower()
    if name not in BACKENDS:
        raise BackendError(
            f"unknown simulator backend {name!r}; choose from {BACKENDS}")
    if name == "jax" and not jax_available():
        raise BackendError(
            "backend 'jax' requested but jax is not installed; install the "
            "pinned range from requirements.txt or use backend='numpy'")
    return name


def get_default_backend() -> str:
    """The backend used when a call site passes ``backend=None``."""
    if _default_backend is not None:
        return _default_backend
    env = os.environ.get(BACKEND_ENV)
    if env:
        return _validate(env)
    return "numpy"


def set_default_backend(name: Optional[str]) -> None:
    """Set (or with ``None`` clear) the process-wide default backend."""
    global _default_backend
    _default_backend = _validate(name) if name is not None else None


@contextlib.contextmanager
def use_backend(name: Optional[str]) -> Iterator[str]:
    """Scoped default backend — how ``run_scenario`` applies
    ``ScenarioSpec.backend`` to everything beneath it (the detector deep
    inside the engine included) without threading an argument through
    every layer.  ``None`` leaves the current default untouched."""
    global _default_backend
    if name is None:
        yield get_default_backend()
        return
    prev = _default_backend
    _default_backend = _validate(name)
    try:
        yield _default_backend
    finally:
        _default_backend = prev


def resolve_backend(name: Optional[str] = None) -> str:
    """Fold an optional per-call ``backend=`` argument against the default."""
    return get_default_backend() if name is None else _validate(name)


# ---------------------------------------------------------------------------
# size-based dispatch for backend="auto"
# ---------------------------------------------------------------------------
# Crossover threshold measured on XLA-CPU (docs/jaxsim.md has the scaling
# table behind it).  Below it NumPy wins on wall clock; at/above it the jit
# kernels win.

#: detector windows: NumPy wins to ~128 ranks, jax from ~256 up (the fused
#: pipeline moved the crossover down from ~1k).
AUTO_DETECT_RANKS = 256


def effective_backend(name: Optional[str] = None, *,
                      ranks: Optional[int] = None) -> str:
    """Resolve ``name`` to a concrete backend (``"numpy"``/``"jax"``).

    Non-auto names resolve exactly like ``resolve_backend``.  ``"auto"``
    compares the window's ``ranks`` against ``AUTO_DETECT_RANKS``, and
    falls back to NumPy when jax is missing or no size is given — so
    ``backend="auto"`` is always safe to request."""
    resolved = resolve_backend(name)
    if resolved != "auto":
        return resolved
    if not jax_available():
        return "numpy"
    if ranks is not None and ranks >= AUTO_DETECT_RANKS:
        return "jax"
    return "numpy"


def cache_info() -> dict:
    """Debug snapshot of the kernel-factory caches (surfaced in benchmark
    ``--json`` output).  Import-safe without jax installed."""
    if not jax_available():
        return {"available": False}
    from repro.core.jaxsim import kernels
    info = kernels.cache_info()
    info["available"] = True
    return info
