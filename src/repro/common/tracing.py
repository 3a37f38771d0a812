"""Spans and counters of the C4D master and the Trainer (docs/tracing.md).

A span is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``: under
an open profiler session (``jax.profiler.trace`` or a capture through
``jax.profiler.start_server``) it lands on the trace's ``/host:CPU`` plane,
on the clock of the device ops, and otherwise costs what an inactive
``TraceMe`` costs (about a microsecond).  Before jax is imported no session
can be open, so a span is a null context and this module never imports jax
itself: the NumPy C4D path stays free of it.

A counter is a plain in-process tally; ``counters()`` returns a copy, and a
reader takes the difference of two copies.
"""
from __future__ import annotations

import contextlib
import sys
from typing import Dict

PREFIX = "repro."

_counts: Dict[str, int] = {}


def span(name: str, **args):
    """A span ``repro.<name>``; ``args`` (such as the window id) ride along
    as the event's arguments."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return contextlib.nullcontext()
    return profiler.TraceAnnotation(PREFIX + name, **args)


def step_span(name: str, step: int):
    """A span that marks one training step for the profiler's step view."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return contextlib.nullcontext()
    return profiler.StepTraceAnnotation(PREFIX + name, step_num=step)


def count(name: str, n: int = 1) -> None:
    _counts[name] = _counts.get(name, 0) + n


def counters() -> Dict[str, int]:
    return dict(_counts)
