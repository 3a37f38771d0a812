"""Reduce a JAX profiler trace (``*.xplane.pb``) to device busy and idle
time, per-module device time, exposed collectives and labelled idle gaps.

A TPU trace holds one plane per chip (``/device:TPU:<n>``) with an
``XLA Ops`` line (one event per executed HLO op; a ``while`` op contains
the ops of its body, so events nest) and an ``XLA Modules`` line (one
event per jitted program run, named ``jit_<function>(<fingerprint>)``).
The host plane (``/host:CPU``) holds the benchmark's own spans, written
with ``jax.profiler.TraceAnnotation`` under the ``chipbench.`` prefix;
both planes count nanoseconds from the start of the trace, so a span and
a device op can be compared directly.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_COLLECTIVE = re.compile(r"all-gather|reduce-scatter|all-reduce|all-to-all|"
                         r"collective-permute")

Interval = Tuple[float, float, str]          # (start_ns, end_ns, name)


@dataclass
class DeviceTrace:
    ops: List[Interval] = field(default_factory=list)
    modules: List[Interval] = field(default_factory=list)
    #: asynchronous ops (copies, collectives) from start to done
    async_ops: List[Interval] = field(default_factory=list)


@dataclass
class Trace:
    devices: Dict[str, DeviceTrace]
    spans: List[Interval]                    # benchmark spans, prefix removed

    def window(self) -> Tuple[float, float]:
        """The measured window: the benchmark's ``window`` span."""
        wins = [(s, e) for s, e, n in self.spans if n == "window"]
        if not wins:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        return wins[0]


def load(path: str | Path) -> Trace:
    """Read the device op and module events and the benchmark's spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices: Dict[str, DeviceTrace] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            dev = devices.setdefault(plane.name, DeviceTrace())
            for line in plane.lines:
                target = {"XLA Ops": dev.ops, "XLA Modules": dev.modules,
                          "Async XLA Ops": dev.async_ops}.get(line.name)
                if target is None:
                    continue
                for ev in line.events:
                    s = float(ev.start_ns)
                    target.append((s, s + float(ev.duration_ns), ev.name))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = float(ev.start_ns)
                        spans.append((s, s + float(ev.duration_ns),
                                      ev.name[len(SPAN_PREFIX):]))
    if not devices:
        raise ValueError(f"{path}: no /device:TPU plane in the trace")
    spans.sort()
    return Trace(devices=devices, spans=spans)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Tuple[float, float]],
          lo: float = float("-inf"), hi: float = float("inf")
          ) -> List[Tuple[float, float]]:
    """Merged, sorted intervals clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Tuple[float, float]], b: List[Tuple[float, float]]
             ) -> List[Tuple[float, float]]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi] between merged busy intervals."""
    return subtract([(lo, hi)], busy)


# ---------------------------------------------------------------------------
# the reductions the metrics read
# ---------------------------------------------------------------------------

def module_ns(dev: DeviceTrace, function: str, lo: float, hi: float) -> float:
    """Device time in [lo, hi] of the runs of one jitted function."""
    prefix = f"jit_{function}("
    return length(union([m for m in dev.modules if m[2].startswith(prefix)],
                        lo, hi))


def module_runs(dev: DeviceTrace, function: str, lo: float, hi: float) -> int:
    prefix = f"jit_{function}("
    return sum(1 for s, e, n in dev.modules
               if n.startswith(prefix) and lo <= s < hi)


def exposed_collective_ns(dev: DeviceTrace, lo: float, hi: float) -> float:
    """Time of collective ops (synchronous, or asynchronous from start to
    done) during which no other op computes."""
    coll = union([o for o in dev.ops + dev.async_ops
                  if _COLLECTIVE.search(_op_name(o[2]))], lo, hi)
    compute = union([o for o in dev.ops
                     if not _COLLECTIVE.search(_op_name(o[2]))
                     and not _op_name(o[2]).startswith("while")], lo, hi)
    return length(subtract(coll, compute))


def span_busy_overlap(spans: List[Interval], busy: List[Tuple[float, float]]
                      ) -> float:
    """Total device-busy time that falls inside the given spans."""
    inside = union([(s, e) for s, e, _ in spans])
    return length(subtract(inside, subtract(inside, busy)))


def _op_name(text: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0]
    return head.lstrip("%")


def top_ops(dev: DeviceTrace, lo: float, hi: float, k: int = 10
            ) -> List[Tuple[str, float]]:
    """The ops with the most self time (time not covered by ops nested in
    them) in [lo, hi], in seconds, named by their HLO text's head."""
    self_ns: Dict[str, float] = {}
    stack: List[List] = []            # [end, name, child_ns]

    def close(entry, start):
        end, name, child, s0 = entry
        own = max(min(end, hi) - max(s0, lo), 0.0) - child
        self_ns[name] = self_ns.get(name, 0.0) + max(own, 0.0)
        if stack:
            stack[-1][2] += max(min(end, hi) - max(s0, lo), 0.0)

    for s, e, text in sorted(dev.ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop(), s)
        stack.append([e, _label(text), 0.0, s])
    while stack:
        close(stack.pop(), hi)
    ranked = sorted(self_ns.items(), key=lambda kv: -kv[1])[:k]
    return [(name, ns / 1e9) for name, ns in ranked if ns > 0]


_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def _label(text: str) -> str:
    """A readable op label: its name and first result shape."""
    name, _, rest = text.partition(" = ")
    shape = _SHAPE.search(rest)
    return f"{name.lstrip('%')} {shape.group(0) if shape else ''}".strip()


def labelled_gaps(trace: Trace, busy: List[Tuple[float, float]],
                  lo: float, hi: float, k: int = 10,
                  skip: Tuple[str, ...] = ("window",)
                  ) -> List[Tuple[str, float]]:
    """The ``k`` longest idle gaps of [lo, hi], each named by the innermost
    benchmark span open at its midpoint (``outside spans`` if none)."""
    out = []
    spans = [sp for sp in trace.spans if sp[2] not in skip]
    for s, e in gaps(busy, lo, hi):
        mid = 0.5 * (s + e)
        open_ = [sp for sp in spans if sp[0] <= mid < sp[1]]
        label = (min(open_, key=lambda sp: sp[1] - sp[0])[2] if open_
                 else "outside spans")
        out.append((label, (e - s) / 1e9))
    out.sort(key=lambda g: -g[1])
    return out[:k]


@dataclass
class Fold:
    """Everything the per-layer metric readers take from one trace."""
    trace: Trace
    lo: float
    hi: float
    busy: Dict[str, List[Tuple[float, float]]]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips in the trace."""
        return (sum(length(b) for b in self.busy.values())
                / len(self.busy) / 1e9)

    def mean_over_devices(self, fn) -> float:
        devs = self.trace.devices
        return sum(fn(d) for d in devs.values()) / len(devs)

    def module_s(self, function: str) -> float:
        return self.mean_over_devices(
            lambda d: module_ns(d, function, self.lo, self.hi)) / 1e9

    def exposed_collective_s(self) -> float:
        return self.mean_over_devices(
            lambda d: exposed_collective_ns(d, self.lo, self.hi)) / 1e9

    def spans(self, name: str) -> List[Interval]:
        return [sp for sp in self.trace.spans
                if sp[2] == name and self.lo <= sp[0] < self.hi]

    def breakdown(self, k: int = 10) -> dict:
        first = sorted(self.trace.devices)[0]
        return {
            "device_ops": [list(x) for x in top_ops(
                self.trace.devices[first], self.lo, self.hi, k)],
            "idle_gaps": [list(x) for x in labelled_gaps(
                self.trace, self.busy[first], self.lo, self.hi, k)],
        }


def fold(trace: Trace, window: Optional[Tuple[float, float]] = None,
         devices: Optional[Iterable[str]] = None) -> Fold:
    """Fold the trace over its measured window, keeping only the planes of
    ``devices`` (``/device:TPU:<id>`` names; all by default)."""
    lo, hi = window or trace.window()
    if devices is not None:
        keep = set(devices)
        trace = Trace(devices={k: v for k, v in trace.devices.items() if k in keep},
                      spans=trace.spans)
    busy = {name: union(dev.ops, lo, hi)
            for name, dev in trace.devices.items()}
    return Fold(trace=trace, lo=lo, hi=hi, busy=busy)
