"""The general generator of C4D telemetry streams, driven by a traffic mix.

A mix (``chipbench/traffic/<name>.json``) describes a stream of
monitoring windows as episodes.  Each episode opens with a number of
fault-free windows drawn uniformly from ``fault_free_windows`` and then
carries one fault: its class drawn from ``classes`` by probability, its
rank uniformly, its signature from the class's syndrome.  The fault lasts
``persist_windows[syndrome]`` windows.  ``warmup`` lists the episodes that
open every stream, so that set-up runs each path the window will run.

The stream is a pure function of the seed: the same seed gives the same
windows, and no window repeats within a stream.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, Optional

import numpy as np

from chipbench.reference.telemetry import Fault, RingTelemetry, Window, fault_for_class


@dataclass
class Planned:
    window: Window
    fault: Optional[Fault]
    warmup: bool


def telemetry_for(cfg: dict, seed: int) -> RingTelemetry:
    t = cfg["telemetry"]
    return RingTelemetry(
        n_ranks=cfg["n_ranks"], iters_per_window=t["iters_per_window"],
        base_transfer_s=t["base_transfer_s"], base_wait_s=t["base_wait_s"],
        msg_bytes=t["msg_bytes"], jitter=t["jitter"], seed=seed,
        channel_strides=t["channel_strides"])


def episode_stream(cfg: dict, mix: dict, seed: int) -> Iterator[Planned]:
    """Windows of the mix for one seed, warm-up episodes first."""
    n = cfg["n_ranks"]
    tel = telemetry_for(cfg, seed)
    rng = np.random.default_rng([seed, 1])
    classes = mix["classes"]
    probs = np.array([c["probability"] for c in classes], float)
    probs /= probs.sum()
    lo, hi = mix["fault_free_windows"]
    wid = 0

    def episode(free: int, syndrome: Optional[str], warm: bool):
        nonlocal wid
        for _ in range(free):
            yield Planned(tel.window(wid), None, warm)
            wid += 1
        if syndrome is None:
            return
        fault = fault_for_class(syndrome, int(rng.integers(n)), n, rng)
        for _ in range(mix["persist_windows"][syndrome]):
            yield Planned(tel.window(wid, [fault]), fault, warm)
            wid += 1

    for ep in mix["warmup"]:
        yield from episode(ep["fault_free_windows"], ep.get("syndrome"), True)
    while True:
        free = int(rng.integers(lo, hi + 1))
        cls = classes[int(rng.choice(len(classes), p=probs))]
        yield from episode(free, cls["syndrome"], False)
