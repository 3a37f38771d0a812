"""Collective time per training step that no computation hides, in ms:
on each chip, the time of its all-gather, reduce-scatter, all-reduce and
other collective ops during which no other op runs, averaged over the
chips.  Nothing is returned where the trace holds no collective."""


def read(run):
    steps = run.facts.get("steps")
    if not steps or len(run.fold.trace.devices) < 2:
        return None
    return 1e3 * run.fold.exposed_collective_s() / steps
