"""Device-busy time per training step in the traced window, in ms
(averaged over the chips the step runs on)."""


def read(run):
    steps = run.facts.get("steps")
    if not steps:
        return None
    return 1e3 * run.fold.busy_s / steps
