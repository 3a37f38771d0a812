"""Device time per training step of the held experts' grouped products, in
ms: the compiled ops under the program's ``moe.experts`` scope (forward,
recomputation and backward) in the traced window, over its steps."""

from chipbench import work_moe


def read(run):
    seconds = work_moe.scope_device_s(run, ["moe.experts"])
    steps = run.facts.get("steps")
    if seconds is None or not steps:
        return None
    return 1e3 * seconds / steps
