"""Device time per training step of the expert layer's routing and row
movement, in ms: the compiled ops under the program's ``moe.route``,
``moe.dispatch`` and ``moe.combine`` scopes in the traced window, over its
steps."""

from chipbench import work_moe


def read(run):
    seconds = work_moe.scope_device_s(run, ["moe.route", "moe.dispatch", "moe.combine"])
    steps = run.facts.get("steps")
    if seconds is None or not steps:
        return None
    return 1e3 * seconds / steps
