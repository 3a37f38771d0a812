"""Device time of the C4D detection kernels per scored window, in ms.

The runs of the fused window kernel (pair medians and hang scoring) and
of the slow-path fold, read from the trace's module line.  Nothing is
returned where neither ran on the device."""

KERNELS = ("fused_window_kernel", "slow_fold_kernel")


def read(run):
    device_s = sum(run.fold.module_s(k) for k in KERNELS)
    if device_s <= 0 or not run.facts.get("windows"):
        return None
    return 1e3 * device_s / run.facts["windows"]
