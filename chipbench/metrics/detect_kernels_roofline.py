"""Share of the roofline reached by the C4D detection kernels, in %.

The least time the chip could take is the bytes the kernels must move
(``work.detect_window_bytes``, counted from each window's problem sizes
at 8 bytes an element) over the peak HBM bandwidth; the share is that
least time over the kernels' device time in the trace."""

from chipbench import work

KERNELS = ("fused_window_kernel", "slow_fold_kernel")


def read(run):
    device_s = sum(run.fold.module_s(k) for k in KERNELS)
    if device_s <= 0:
        return None
    nbytes = sum(work.detect_window_bytes(s) for s in run.facts["sizes"])
    peak = work.peaks(run.devices[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * (nbytes / peak) / device_s
