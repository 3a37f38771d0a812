"""The whole detection path's share of the chip's peak, in %.

Detection is bound by memory, not arithmetic, so its peak is the HBM
bandwidth: the bytes the detection kernels must move
(``work.detect_window_bytes``) over the master's whole ``ingest`` time
(host and device) times the peak bandwidth.  It bounds the kernels'
roofline share from below, and still reads where a change takes a
kernel off the path."""

from chipbench import work


def read(run):
    ingest_s = sum(run.facts["ingest_s"])
    nbytes = sum(work.detect_window_bytes(s) for s in run.facts["sizes"])
    peak = work.peaks(run.devices[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * (nbytes / peak) / ingest_s
