"""Host time of the C4D master per scored window, in ms: the benchmark's
span around each ``C4DMaster.ingest``, less the device-busy time inside it
(prefilter, grouping, packing, centre/scale, verdicts and the fold)."""

from chipbench import tracefold


def read(run):
    spans = run.fold.spans("ingest")
    if not spans:
        return None
    busy = run.fold.busy[sorted(run.fold.busy)[0]]
    total = sum(e - s for s, e, _ in spans)
    host_ns = total - tracefold.span_busy_overlap(spans, busy)
    return host_ns / 1e6 / len(spans)
