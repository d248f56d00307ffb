"""Per cent of each drain during which the device sat idle: within the
span from the first to the last execution of the drain's chunk program in
a sweep, the time no operation ran (the host's per-chunk readback,
retirement and compaction), summed over sweeps and chips."""

import tracefile


def read(run):
    if run.trace is None:
        return None
    spans = tracefile.drains(run.trace)
    total = sum(d["span_ns"] for d in spans)
    if not total:
        return None
    return 100.0 * sum(d["span_ns"] - d["busy_ns"] for d in spans) / total
