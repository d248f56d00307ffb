"""Per cent of the traced window in which no operation ran on the device
(averaged over the cell's chips): 1 - union of busy intervals / window."""

import tracefile


def read(run):
    if run.trace is None or not run.trace.ops or run.trace.window is None:
        return None
    lo, hi = run.trace.window
    if hi <= lo:
        return None
    return 100.0 * (1.0 - tracefile.device_busy_s(run.trace, lo, hi)
                    / ((hi - lo) * 1e-9))
