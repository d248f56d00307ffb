"""Seconds per sweep that the drain spends on the host without waiting on
the chip: the ``noc.drain`` span less its ``noc.drain.wait`` spans (the
host reads that block on a chunk program), host clock, averaged over the
window's untraced sweeps. What is left is the drain's set-up, dispatch,
retirement and the building of its results. The cells run no result
phase, whose drain's waits the wait spans would hold too."""

from program_spans import mean_over_sweeps, span_s


def _host(stats):
    drain, wait = span_s(stats, "noc.drain"), span_s(stats, "noc.drain.wait")
    return None if drain is None or wait is None else drain - wait


def read(run):
    return mean_over_sweeps(run, _host)
