"""Seconds per sweep in stream assembly: the ``noc.packetize.assemble``
spans of ``run_sweep`` (the streamed assembler's host scatter and uploads,
then the stream padding and lane concatenation), host clock, averaged over
the window's untraced sweeps."""

from program_spans import mean_over_sweeps, span_s


def read(run):
    return mean_over_sweeps(run,
                            lambda st: span_s(st, "noc.packetize.assemble"))
