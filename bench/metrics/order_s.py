"""Seconds per sweep in ordering: the ``noc.packetize.order`` spans of
``run_sweep`` (each variant's quantization of a layer and each packet
chunk's ordering up to host words, the O3 chain on the host CPU included),
host clock, averaged over the window's untraced sweeps."""

from program_spans import mean_over_sweeps, span_s


def read(run):
    return mean_over_sweeps(run, lambda st: span_s(st, "noc.packetize.order"))
