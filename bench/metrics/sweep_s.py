"""Seconds per whole sweep: the measured window over the sweeps it
completed (packetize, drain and, where the cell has one, the result phase
of each)."""


def read(run):
    return run.window_s / len(run.sweeps) if run.sweeps else None
