"""Seconds from process start to the window: JAX start-up, the seed's
image and layer operands, and the warm-up sweep with its compiles."""


def read(run):
    return run.setup_s
