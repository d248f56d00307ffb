"""Device microseconds per simulated mesh cycle of the router step: the
summed device time of the drain's chunk programs (the fused step under
``lax.scan``) over the mesh cycles they stepped (executions x chunk
length), per chip."""

import tracefile


def read(run):
    chunk = getattr(run.grid, "chunk", None)
    if run.trace is None or not chunk:
        return None
    spans = tracefile.drains(run.trace)
    steps = sum(d["programs"] for d in spans) * chunk
    if not steps:
        return None
    return sum(d["program_ns"] for d in spans) * 1e-3 / steps
