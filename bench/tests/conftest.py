"""Test set-up for the benchmark harness: import paths and a tiny cell.

The tiny cell runs the real sweep path on the CPU at a size a test can
hold: a 4x4 mesh with 2 MCs and three small random layers in place of a
configuration's network.
"""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

# Harness modules of the tests (``harness/<stem>.py`` here), and what the
# tiny one was called for.
TEST_HARNESS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "harness")
HARNESS_CALLS = []

TINY_CONFIG = {"model": "tiny", "precision": "fixed8",
               "noc": {"rows": 4, "cols": 4, "num_mcs": 2, "num_vcs": 4,
                       "vc_depth": 4, "lanes": 16}}


def tiny_traffic(transforms=("O0", "O1", "O2")):
    return {"grid": {"placements": ["interleaved"],
                     "affinity": ["roundrobin"],
                     "transforms": list(transforms),
                     "tiebreaks": ["pattern"], "result_phase": False},
            "warmup_cycles": None}


def tiny_layers(seed=0, shapes=((40, 25), (24, 150), (12, 40))):
    """(program layers, host layers) of random operands."""
    import jax.numpy as jnp
    from repro.noc.traffic import LayerTraffic
    rng = np.random.default_rng(seed)
    host = [(rng.normal(size=s).astype(np.float32) * 0.5,
             rng.normal(size=s).astype(np.float32) * 0.1) for s in shapes]
    prog = [LayerTraffic(jnp.asarray(i), jnp.asarray(w)) for i, w in host]
    return prog, host


def cpu_lines(plane, line):
    """Where a CPU trace keeps what stands in for device events: XLA's
    CPU client threads run the operations, and the Python thread's
    ``PjitFunction(...)`` events stand for program executions."""
    if plane != "/host:CPU":
        return None
    if line.startswith("tf_XLAPjRtCpuClient"):
        return ("op", 0)
    if line == "python":
        return ("module", 0)
    return None


@pytest.fixture
def tiny():
    return TINY_CONFIG, tiny_traffic(), tiny_layers()
