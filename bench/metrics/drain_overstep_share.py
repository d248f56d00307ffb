"""Per cent of the mesh cycles the drain stepped that it did not need:
100 x (``drain.stepped_cycles`` - ``drain.cycles``) / ``drain.stepped_cycles``,
from ``run_sweep``'s counters, averaged over the window's untraced sweeps.
The pipelined drain loop dispatches chunk k+1 before it reads chunk k, so it
steps at least one chunk past the drain. A count, the same on every
platform."""

from program_spans import mean_over_sweeps


def _overstep(stats):
    ct = stats.get("counters") or {}
    stepped, needed = ct.get("drain.stepped_cycles"), ct.get("drain.cycles")
    if not stepped or needed is None:
        return None
    return 100.0 * (stepped - needed) / stepped


def read(run):
    return mean_over_sweeps(run, _overstep)
