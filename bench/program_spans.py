"""What the readers of program spans share: a per-sweep quantity read from
``run_sweep``'s ``stats["spans"]`` and ``stats["counters"]``, averaged over
the window's untraced sweeps. A program that records no spans gives None,
never an error."""
from __future__ import annotations

from typing import Callable, Optional


def span_s(stats: dict, name: str) -> Optional[float]:
    """Seconds of the span ``name`` in one sweep's stats, or None."""
    return (stats.get("spans") or {}).get(name, {}).get("s")


def mean_over_sweeps(run, quantity: Callable[[dict], Optional[float]]):
    """``quantity`` of each window sweep's stats, averaged; None where a
    sweep has none."""
    vals = [quantity(s["stats"]) for s in run.sweeps]
    if not vals or None in vals:
        return None
    return sum(vals) / len(vals)
