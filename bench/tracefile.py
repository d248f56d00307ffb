"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. On a TPU each
chip is a plane ``/device:TPU:<n>`` whose ``XLA Ops`` line holds every
operation the chip ran and whose ``XLA Modules`` line holds every program
(one event per execution, named after the jitted function). The harness's
own spans (``bench.start``, ``bench.sweep.<i>``) are host events on the
``/host:CPU`` plane. All events of one trace share one clock, in ns. Where
the profiler's buffers overflowed, its ``XLA TraceMe`` line holds a
``Trace Buffers Dropped`` event over the stretch it lost.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]
# (plane name, line name) -> ("op" | "module", device index) or None
LineKind = Callable[[str, str], Optional[Tuple[str, int]]]

_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_SWEEP = re.compile(r"bench\.sweep\.\d+")
# The drain's chunk program: the jitted ``run`` of ``noc/sim.py``.
DRAIN_PROGRAM = r"^jit_run\b"


def tpu_lines(plane: str, line: str) -> Optional[Tuple[str, int]]:
    m = _TPU_PLANE.match(plane)
    if not m:
        return None
    kind = {"XLA Ops": "op", "XLA Modules": "module"}.get(line)
    return (kind, int(m.group(1))) if kind else None


class Trace:
    """Device operations, device programs and host spans of one trace.

    ``ops[d]`` and ``modules[d]`` are lists of ``(start_ns, end_ns, name)``
    on device ``d``, sorted by start; ``spans`` the host events whose name
    starts with ``bench.``; ``dropped`` the stretches the profiler lost.
    ``window`` is the part of the trace the metrics read and ``tail`` the
    sweep that was still running when the trace stopped, if any.
    """

    def __init__(self, ops: Dict[int, list], modules: Dict[int, list],
                 spans: List[Tuple[float, float, str]],
                 dropped: Sequence[Interval] = ()):
        self.ops = {d: sorted(v) for d, v in ops.items()}
        self.modules = {d: sorted(v) for d, v in modules.items()}
        self.spans = sorted(spans)
        self.dropped = sorted(dropped)
        self.window: Optional[Interval] = None
        self.tail: Optional[Interval] = None

    def span(self, name: str) -> Optional[Interval]:
        hits = [(s, e) for s, e, n in self.spans if n == name]
        return hits[0] if hits else None

    def bound(self, traced_s: float) -> Interval:
        """Fix the window to the ``traced_s`` seconds from ``bench.start``,
        stretched to the end of a sweep that began inside them (the host
        clock that measured ``traced_s`` and the trace's own differ by
        microseconds), and ended early where the profiler began to drop
        events; a sweep the drop cut short then counts up to the drop."""
        lo = self.span("bench.start")[0]
        hi = lo + traced_s * 1e9
        hi = max([hi] + [e for s, e, n in self.spans
                         if _SWEEP.fullmatch(n) and s < hi])
        if self.dropped and self.dropped[0][0] < hi:
            hi = self.dropped[0][0]
            done = [e for s, e in self.sweeps() if e <= hi]
            start = max(done) if done else lo
            self.tail = (start, hi) if hi > start else None
        self.window = (lo, hi)
        return self.window

    def sweeps(self) -> List[Interval]:
        """The sweeps inside the window: whole ones, then the tail."""
        hi = self.window[1] if self.window else float("inf")
        whole = [(s, e) for s, e, n in self.spans
                 if _SWEEP.fullmatch(n) and e <= hi]
        return whole + ([self.tail] if self.tail else [])

    def devices(self) -> List[int]:
        return sorted(self.ops)

    def busy(self, dev: int) -> list:
        """The events that make device ``dev`` busy: its operations, or
        its program executions where the trace holds no operations."""
        return self.ops.get(dev) or self.modules.get(dev, [])


def load(trace_dir: str, lines: LineKind = tpu_lines,
         devices: Optional[int] = None) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``. ``devices``
    keeps the first n device indices (the chips the cell uses)."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    ops: Dict[int, list] = {}
    modules: Dict[int, list] = {}
    spans, dropped = [], []
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name.startswith("/host:"):
                spans.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name)
                             for ev in line.events
                             if ev.name.startswith("bench."))
            if line.name == "XLA TraceMe":
                dropped.extend((ev.start_ns, ev.start_ns + ev.duration_ns)
                               for ev in line.events
                               if ev.name == "Trace Buffers Dropped")
            kind = lines(plane.name, line.name)
            if kind is None:
                continue
            what, dev = kind
            if devices is not None and dev >= devices:
                continue
            dst = ops if what == "op" else modules
            dst.setdefault(dev, []).extend(
                (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                for ev in line.events
                if ev.duration_ns > 0 and not ev.name.startswith("bench."))
    for d in list(ops) + list(modules):
        ops.setdefault(d, [])
        modules.setdefault(d, [])
    return Trace(ops, modules, spans, dropped)


def union(intervals, lo: float, hi: float) -> List[Interval]:
    """Merged busy intervals, clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(intervals, lo, hi))


def gaps(intervals, lo: float, hi: float) -> List[Interval]:
    """Idle stretches of ``[lo, hi]`` between the busy intervals."""
    out, t = [], lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def matching(events, pattern: str):
    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(ev[2])]


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10):
    """The device operations that took the most time in ``[lo, hi]``,
    averaged over the devices: ``[[name, seconds], ...]``."""
    tot: Dict[str, float] = {}
    for evs in (trace.busy(d) for d in trace.devices()):
        for s, e, name in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                tot[name] = tot.get(name, 0.0) + d
    nd = max(len(trace.devices()), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / nd * 1e-9] for k, v in best]


def labelled_gaps(trace: Trace, lo: float, hi: float,
                  program: str = DRAIN_PROGRAM, n: int = 10):
    """The longest device-idle stretches in ``[lo, hi]`` on device 0, each
    named by what the host was doing: which sweep, and whether it lay
    before that sweep's first ``program`` execution (packetize), between
    two (the drain's host loop), or after the last (rows), or between
    sweeps."""
    if not trace.ops:
        return []
    dev = trace.devices()[0]
    progs = matching(trace.modules.get(dev, []), program)
    sweeps = trace.sweeps()
    out = []
    for s, e in gaps(trace.busy(dev), lo, hi):
        mid = (s + e) / 2
        label = "between sweeps"
        for i, (a, b) in enumerate(sweeps):
            if a <= mid <= b:
                inside = [p for p in progs if a <= p[0] <= b]
                if not inside or mid < inside[0][0]:
                    phase = "packetize, before the first drain program"
                elif mid > inside[-1][1]:
                    phase = "rows, after the last drain program"
                else:
                    phase = "drain host loop, between drain programs"
                label = f"sweep {i}: {phase}"
                break
        out.append([label, (e - s) * 1e-9])
    return sorted(out, key=lambda kv: -kv[1])[:n]


def drains(trace: Trace, program: str = DRAIN_PROGRAM) -> List[dict]:
    """Per sweep and device: the span from the first to the last
    ``program`` execution, the device-busy time inside it, and the
    programs' own summed time and count."""
    out = []
    for a, b in trace.sweeps():
        for dev in trace.devices():
            progs = [p for p in matching(trace.modules.get(dev, []), program)
                     if a <= p[0] <= b]
            if not progs:
                continue
            lo, hi = progs[0][0], max(p[1] for p in progs)
            out.append({"device": dev, "span_ns": hi - lo,
                        "busy_ns": busy_ns(trace.busy(dev), lo, hi),
                        "program_ns": float(sum(p[1] - p[0] for p in progs)),
                        "programs": len(progs)})
    return out


def device_busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in ``[lo, hi]`` in which an operation ran, averaged over the
    devices."""
    if not trace.ops:
        return 0.0
    return float(np.mean([busy_ns(trace.busy(d), lo, hi)
                          for d in trace.devices()])) * 1e-9
