"""Named host spans and counters for the NoC sweep.

``span(name, **args)`` always enters ``jax.profiler.TraceAnnotation``, so a
running profiler trace shows it on its ``/host:CPU`` plane, on the clock of
the device events. Inside :func:`recording` it also logs
``(name, parent, start_ns, end_ns, args)`` in memory, and ``count(name, n)``
adds to a counter of that log. Outside one, spans only annotate and
counters do nothing. Spans close before a generator yields and never open
inside jitted code, where they would run at trace time only.
"""
from __future__ import annotations

import contextlib
import contextvars
import time
from typing import Dict, Iterator, List, Optional, Tuple

import jax

__all__ = ["SpanLog", "span", "count", "recording"]

Record = Tuple[str, Optional[str], int, int, dict]

_ACTIVE: contextvars.ContextVar[Optional["SpanLog"]] = contextvars.ContextVar(
    "noc_span_log", default=None)


class SpanLog:
    """Closed spans and counters of one :func:`recording`."""

    def __init__(self):
        self.records: List[Record] = []
        self.counters: Dict[str, int] = {}
        self._open: List[str] = []

    def seconds(self, name: str) -> float:
        """Summed seconds of the spans called ``name``."""
        return sum(t1 - t0 for n, _, t0, t1, _ in self.records
                   if n == name) * 1e-9

    def last(self, name: str) -> float:
        """Seconds of the latest closed span called ``name``."""
        t0, t1 = next((r[2], r[3]) for r in reversed(self.records)
                      if r[0] == name)
        return (t1 - t0) * 1e-9

    def by_arg(self, name: str, arg: str) -> Dict[str, float]:
        """Seconds of the spans called ``name``, per value of ``arg``."""
        out: Dict[str, float] = {}
        for n, _, t0, t1, a in self.records:
            if n == name and arg in a:
                out[a[arg]] = out.get(a[arg], 0.0) + (t1 - t0) * 1e-9
        return out

    def totals(self) -> Dict[str, dict]:
        """Per span name: seconds ``s``, self seconds ``self_s`` (``s``
        minus what its child spans cover) and call count ``n``."""
        ns: Dict[str, list] = {}
        for name, _, t0, t1, _ in self.records:
            e = ns.setdefault(name, [0, 0, 0])
            e[0] += t1 - t0
            e[1] += t1 - t0
            e[2] += 1
        for _, parent, t0, t1, _ in self.records:
            if parent in ns:
                ns[parent][1] -= t1 - t0
        return {k: {"s": s * 1e-9, "self_s": own * 1e-9, "n": n}
                for k, (s, own, n) in sorted(ns.items())}


@contextlib.contextmanager
def span(name: str, **args) -> Iterator[None]:
    log = _ACTIVE.get()
    with jax.profiler.TraceAnnotation(name, **args):
        if log is None:
            yield
            return
        parent = log._open[-1] if log._open else None
        log._open.append(name)
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            t1 = time.perf_counter_ns()
            log._open.pop()
            log.records.append((name, parent, t0, t1, args))


def count(name: str, n: int = 1) -> None:
    log = _ACTIVE.get()
    if log is not None:
        log.counters[name] = log.counters.get(name, 0) + int(n)


@contextlib.contextmanager
def recording() -> Iterator[SpanLog]:
    """Open a log for the spans and counters of the enclosed code. A log
    opened inside another passes its records and counters up on exit."""
    outer = _ACTIVE.get()
    log = SpanLog()
    token = _ACTIVE.set(log)
    try:
        yield log
    finally:
        _ACTIVE.reset(token)
        if outer is not None:
            top = outer._open[-1] if outer._open else None
            outer.records.extend((n, top if p is None else p, t0, t1, a)
                                 for n, p, t0, t1, a in log.records)
            for k, v in log.counters.items():
                outer.counters[k] = outer.counters.get(k, 0) + v
