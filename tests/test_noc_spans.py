"""Named spans and drain counters of the sweep (``repro.noc.spans``): the
recorder's arithmetic, the spans and counters every ``run_sweep`` report
carries, the timings that read them, and the spans a profiler trace
holds."""
import glob
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.noc import (LayerTraffic, SweepGrid, build_traffic_batch,
                       make_noc, run_serving, run_sweep, simulate_batch)
from repro.noc import spans
from repro.core.wire import by_name

SWEEP_SPANS = {"noc.sweep", "noc.packetize", "noc.packetize.order",
               "noc.packetize.assemble", "noc.drain", "noc.drain.setup",
               "noc.drain.wait", "noc.drain.retire", "noc.result.packetize",
               "noc.result.drain"}
COUNTERS = {"drain.stepped_cycles", "drain.cycles"}


def _layers(seed=0, shapes=((12, 20), (8, 30), (5, 17))):
    rng = np.random.default_rng(seed)
    return [LayerTraffic(jnp.asarray(rng.normal(size=s).astype(np.float32)),
                         jnp.asarray(rng.normal(size=s).astype(np.float32)))
            for s in shapes]


def _grid(**kw):
    base = dict(meshes=("4x4_mc2",), placements=("edge", "interleaved"),
                transforms=("O0", "O1", "O2"), precisions=("fixed8",),
                max_packets_per_layer=None, stream_chunk_packets=4,
                chunk=64, result_phase=True)
    base.update(kw)
    return SweepGrid(**base)


@pytest.fixture(scope="module")
def layers():
    return _layers()


class _Clock:
    def __init__(self):
        self.t = 0

    def perf_counter_ns(self):
        return self.t


def test_recorder_arithmetic(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(spans, "time", clock)
    with spans.recording() as log:
        with spans.span("a"):
            clock.t += 10
            with spans.span("a.b", k="x"):
                clock.t += 5
            with spans.span("a.b", k="y"):
                clock.t += 3
                spans.count("c", 2)
            with spans.recording() as inner:
                with spans.span("a.c"):
                    clock.t += 4
                spans.count("c")
            assert inner.totals() == {"a.c": pytest.approx(
                {"s": 4e-9, "self_s": 4e-9, "n": 1})}
            clock.t += 1
    tot = log.totals()
    assert tot["a"] == pytest.approx({"s": 23e-9, "self_s": 11e-9, "n": 1})
    assert tot["a.b"] == pytest.approx({"s": 8e-9, "self_s": 8e-9, "n": 2})
    assert tot["a.c"]["n"] == 1      # an inner log passes its spans up
    assert log.counters == {"c": 3}
    assert log.by_arg("a.b", "k") == pytest.approx({"x": 5e-9, "y": 3e-9})
    assert log.seconds("a.b") == pytest.approx(8e-9)
    assert log.last("a.b") == pytest.approx(3e-9)
    # Without a log, spans only annotate and counters do nothing.
    with spans.span("a"):
        spans.count("c")
    assert log.counters == {"c": 3} and len(log.records) == 4


@pytest.mark.parametrize("streamed", [True, False],
                         ids=["streamed", "oneshot"])
def test_sweep_reports_every_span(layers, streamed):
    grid = _grid(max_packets_per_layer=None if streamed else 8)
    st = run_sweep(grid, lambda _m: layers, devices=None).stats
    sp, ct = st["spans"], st["counters"]
    assert set(sp) == SWEEP_SPANS and set(ct) == COUNTERS
    for name, v in sp.items():
        assert set(v) == {"s", "self_s", "n"} and v["n"] >= 1
        # children cover no more than their parent
        assert -1e-9 <= v["self_s"] <= v["s"] + 1e-9, name
    s = {k: v["s"] for k, v in sp.items()}
    assert (s["noc.packetize.order"] + s["noc.packetize.assemble"]
            <= s["noc.packetize"] + 1e-9)
    # the drain's sub-spans sum over the request and the result drain
    assert (s["noc.drain.setup"] + s["noc.drain.wait"]
            + s["noc.drain.retire"]
            <= s["noc.drain"] + s["noc.result.drain"] + 1e-9)
    assert (s["noc.packetize"] + s["noc.drain"] + s["noc.result.packetize"]
            + s["noc.result.drain"] <= s["noc.sweep"] + 1e-9)
    # one measurement, not two
    for stat, name in [("packetize_s", "noc.packetize"),
                       ("simulate_s", "noc.drain"),
                       ("result_packetize_s", "noc.result.packetize"),
                       ("result_simulate_s", "noc.result.drain")]:
        assert st[stat] == pytest.approx(s[name], abs=5.1e-5), stat
    (cls,) = st["shape_classes"]
    assert cls["packetize_s"] == st["packetize_s"]
    assert cls["simulate_s"] == st["simulate_s"]
    by_tr = st["packetize_by_transform"]
    assert set(by_tr) == {"O0", "O1", "O2"}
    assert sum(by_tr.values()) == pytest.approx(s["noc.packetize.order"],
                                                abs=2e-4)
    assert ct["drain.cycles"] <= ct["drain.stepped_cycles"]
    assert ct["drain.stepped_cycles"] % grid.chunk == 0


@pytest.mark.parametrize("chunk", [16, 64, 100])
def test_drain_counters_single_lane(layers, chunk):
    cfg = make_noc(4, 4, 2)
    traffic = build_traffic_batch(layers, cfg, [(by_name("O0"), None)],
                                  max_packets_per_layer=6)
    with spans.recording() as log:
        (res,) = simulate_batch(cfg, traffic, chunk=chunk)
    cycles = res.drain_cycle
    assert log.counters["drain.cycles"] == cycles
    assert log.counters["drain.stepped_cycles"] == \
        (math.ceil(cycles / chunk) + 1) * chunk
    # one readback per chunk read and one for the harvest; the harvest
    # copies and the SimResult build retire
    tot = log.totals()
    assert tot["noc.drain.setup"]["n"] == 1
    assert tot["noc.drain.wait"]["n"] == math.ceil(cycles / chunk) + 1
    assert tot["noc.drain.retire"]["n"] == 2
    # a direct call outside a recording counts nothing and drains the same
    (again,) = simulate_batch(cfg, traffic, chunk=chunk)
    assert again.total_bt == res.total_bt and again.drain_cycle == cycles


def test_rows_identical_under_a_trace_with_nested_spans(layers, tmp_path):
    from jax.profiler import ProfileData
    grid = _grid(result_phase=False)
    plain = run_sweep(grid, lambda _m: layers, devices=None)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = run_sweep(grid, lambda _m: layers, devices=None)
    finally:
        jax.profiler.stop_trace()
    assert traced.rows == plain.rows
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    host = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]
    # the packet chunk program carries a stable name, not ``<lambda>``
    assert any("noc_packet_chunk" in n for *_, n in host)
    events = [e for e in host if e[2].startswith("noc.")]
    names = {n for *_, n in events}
    assert {"noc.sweep", "noc.packetize", "noc.packetize.order",
            "noc.drain", "noc.drain.wait"} <= names
    (sweep,) = [(a, b) for a, b, n in events if n == "noc.sweep"]
    for a, b, n in events:
        if n in ("noc.packetize", "noc.drain"):
            assert sweep[0] <= a <= b <= sweep[1], n


def test_serving_reads_its_span(layers):
    grid = SweepGrid(meshes=("4x4_mc2",), transforms=("O0", "O1"),
                     precisions=("fixed8",), max_packets_per_layer=6,
                     chunk=64, offered_loads=(5.0,), serving_inferences=2)
    st = run_serving(grid, lambda _m: layers, devices=None).stats
    assert {"noc.serving", "noc.sweep", "noc.drain"} <= set(st["spans"])
    assert st["serving"]["serving_s"] == pytest.approx(
        st["spans"]["noc.serving"]["s"], abs=5.1e-5)
    assert set(st["counters"]) == COUNTERS
