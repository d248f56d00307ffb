"""The readers of the program's spans and counters: their arithmetic on
synthetic stats, their silence on a program that records none, and their
readings on a tiny traced run.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import types

import pytest

from conftest import TINY_CONFIG, cpu_lines, tiny_layers, tiny_traffic

import cell
import run as bench_run
import tracefile

NEW = ("order_s", "assemble_s", "drain_host_s", "drain_overstep_share")


def _stats(order, assemble, drain, wait, stepped, cycles):
    return {"packetize_s": order + assemble + 0.1, "simulate_s": drain,
            "spans": {"noc.packetize.order": {"s": order},
                      "noc.packetize.assemble": {"s": assemble},
                      "noc.drain": {"s": drain},
                      "noc.drain.wait": {"s": wait}},
            "counters": {"drain.stepped_cycles": stepped,
                         "drain.cycles": cycles}}


def _run(*stats):
    run = bench_run.Run(grid=None, setup_s=0.0)
    run.sweeps = [{"seconds": 1.0, "stats": st, "rows": []} for st in stats]
    return run


def test_span_readers_on_synthetic_stats():
    run = _run(_stats(2.0, 0.5, 3.0, 2.5, 10240, 7901),
               _stats(4.0, 1.5, 5.0, 4.0, 10240, 7901))
    want = {"order_s": 3.0, "assemble_s": 1.0, "drain_host_s": 0.75,
            "drain_overstep_share": 100 * 2339 / 10240}
    for name, value in want.items():
        for suffix in ("", ".host"):    # the twin reads the stem's file
            got = bench_run.metric_reader(name + suffix)(run)
            assert got == pytest.approx(value), name + suffix
    assert want["drain_overstep_share"] == pytest.approx(22.84, abs=5e-3)


def test_span_readers_silent_without_spans():
    """The parent program's stats carry no spans or counters: every new
    reader gives None and none raises."""
    plain = _run({"packetize_s": 1.0, "simulate_s": 2.0})
    partial = _run(_stats(1.0, 1.0, 2.0, 1.0, 2048, 100),
                   {"packetize_s": 1.0, "simulate_s": 2.0})
    for name in NEW:
        read = bench_run.metric_reader(name)
        assert read(plain) is None and read(partial) is None
        assert read(bench_run.Run(grid=None, setup_s=0.0)) is None


def test_noc_host_spans_leave_existing_readings_alone():
    """A trace whose host spans include the program's ``noc.*`` events
    reads the same in every existing reader and in the breakdown as one
    without them."""
    ms = 1e6
    ops = {0: [(0, 2 * ms, "fusion"), (6 * ms, 10 * ms, "while"),
               (12 * ms, 16 * ms, "while"), (17 * ms, 18 * ms, "copy")]}
    modules = {0: [(6 * ms, 10 * ms, "jit_run(1)"),
                   (12 * ms, 16 * ms, "jit_run(1)")]}
    bench = [(0, 0, "bench.start"), (0, 20 * ms, "bench.sweep.0")]
    noc = [(0.1 * ms, 19.9 * ms, "noc.sweep"),
           (0.2 * ms, 5.5 * ms, "noc.packetize"),
           (5.5 * ms, 17 * ms, "noc.drain"),
           (5.5 * ms, 5.9 * ms, "noc.drain.setup")]
    readings = []
    for spans in (bench, bench + noc):
        trace = tracefile.Trace(ops, modules, spans)
        run = bench_run.Run(grid=types.SimpleNamespace(chunk=1000),
                            setup_s=0)
        run.trace = trace
        trace.bound(0.02)
        readings.append(
            [bench_run.metric_reader(m)(run) for m in
             ("device_idle_share", "drain_idle_share",
              "step_device_us_per_cycle")]
            + [bench_run.breakdown(trace), trace.sweeps()])
    assert readings[0] == readings[1]


def test_span_metrics_on_a_tiny_traced_run(tiny):
    import jax
    config, traffic, layers = tiny
    name = cell.manifest()["workloads"][0]["name"]
    res = bench_run.run_cell(name, config, traffic, 7, 0.5, True,
                             jax.devices(), layers=layers, lines=cpu_lines)
    assert res["correct"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW) <= set(m)
    assert 0 < m["order_s"] and 0 < m["assemble_s"]
    assert m["order_s"] + m["assemble_s"] <= m["packetize_s"]
    assert 0 < m["drain_host_s"] <= m["drain_s"]
    assert 0 < m["drain_overstep_share"] < 100
