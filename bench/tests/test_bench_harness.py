"""The harness itself: manifest, window loop, metric arithmetic, trace
reduction, the reference against the program, and the no-chip exit.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import json
import os
import re
import subprocess
import sys
import time
import types

import pytest

from conftest import (BENCH, ROOT, TINY_CONFIG, cpu_lines, tiny_layers,
                      tiny_traffic)

import cell
import run as bench_run
import tracefile

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
ONE_LINE = re.compile(r"^[^\t\n\r]{1,200}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_keys_names_and_units(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    for p in manifest["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
    for w in manifest["command"]:
        assert ONE_LINE.match(w)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and ONE_LINE.match(c["source"])
        assert ONE_LINE.match(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and ONE_LINE.match(w["why"])
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in names
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and ONE_LINE.match(m["layer"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert len(json.dumps(manifest)) < 64 * 1024


def test_manifest_files_exist(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    for c in manifest["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in manifest["workloads"]:
        _, config, traffic = cell.workload(w["name"])
        assert config["name"] == w["config"]
        assert set(traffic["grid"]) >= {"placements", "affinity",
                                        "transforms"}
        cell.sweep_grid(config, traffic)       # the grid builds
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert callable(bench_run.metric_reader(m["name"]))


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    wl = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         wl[0]["name"], "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no TPU" in p.stderr


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        return self.t


def test_window_runs_whole_sweeps_that_fit(monkeypatch):
    clock = _FakeClock()
    monkeypatch.setattr(bench_run, "time", clock)
    durations = iter([3.0, 2.0, 2.0, 2.5, 9.0])

    def fake_sweep(grid, layers, devices):
        dt = next(durations)
        clock.t += dt
        return types.SimpleNamespace(stats={"packetize_s": dt / 2,
                                            "simulate_s": dt / 4},
                                     rows=[]), dt

    monkeypatch.setattr(bench_run, "timed_sweep", fake_sweep)
    run = bench_run.Run(grid=None, setup_s=1.0)
    counter = types.SimpleNamespace(on=False)
    bench_run.window(run, None, None, 10.0, counter)
    # 3 -> 5 -> 7 -> 9.5: after the fourth, 9.5 + 2.5 > 10 stops the loop.
    assert [s["seconds"] for s in run.sweeps] == [3.0, 2.0, 2.0, 2.5]
    assert run.window_s == 9.5
    assert bench_run.metric_reader("sweep_s")(run) == 9.5 / 4
    assert bench_run.metric_reader("packetize_s")(run) == 9.5 / 2 / 4
    assert bench_run.metric_reader("drain_s")(run) == 9.5 / 4 / 4
    assert bench_run.metric_reader("setup_s")(run) == 1.0

    clock.t, durations = 0.0, iter([30.0])
    run = bench_run.Run(grid=None, setup_s=1.0)
    bench_run.window(run, None, None, 10.0, counter)
    assert len(run.sweeps) == 1 and run.window_s == 30.0   # at least one


def test_trace_metric_arithmetic():
    ms = 1e6
    trace = tracefile.Trace(
        ops={0: [(0, 2 * ms, "fusion"), (1 * ms, 3 * ms, "fusion"),
                 (6 * ms, 10 * ms, "while"), (12 * ms, 16 * ms, "while"),
                 (17 * ms, 18 * ms, "copy")]},
        modules={0: [(6 * ms, 10 * ms, "jit_run(1)"),
                     (12 * ms, 16 * ms, "jit_run(1)"),
                     (17 * ms, 18 * ms, "jit_other")]},
        spans=[(0, 0, "bench.start"), (0, 20 * ms, "bench.sweep.0")])
    run = bench_run.Run(grid=types.SimpleNamespace(chunk=1000), setup_s=0)
    run.trace = trace
    # a host clock that stopped a little before the sweep's span ended
    assert trace.bound(0.0199) == (0, 20 * ms)
    assert trace.sweeps() == [(0, 20 * ms)]
    # busy: [0,3] + [6,10] + [12,16] + [17,18] = 12 of 20 ms
    assert bench_run.metric_reader("device_idle_share")(run) == \
        pytest.approx(40.0)
    # drain span [6, 16]: busy 8 of 10 ms
    assert bench_run.metric_reader("drain_idle_share")(run) == \
        pytest.approx(20.0)
    # 8 ms of chunk programs over 2 x 1000 cycles
    assert bench_run.metric_reader("step_device_us_per_cycle")(run) == \
        pytest.approx(4.0)
    labels = dict((k, v) for k, v in reversed(
        tracefile.labelled_gaps(trace, 0, 20 * ms)))
    assert labels["sweep 0: packetize, before the first drain program"] \
        == pytest.approx(3e-3)
    assert labels["sweep 0: drain host loop, between drain programs"] \
        == pytest.approx(2e-3)
    assert labels["sweep 0: rows, after the last drain program"] \
        == pytest.approx(2e-3)
    assert tracefile.top_ops(trace, 0, 20 * ms)[0] == ["while", 8e-3]
    # Dropped from 14 ms on: the window ends there and the sweep, no longer
    # whole, counts as the tail up to it.
    cut = tracefile.Trace(trace.ops, trace.modules, trace.spans,
                          dropped=[(14 * ms, 20 * ms)])
    run.trace = cut
    assert cut.bound(0.020) == (0, 14 * ms)
    assert cut.sweeps() == [(0, 14 * ms)]
    # busy [0,3] + [6,10] + [12,14] = 9 of 14 ms; one program left whole
    assert bench_run.metric_reader("device_idle_share")(run) == \
        pytest.approx(500 / 14)
    assert bench_run.metric_reader("step_device_us_per_cycle")(run) == \
        pytest.approx(4.0)
    empty = bench_run.Run(grid=None, setup_s=0)
    for name in ("device_idle_share", "drain_idle_share",
                 "step_device_us_per_cycle"):
        assert bench_run.metric_reader(name)(empty) is None


def test_trace_reduction_on_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(x):
        return jax.lax.fori_loop(0, 50, lambda i, a: jnp.tanh(a @ a), x)

    x = jnp.ones((128, 128)) * 0.01
    run(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.start"):
        t0 = time.perf_counter()
    for i in range(2):
        with jax.profiler.TraceAnnotation(f"bench.sweep.{i}"):
            run(x).block_until_ready()
            run(x).block_until_ready()
    span_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    trace = tracefile.load(str(tmp_path), cpu_lines)
    lo, hi = trace.bound(span_s)
    assert len(trace.sweeps()) == 2 and hi > lo
    busy = tracefile.device_busy_s(trace, lo, hi)
    assert 0 < busy <= (hi - lo) * 1e-9
    assert tracefile.top_ops(trace, lo, hi)
    d = tracefile.drains(trace, r"PjitFunction\(run\)")
    # one per sweep; the CPU client may log a dispatch more than once
    assert len(d) == 2 and d[0]["programs"] == d[1]["programs"] >= 2
    assert all(0 <= x["busy_ns"] <= x["span_ns"] for x in d)


def test_traced_sweep_follows_the_window(monkeypatch):
    """The profiler runs over one sweep after the window, so the host-clock
    metrics read only untraced sweeps."""
    clock = _FakeClock()
    monkeypatch.setattr(bench_run, "time", clock)
    events = []
    monkeypatch.setattr("jax.profiler.start_trace",
                        lambda d: events.append(("start", clock.t)))
    monkeypatch.setattr("jax.profiler.stop_trace",
                        lambda: events.append(("stop", clock.t)))
    durations = iter([2.0, 2.0, 2.0, 7.0])

    def fake_sweep(grid, layers, devices):
        dt = next(durations)
        clock.t += dt
        return types.SimpleNamespace(stats={"packetize_s": dt / 2,
                                            "simulate_s": dt / 2},
                                     rows=[]), dt

    monkeypatch.setattr(bench_run, "timed_sweep", fake_sweep)
    run = bench_run.Run(grid=None, setup_s=0.0)
    bench_run.window(run, None, None, 6.0, types.SimpleNamespace(on=False))
    assert events == [] and len(run.sweeps) == 3 and run.window_s == 6.0
    span_s = bench_run.traced_sweep(run, None, None, "unused")
    assert events == [("start", 6.0), ("stop", 13.0)] and span_s == 7.0
    assert [s["seconds"] for s in run.traced] == [7.0]
    assert bench_run.metric_reader("packetize_s")(run) == 1.0
    assert bench_run.metric_reader("drain_s.host")(run) == 1.0
    assert bench_run.metric_reader("sweep_s")(run) == 2.0


def test_reference_matches_the_program_on_a_tiny_cell():
    from repro.noc import run_sweep
    from reference import reference_rows
    import check
    traffic = tiny_traffic(("O0", "O1", "O2", "O3"))
    traffic["grid"]["placements"] = ["interleaved", "edge"]
    traffic["grid"]["affinity"] = ["roundrobin", "nearest"]
    prog, host = tiny_layers(1, shapes=((20, 25), (8, 30), (5, 17)))
    grid = cell.sweep_grid(TINY_CONFIG, traffic)
    report = run_sweep(grid, lambda _m: prog, devices=None)
    ref = reference_rows(host, TINY_CONFIG, traffic)
    numbers, due, wrong = check.compare([report.rows], ref)
    assert check.passed(numbers), numbers
    assert due == 16 and wrong == 0


def test_window_loop_on_a_tiny_cell(tiny):
    import jax
    config, traffic, layers = tiny
    res = bench_run.run_cell("tiny", config, traffic, 5, 1.0, False,
                             jax.devices(), layers=layers)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] % 3 == 0 and res["attempted"] >= 3
    assert list(res)[-1] == "check"
    assert set(res["check"]) == set(__import__("check").LIMITS)


def test_traced_run_on_a_tiny_cell(tiny):
    import jax
    config, traffic, layers = tiny
    # the tiny cell reads the metrics the manifest gives the first cell
    name = cell.manifest()["workloads"][0]["name"]
    res = bench_run.run_cell(name, config, traffic, 5, 0.5, True,
                             jax.devices(), layers=layers, lines=cpu_lines)
    assert res["correct"]
    dev = res["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(res["breakdown"]["device_ops"]) <= 10
    assert {"packetize_s", "drain_s", "device_idle_share"} <= set(
        res["metrics"])
    # the traced sweep lies inside the traced window, so its gaps are named
    assert any(label.startswith("sweep 0:")
               for label, _ in res["breakdown"]["idle_gaps"])
