"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A run makes the layer operands of one inference from ``--seed`` (by
default ``operands.py``, on the seed's image with the weights kept beside
the benchmark; a configuration may name its own harness module, see
``cell.harness``), warms up every program the window will use (set-up),
then drives ``repro.noc.run_sweep`` in whole sweeps for ``--seconds``. With
``--trace 1`` one more whole sweep follows the window under the profiler.
Afterwards every row of every sweep is compared with the plain reference
(by default ``reference.py``), and the last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last the compared
numbers beside their limits under ``check``.

``--trace 0`` reports the cell's end-to-end metrics and ``--trace 1`` its
per-layer metrics, each read by ``metrics/<name>.py``: the host-clock spans
from the window's untraced sweeps, the device metrics from the traced one.
The run exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
for _p in (BENCH, os.path.join(BENCH, "metrics")):   # readers import siblings
    if _p not in sys.path:
        sys.path.insert(0, _p)

import cell as cells  # noqa: E402
import check  # noqa: E402
import tracefile  # noqa: E402

class NoChip(RuntimeError):
    pass


def require_chips(n: int):
    """The first ``n`` TPU devices; raises :class:`NoChip` otherwise."""
    import jax
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform "
                     f"{devs[0].platform if devs else None!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def compile_cache() -> None:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache`` at the checkout's root. Every program
    is cached, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs lowered and compiled while ``on``."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.on = False
        self.lowered = self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, _duration, **_kw):
        if self.on:
            self.lowered += event == self.LOWER
            self.compiled += event == self.COMPILE


class Run:
    """What the metric readers read: set-up and window seconds, each
    window sweep's seconds, stats and rows, the grid, and the reduced
    trace of the sweep traced after the window."""

    def __init__(self, grid, setup_s: float):
        self.grid = grid
        self.setup_s = setup_s
        self.window_s = 0.0
        self.sweeps: List[Dict] = []
        self.traced: List[Dict] = []    # the traced sweep, after the window
        self.trace: Optional[tracefile.Trace] = None
        self.failed_sweeps = 0          # sweeps that raised instead


def metric_reader(name: str) -> Callable:
    """``read`` of ``metrics/<name>.py``. A name with a suffix, such as
    ``drain_s.host``, which splits one quantity by the end-to-end metric
    it moves, falls back to the reader of its stem, ``metrics/drain_s.py``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(BENCH, "metrics", f"{name.split('.')[0]}.py")
    return cells.load_module(path, f"metric_{name}").read


def cell_metrics(name: str, trace: bool) -> List[dict]:
    """The metrics the manifest gives this cell for this kind of run."""
    m = cells.manifest()
    pool = m["per_layer"] if trace else m["end_to_end"]
    return [x for x in pool if "workloads" not in x or name in x["workloads"]]


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def timed_sweep(grid, layers, devices):
    from repro.noc import run_sweep
    t0 = time.perf_counter()
    report = run_sweep(grid, lambda _model: layers, devices=devices)
    return report, time.perf_counter() - t0


def warm_up(config, traffic, layers, devices):
    """One sweep of the cell, whole, or cut at ``warmup_cycles`` drain
    cycles where the traffic mix says every program is built by then."""
    from repro.noc import DrainTimeout
    cut = traffic.get("warmup_cycles")
    grid = cells.sweep_grid(config, traffic, **(
        {"max_cycles": cut} if cut else {}))
    try:
        timed_sweep(grid, layers, devices)
    except DrainTimeout:
        if not cut:
            raise


def one_sweep(run: Run, layers, devices, into: List[Dict]) -> bool:
    """One whole sweep appended to ``into``; False where it raised."""
    import jax
    n = len(run.sweeps) + len(run.traced)
    try:
        with jax.profiler.TraceAnnotation(f"bench.sweep.{n}"):
            report, dt = timed_sweep(run.grid, layers, devices)
    except Exception:       # a sweep that never answers
        traceback.print_exc()
        run.failed_sweeps += 1
        return False
    into.append({"seconds": dt, "stats": report.stats, "rows": report.rows})
    return True


def window(run: Run, layers, devices, seconds: float, counter):
    """Whole sweeps back to back while the last one's duration still fits
    before ``seconds``; at least one."""
    counter.on = True
    t0 = time.perf_counter()
    while one_sweep(run, layers, devices, run.sweeps):
        run.window_s = time.perf_counter() - t0
        if run.window_s + run.sweeps[-1]["seconds"] > seconds:
            break
    run.window_s = time.perf_counter() - t0
    counter.on = False


def traced_sweep(run: Run, layers, devices, trace_dir: str) -> float:
    """One more whole sweep under the profiler, after the window, so that
    tracing slows no sweep the host-clock metrics read. The profiler
    records every operation of every scan step, and its buffers drop
    events after some seconds of drain (~8 s at 8x8; ``tracefile`` keeps
    what comes before). Returns the seconds traced; the trace is written
    out after them."""
    import jax
    jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation("bench.start"):
        t0 = time.perf_counter()
    one_sweep(run, layers, devices, run.traced)
    span_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    return span_s


def peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def breakdown(trace: tracefile.Trace) -> Dict:
    lo, hi = trace.window
    return {"device_ops": tracefile.top_ops(trace, lo, hi),
            "idle_gaps": tracefile.labelled_gaps(trace, lo, hi)}


def run_cell(name: str, config: dict, traffic: dict, seed: int,
             seconds: float, trace: bool, devices, layers=None,
             lines=tracefile.tpu_lines) -> Dict:
    """Set-up, window, check and metrics of one run; returns the result
    object. The configuration's harness (``cell.harness``) makes the
    operands and the reference rows; ``layers`` (program layers, host
    layers) stands in for its operands. ``lines`` tells the trace reduction
    where the device events are."""
    import jax
    compile_cache()
    counter = CompileCounter()
    harness = cells.harness(config)
    if layers is None:
        layers = harness.cell_layers(config, seed)
        log(f"operands made: {cells.layer_shapes(layers[1])}")
    prog_layers, host_layers = layers
    devs = "auto" if len(devices) == len(jax.devices()) else list(devices)
    warm_up(config, traffic, prog_layers, devs)
    setup_s = time.perf_counter() - T_START
    log(f"set-up done: {setup_s:.3f} s")

    run = Run(cells.sweep_grid(config, traffic), setup_s)
    window(run, prog_layers, devs, seconds, counter)
    print(f"compiles_in_window: lowered {counter.lowered}, "
          f"compiled {counter.compiled}", flush=True)
    log(f"window: {len(run.sweeps)} sweeps in {run.window_s:.3f} s")
    if trace:
        with tempfile.TemporaryDirectory() as tdir:
            span_s = traced_sweep(run, prog_layers, devs, tdir)
            run.trace = tracefile.load(tdir, lines, devices=len(devices))
        lo, hi = run.trace.bound(span_s)
        log(f"trace: {span_s:.3f} s traced, {(hi - lo) * 1e-9:.3f} s kept")
    peak = peak_bytes(devices)

    ref = harness.reference_rows(host_layers, config, traffic, log=log)
    numbers, due, wrong = check.compare(
        [s["rows"] for s in run.sweeps + run.traced]
        + [[]] * run.failed_sweeps, ref)
    log(f"reference and check done: {due} rows due, {wrong} wrong")
    metrics = {}
    for m in cell_metrics(name, trace):
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": check.passed(numbers), "attempted": due,
              "failed": wrong, "metrics": metrics, "device": device}
    if trace:
        lo, hi = run.trace.window
        device["busy_s"] = tracefile.device_busy_s(run.trace, lo, hi)
        device["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = breakdown(run.trace)
    result["check"] = numbers
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    entry, config, traffic = cells.workload(args.workload)
    try:
        devices = require_chips(int(entry["chips"]))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    log(f"{args.workload} on {len(devices)} x {devices[0].device_kind}, "
        f"seed {args.seed}")
    result = run_cell(args.workload, config, traffic, args.seed,
                      args.seconds, bool(args.trace), devices)
    for line in check.lines(result["check"]):
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
