"""Benchmark suite entry point - one module per paper table/figure plus the
framework-level analyses. Prints ``name,us_per_call,derived`` CSV lines.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run table1 fig12
    PYTHONPATH=src python -m benchmarks.run fig12 --transforms O0,O1,O2,O3

After each invocation the NoC-relevant trajectory numbers (per-suite
wall-clock, sweep-engine cycles/sec and packetizer time, result-phase and
affinity deltas, and the pinned speedup-vs-seed-driver comparison) are
written to ``BENCH_noc.json`` at the repo root so future PRs can track
sweep-engine performance. Every suite key and field is documented in
``docs/bench_schema.md``.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

from repro import enable_compile_cache

from . import (table1, compression, fig1_expectation, fig10_11, fig12,
               fig13, table2_power, darknet_full, faults, kernel_backend,
               ordered_collectives, ordering_throughput, roofline,
               serving, static_layout, step_overhaul)

SUITES = {
    "table1": table1.main,                    # Tab. I: BT reduction w/o NoC
    "fig1": fig1_expectation.main,            # Fig. 1: E[BT] surface
    "fig10_11": fig10_11.main,                # Figs. 10-11: bit distributions
    "fig12": fig12.main,                      # Fig. 12: NoC sizes x O0/O1/O2
    "fig13": fig13.main,                      # Fig. 13: LeNet vs DarkNet
    "table2": table2_power.main,              # Tab. II + link power model
    "darknet_full": darknet_full.main,        # beyond-paper: full traffic,
                                              # 16x16, placements, sharding
    "step_overhaul": step_overhaul.main,      # fused-step before/after cps
    "kernel_backend": kernel_backend.main,    # Pallas step + batched-O3
                                              # ordering before/after
    "ordered_collectives": ordered_collectives.main,  # beyond-paper: ICI
    "ordering_throughput": ordering_throughput.main,
    "roofline": roofline.main,                # from dry-run artifacts
    "static_layout": static_layout.main,      # trained-vs-random layouts
    "serving": serving.main,                  # closed-loop: latency vs load
    "faults": faults.main,                    # fault injection: BT + SLO
                                              # under flips/dead links
    "compression": compression.main,          # ordering x MSR co-design:
                                              # does ordering pay on 5b lanes
}

BENCH_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_noc.json")


def main() -> None:
    enable_compile_cache()
    argv = sys.argv[1:]
    transforms = None
    if "--transforms" in argv:
        i = argv.index("--transforms")
        transforms = tuple(t.strip() for t in argv[i + 1].split(",") if t.strip())
        argv = argv[:i] + argv[i + 2:]
    picks = argv or list(SUITES)
    failed = []
    bench = {"suites": {}}
    # The pinned speedup comparison runs first, while the process is cold:
    # both the seed driver and the sweep engine pay their own compiles.
    if "fig12" in picks:
        try:
            bench["reference_compare"] = fig12.reference_compare()
            rc = bench["reference_compare"]
            print(f"fig12/reference_compare,{rc['sweep_s'] * 1e6:.0f},"
                  f"speedup={rc['speedup']}x bt_identical={rc['bt_identical']}")
        except Exception as e:  # noqa: BLE001
            failed.append("fig12:reference_compare")
            print(f"fig12:reference_compare,0,FAILED:{type(e).__name__}:{e}")
            traceback.print_exc()
    for name in picks:
        try:
            t0 = time.perf_counter()
            # `--transforms O0,O1,O2,O3` widens the ordering axis of the
            # sweep-driven figure suites (e.g. to include the O3 lanes and
            # record the o3_vs_o2 verdict); others keep their defaults.
            if transforms and name in ("fig12", "fig13"):
                out = SUITES[name](transforms=transforms)
            else:
                out = SUITES[name]()
            entry = {"wall_s": round(time.perf_counter() - t0, 3)}
            # Sweep-driven suites return {"results", "bench"}; record the
            # engine stats (cycles/sec simulated, packetizer wall-clock, ...)
            if isinstance(out, dict) and "bench" in out:
                entry.update(out["bench"])
            bench["suites"][name] = entry
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            print(f"{name},0,FAILED:{type(e).__name__}:{e}")
            traceback.print_exc()
    # Merge into the existing trajectory file: a selective run (e.g.
    # `benchmarks.run table1`) must not wipe recorded sweep stats.
    merged = {"suites": {}}
    if os.path.exists(BENCH_PATH):
        try:
            with open(BENCH_PATH) as f:
                merged = json.load(f)
        except (OSError, ValueError):
            pass
    merged.setdefault("suites", {}).update(bench["suites"])
    if "reference_compare" in bench:
        merged["reference_compare"] = bench["reference_compare"]
    # The cross-PR step trajectory: *derived* numbers only - the raw
    # pinned-chunk record lives solely under suites/step_overhaul (it used
    # to be duplicated wholesale at top level; see docs/bench_schema.md).
    dk = merged["suites"].get("darknet_full", {})
    if dk.get("cycles_per_sec"):
        merged["step_trajectory"] = {
            "darknet_full_cps_pr3": step_overhaul.PR3_DARKNET_CPS,
            "darknet_full_cps": dk["cycles_per_sec"],
            "darknet_full_speedup": round(
                dk["cycles_per_sec"] / step_overhaul.PR3_DARKNET_CPS, 2),
        }
    merged.pop("step_overhaul", None)   # drop the pre-PR-7 duplicate block
    # Atomic write: a crash mid-dump must not truncate the trajectory file
    # (the merge above would then silently drop every prior suite's stats).
    tmp = BENCH_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(merged, f, indent=1)
    os.replace(tmp, BENCH_PATH)
    if failed:
        raise SystemExit(f"failed suites: {failed}")


if __name__ == "__main__":
    main()
