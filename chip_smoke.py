"""Smoke test of the NoC sweep's main path on a TPU, in one process.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the variants-sharded drain, 4 chips

One chip: restore the trained DarkNet from the committed checkpoint,
extract its full, unsubsampled operand traffic (~99.7k packets, ~1.31M
flits) and drain it to completion through ``run_sweep`` on the 16x16 mesh
with 16 MCs (interleaved placement, round-robin affinity, O0/O1/O2,
fixed8, request phase) - once cold, once warm. Then check, against the
CPU backend of the same process, that these are bit-identical: the full
LeNet traffic extracted and packetized on 4x4/MC2 (O0-O3, fixed8) and its
whole drain (the TPU drain carries the conservation ledger); the full
DarkNet traffic extracted and packetized as in the sweep, and the first
4096-cycle chunk of its drain. The DarkNet rows must reproduce the
recorded drain cycles and flit counts (``experiments/darknet_full.json``).

``--chips 4``: only the DarkNet sweep, sharded over every chip
(``devices="auto"``) and on one chip (``devices=None``); the rows must be
bit-identical.

Exits non-zero, without the final line, when JAX finds no TPU. The last
line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from benchmarks._trained import get_trained  # noqa: E402
from repro import enable_compile_cache  # noqa: E402
from repro.core.wire import by_name  # noqa: E402
from repro.data import glyph_batch  # noqa: E402
from repro.noc import (SweepGrid, build_traffic_streamed,  # noqa: E402
                       fuse_traffic, make_state, mc_placement, mesh_by_name,
                       run_sweep, simulate_batch)
from repro.noc.sim import _chunk_runner, _mesh_key  # noqa: E402
from repro.quant import quantize_fixed8  # noqa: E402

CHUNK = 4096
DARKNET_MESH = "16x16_mc16"
DARKNET_RECORD = os.path.join(ROOT, "experiments", "darknet_full.json")
TRAFFIC_FIELDS = ("words", "dest", "meta", "vc", "pkt", "length")


def _fail(msg: str) -> None:
    print(f"chip_smoke FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def _layers(name: str, glyph_seed: int):
    """One inference's operand traffic under the committed trained weights
    (a missing or broken checkpoint raises; nothing is retrained)."""
    model, params, _ = get_trained(name)
    hw, ch = model.input_shape[0], model.input_shape[-1]
    x, _ = glyph_batch(jax.random.PRNGKey(glyph_seed), 1, hw=hw, channels=ch)
    return model.layer_traffic(params, x[0])


def darknet_grid():
    return SweepGrid(
        meshes=(DARKNET_MESH,), placements=("interleaved",),
        affinity=("roundrobin",), transforms=("O0", "O1", "O2"),
        tiebreaks=("pattern",), precisions=("fixed8",), models=("darknet",),
        max_packets_per_layer=None, chunk=CHUNK)


def timed_sweep(grid, layers, devices):
    t0 = time.perf_counter()
    report = run_sweep(grid, lambda _name: layers, devices=devices)
    return report, time.perf_counter() - t0


def check_darknet_rows(report) -> None:
    """Every lane drained all flits in the recorded number of cycles (drain
    timing never reads payload values), and ordering cut BT."""
    with open(DARKNET_RECORD) as f:
        record = json.load(f)
    base = report.row(transform="O0")["total_bt"]
    for r in report.rows:
        want = record[f"{r['mesh']}/{r['placement']}/{r['precision']}/"
                      f"{r['transform']}"]
        for key in ("cycles", "flits"):
            if r[key] != want[key]:
                _fail(f"{r['transform']} {key} {r[key]} != recorded "
                      f"{want[key]}")
        if r["transform"] != "O0" and not r["total_bt"] < base:
            _fail(f"{r['transform']} did not reduce BT: {r['total_bt']} "
                  f">= O0 {base}")


def print_sweep(label: str, report, wall: float) -> None:
    st = report.stats
    cycles = report.rows[0]["cycles"]
    print(f"[{label}] wall {wall:.3f} s: packetize {st['packetize_s']} s, "
          f"drain {st['simulate_s']} s ({cycles} cycles -> "
          f"{cycles / st['simulate_s']:.1f} mesh cycles/s, "
          f"{st['cycles_per_sec']} stepped lane-cycles/s over "
          f"{len(report.rows)} lanes)")
    for r in report.rows:
        red = ("" if r["transform"] == "O0" else
               f" reduction {r['reduction_pct']:.4f}% "
               f"(adjusted {r['adjusted_reduction_pct']:.4f}%)")
        print(f"[{label}]   {r['transform']}: drain {r['cycles']} cycles, "
              f"{r['flits']} flits, total BT {r['total_bt']}{red}")


def _host_traffic(traffic):
    return traffic._replace(**{f: np.asarray(getattr(traffic, f))
                               for f in TRAFFIC_FIELDS})


def packetize_on(devices, label, layers_of, cfg, transforms):
    """Extract and packetize the same inference under fixed8 with each
    device as the default one; every traffic array must be bit-identical."""
    fixed8 = lambda t: quantize_fixed8(t).values  # noqa: E731
    variants = [(by_name(o, tiebreak="pattern"), fixed8) for o in transforms]
    out = []
    for dev in devices:
        with jax.default_device(dev):
            t0 = time.perf_counter()
            out.append(_host_traffic(build_traffic_streamed(layers_of(), cfg,
                                                            variants)))
        print(f"[{label} on {dev.platform}] extracted and packetized in "
              f"{time.perf_counter() - t0:.3f} s (compile included)")
    bad = {f: [o for i, o in enumerate(transforms)
               if not np.array_equal(getattr(out[0], f)[i],
                                     getattr(out[1], f)[i])]
           for f in TRAFFIC_FIELDS}
    bad = {f: lanes for f, lanes in bad.items() if lanes}
    if bad:
        _fail(f"{label} traffic differs between TPU and CPU "
              f"(field: transforms) {bad}")
    print(f"[{label}] TPU == CPU traffic bit for bit ({', '.join(TRAFFIC_FIELDS)}"
          f"; {int(out[0].length.sum())} flits)")
    return out[0]


def check_lenet(tpu, cpu) -> None:
    """Whole full-LeNet run on 4x4/MC2 under O0-O3, TPU == CPU: the traffic
    and the drain (the TPU drain carries the conservation ledger)."""
    cfg = mesh_by_name("4x4_mc2")
    transforms = ("O0", "O1", "O2", "O3")
    traffic = packetize_on((tpu, cpu), "lenet 4x4_mc2",
                           lambda: _layers("lenet", 7), cfg, transforms)
    runs = []
    for dev, track in ((tpu, True), (cpu, False)):
        with jax.default_device(dev):
            t0 = time.perf_counter()
            runs.append(simulate_batch(cfg, traffic, chunk=CHUNK,
                                       check_conservation=track))
            dt = time.perf_counter() - t0
        print(f"[lenet 4x4_mc2 on {dev.platform}] whole drain {dt:.3f} s "
              f"(compile included), drain cycles "
              f"{[r.drain_cycle for r in runs[-1]]}")
    for o, a, b in zip(transforms, *runs):
        if (a.total_bt != b.total_bt or a.drain_cycle != b.drain_cycle
                or not np.array_equal(a.link_bt, b.link_bt)):
            _fail(f"LeNet {o} drain differs: tpu total_bt {a.total_bt} "
                  f"cycle {a.drain_cycle}, cpu total_bt {b.total_bt} cycle "
                  f"{b.drain_cycle}")
    print(f"[lenet 4x4_mc2] TPU == CPU bit for bit (total_bt, link_bt, "
          f"drain_cycle) on O0-O3; total_bt "
          f"{[r.total_bt for r in runs[0]]}")


def check_darknet_chunk(tpu, cpu) -> None:
    """The DarkNet traffic, TPU == CPU, and its drain's first chunk, leaf
    by leaf."""
    cfg = mesh_by_name(DARKNET_MESH)
    cfg = dataclasses.replace(cfg, mc_nodes=mc_placement(
        cfg.rows, cfg.cols, cfg.num_mcs, "interleaved"))
    traffic = packetize_on((tpu, cpu), "darknet",
                           lambda: _layers("darknet", 11), cfg,
                           ("O0", "O1", "O2"))
    b, m = traffic.length.shape
    run = _chunk_runner(_mesh_key(cfg), True, CHUNK, True, False, "fused")
    out = []
    for dev in (tpu, cpu):
        with jax.default_device(dev):
            state = jax.tree.map(lambda x: jnp.broadcast_to(x, (b,) + x.shape),
                                 make_state(cfg, m))
            mc = jnp.broadcast_to(jnp.asarray(cfg.mc_nodes, jnp.int32), (b, m))
            t0 = time.perf_counter()
            state, _ = run(state, fuse_traffic(traffic), mc)
            out.append({k: np.asarray(v) for k, v in state._asdict().items()
                        if v is not None})
            dt = time.perf_counter() - t0
        print(f"[darknet first chunk on {dev.platform}] {CHUNK} cycles x "
              f"{b} lanes in {dt:.3f} s (compile included)")
    bad = [k for k in out[0] if not np.array_equal(out[0][k], out[1][k])]
    if bad:
        _fail(f"DarkNet first chunk differs between TPU and CPU in {bad}")
    print(f"[darknet first chunk] TPU == CPU in every state leaf "
          f"({', '.join(out[0])}); ejected {out[0]['ejected']}")


def one_chip(devices) -> None:
    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    layers = _layers("darknet", 11)
    print(f"[darknet] restored checkpoint and extracted {len(layers)} "
          f"layers in {time.perf_counter() - t0:.3f} s")
    grid = darknet_grid()
    cold, cold_s = timed_sweep(grid, layers, None)
    print_sweep(f"darknet {DARKNET_MESH} cold", cold, cold_s)
    warm, warm_s = timed_sweep(grid, layers, None)
    print_sweep(f"darknet {DARKNET_MESH} warm", warm, warm_s)
    print(f"[darknet] first-call overhead (compile): packetize "
          f"{cold.stats['packetize_s'] - warm.stats['packetize_s']:.3f} s, "
          f"drain {cold.stats['simulate_s'] - warm.stats['simulate_s']:.3f} s")
    if cold.rows != warm.rows:
        _fail("cold and warm DarkNet sweeps disagree")
    check_darknet_rows(warm)
    check_lenet(devices[0], cpu)
    check_darknet_chunk(devices[0], cpu)


def four_chips(devices) -> None:
    """One-chip sweep first (it also warms the packetizer), then the same
    sweep sharded over every chip and again on one chip; all rows must be
    bit-identical. The sharded wall time includes its drain's compile."""
    layers = _layers("darknet", 11)
    grid = darknet_grid()
    runs = []
    for label, devs, want in (("one chip, first", None, 1),
                              ("sharded x4", "auto", len(devices)),
                              ("one chip", None, 1)):
        report, wall = timed_sweep(grid, layers, devs)
        if report.stats["devices"] != want:
            _fail(f"{label} ran on {report.stats['devices']} devices")
        print_sweep(f"darknet {label}", report, wall)
        runs.append(json.dumps(report.rows, sort_keys=True))
    if len(set(runs)) != 1:
        _fail("sharded and one-chip DarkNet rows differ")
    check_darknet_rows(report)
    print("[darknet] sharded x4 rows == one-chip rows, bit for bit")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU: JAX sees {devices[0].platform} devices")
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} TPUs, "
              f"JAX sees {len(devices)}")
    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {devices[0].device_kind} x {len(devices)}; every time "
          "below is from this run on this chip")
    if args.chips == 4:
        four_chips(devices)
    else:
        one_chip(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
