"""Run a LeNet inference's operand traffic through the cycle-level NoC
under O0/O1/O2 and report per-configuration link BT - the paper's Fig. 12
pipeline end to end (train -> quantize -> packetize -> order -> simulate),
driven by the declarative sweep engine: all three orderings are packetized
once and drained in a single batched, compile-cached simulation.
``--results`` also drains the PE->MC result phase; ``--affinity nearest``
serves each PE from its hop-minimizing MC instead of round-robin; ``--o3``
adds the beyond-paper O3/O3a min-Hamming ordering lanes.

    PYTHONPATH=src python examples/noc_inference.py [--noc 8x8_mc4] [--f32]
"""
import argparse

import jax

from repro import enable_compile_cache
from repro.data import glyph_batch
from repro.models import LeNet, init_params
from repro.noc import (AFFINITIES, PAPER_NOCS, PLACEMENTS, SweepGrid,
                       mc_placement, mesh_by_name, run_sweep)
from repro.noc.power import link_power_mw, ordering_overhead_mw
from repro.optim import AdamW, cosine
from repro.train import make_train_step, init_state

ap = argparse.ArgumentParser()
ap.add_argument("--noc", default="4x4_mc2",
                help=f"one of {sorted(PAPER_NOCS)} or any RxC_mcN spec")
ap.add_argument("--f32", action="store_true", help="float-32 (default fixed-8)")
ap.add_argument("--placement", default="edge", choices=sorted(PLACEMENTS),
                help="MC placement strategy (default: the paper's edge spread)")
ap.add_argument("--full", action="store_true",
                help="packetize the full inference (streamed chunked path) "
                     "instead of subsampling to --max-packets")
ap.add_argument("--affinity", default="roundrobin", choices=sorted(AFFINITIES),
                help="packet->MC assignment (nearest = hop-minimizing MC "
                     "per PE instead of the round-robin deal)")
ap.add_argument("--results", action="store_true",
                help="also drain the PE->MC result phase and report its "
                     "per-direction BT and drain cycles")
ap.add_argument("--o3", action="store_true",
                help="add the beyond-paper O3/O3a min-Hamming orderings "
                     "to the transform axis")
ap.add_argument("--train-steps", type=int, default=60)
ap.add_argument("--max-packets", type=int, default=30)
args = ap.parse_args()
enable_compile_cache()

model = LeNet()
params = init_params(model.specs(), jax.random.PRNGKey(0))
opt = AdamW(cosine(2e-3, args.train_steps, warmup=5), weight_decay=0.0)
step = jax.jit(make_train_step(lambda p, b: model.loss(p, *b), opt))
state = init_state(params, opt)
print(f"training LeNet on glyphs for {args.train_steps} steps...")
for i in range(args.train_steps):
    state, m = step(state, glyph_batch(jax.random.PRNGKey(i), 32))
print(f"final loss {float(m['loss']):.3f}")

x, _ = glyph_batch(jax.random.PRNGKey(99), 1)
layers = model.layer_traffic(state.params, x[0])
cfg = mesh_by_name(args.noc)
mc_nodes = mc_placement(cfg.rows, cfg.cols, cfg.num_mcs, args.placement)

print(f"\nNoC {args.noc}: {cfg.rows}x{cfg.cols}, {cfg.num_mcs} MCs "
      f"({args.placement} placement at routers {list(mc_nodes)}), "
      f"{cfg.num_inter_router_links} inter-router links")
grid = SweepGrid(
    meshes=(args.noc,), placements=(args.placement,),
    affinity=(args.affinity,),
    transforms=("O0", "O1", "O2", "O3", "O3a") if args.o3
    else ("O0", "O1", "O2"),
    tiebreaks=("pattern",),
    precisions=("float32" if args.f32 else "fixed8",), models=("lenet",),
    max_packets_per_layer=None if args.full else args.max_packets,
    result_phase=args.results, chunk=2048)
report = run_sweep(grid, lambda _name: layers)
print(f"packet->MC affinity: {args.affinity} "
      f"(mean {report.rows[0]['mean_hops']:.2f} hops per packet)")
for row in report.rows:
    red = "" if row["transform"] == grid.baseline else \
        f"  ({row['reduction_pct']:+.1f}% vs O0," \
        f" {row['adjusted_reduction_pct']:+.1f}% after recovery index)"
    tpc = row["total_bt"] / row["cycles"]
    pw = link_power_mw(tpc)
    res = "" if row["result_bt"] is None else \
        f" + result phase {row['result_bt']} BT / {row['result_cycles']} cyc"
    print(f"{row['transform']}: {row['total_bt']:10d} BT over "
          f"{row['cycles']} cycles -> link power {pw:7.2f} mW{red}{res}")
print(f"sweep engine: {report.stats['cycles_per_sec']:.0f} simulated "
      f"cycles/s across {report.stats['cells']} cells")
print(f"ordering-unit overhead: O1 {ordering_overhead_mw(cfg.num_mcs):.2f} mW, "
      f"O2 {ordering_overhead_mw(cfg.num_mcs, separated=True):.2f} mW")
