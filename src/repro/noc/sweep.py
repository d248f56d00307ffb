"""Declarative NoC sweep engine: the paper's Figs. 12-13 axis, batched.

The paper's headline results come from sweeping full DNNs across NoC sizes,
MC counts, orderings, and precisions. A :class:`SweepGrid` declares that
cross product once; :func:`run_sweep` then exploits two structural facts to
make the sweep cheap:

* all ordering/precision/tiebreak variants of one (mesh, model) pair share
  identical traffic *shapes* (ordering permutes words within packets,
  quantization narrows them; neither changes flit geometry), so the
  packetization skeleton is built once per shape class
  (``build_traffic_batch``) and every variant drains in a single vmapped,
  compile-cached simulation (``simulate_batch``);
* meshes of equal size share the simulator executable across models, since
  the compiled step is keyed only on (config, traffic shape).

Each grid cell yields one row: raw BT totals, exact drain cycles, the
reduction against the cell's O0 baseline, and an *honest* reduction that
charges the O2 recovery index (``WireTransform.overhead_bits_per_value``,
paper Sec. IV-C1) against the win. ``out_path`` writes the rows plus grid
metadata as a JSON artifact.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import msr
from repro.core.wire import COMPRESSIONS, WireTransform, by_name
from repro.quant import quantize_fixed8
from .topology import (AFFINITIES, NocConfig, PLACEMENTS, affinity_mc_table,
                       mc_placement, mesh_by_name, packet_mean_hops,
                       xy_link_loads)
from .traffic import (DEFAULT_RESULT_WINDOW, LayerTraffic, assemble_traffic,
                      build_result_traffic, build_traffic_batch,
                      build_traffic_streamed_multi, compression_overhead,
                      ordered_payloads, pad_traffic_length, payload_shapes,
                      result_values, stream_lengths)
from .sim import SimResult, Traffic, simulate_batch

__all__ = ["SweepGrid", "SweepReport", "run_sweep", "run_serving",
           "recovery_overhead_bits", "drain_estimate"]

Mesh = Union[str, NocConfig]
LayersFn = Callable[[str], Sequence[LayerTraffic]]

_QUANTIZERS = {
    "float32": None,
    "fixed8": lambda t: quantize_fixed8(t).values,
}


@dataclasses.dataclass(frozen=True)
class SweepGrid:
    """One declarative sweep: mesh sizes x MC placements x packet->MC
    affinities x transforms x tiebreaks x precisions x models, with an
    optional PE->MC result phase.

    meshes: PAPER_NOCS names, ``RxC_mcN`` specs, or NocConfig instances.
    placements: MC placement strategies (``topology.PLACEMENTS``). The
        default ``"edge"`` keeps every mesh's resolved mc_nodes untouched
        (for named meshes that IS the evenly-spread boundary placement);
        other strategies re-place the same MC count via ``mc_placement``.
        Placements of one mesh size stay in one shape class and share the
        compiled simulator.
    affinity: packet->MC assignment strategies (``topology.AFFINITIES``) -
        the fourth ordering knob. ``"roundrobin"`` (the default) deals
        packet g to MC ``g % M`` exactly as the seed packetizer did, and
        its rows are bit-identical to a grid without the axis;
        ``"nearest"`` serves each PE from its hop-minimizing MC
        (``topology.affinity_mc_table``). Affinity lanes ride the same
        batched drain as placements (same flit volume, different per-MC
        stream split).
    transforms: WireTransform names (``repro.core.wire.by_name``); the
        ``baseline`` transform anchors the per-cell reduction percentages.
    compression: flit payload compression schemes (``core.wire.COMPRESSIONS``)
        - the fifth ordering knob, crossed with every other axis. ``"none"``
        (the default) is the seed packetizer and its rows are bit-identical
        to a grid without the axis; ``"msr"`` runs every packet payload
        through the MSR 8b->5b codec (``repro.core.msr``) after ordering,
        shrinking flit counts and shifting drain cycles, and charges the
        per-window escape metadata at half a transition per bit in
        ``compression_overhead_bits`` / ``adjusted_bt`` - exactly how the
        O2 recovery index is priced. MSR reads int8 payloads, so ``"msr"``
        requires ``precisions`` to be exactly ``("fixed8",)`` subsets.
    max_packets_per_layer: deterministic-stride neuron subsampling budget;
        ``None`` packetizes the *full* layers through the streamed
        chunked path (``build_traffic_streamed``) instead of the one-shot
        payload cache.
    stream_chunk_packets: packet-chunk size of the streamed path.
    result_phase: also model the PE->MC result traffic: each cell's result
        packets (``traffic.build_result_traffic``) drain in a second,
        independent batched simulation and the row gains
        ``result_bt``/``result_cycles``/``result_flits`` plus the honest
        single-stream accounting columns ``result_overhead_bits``/
        ``result_adjusted_bt``/``result_adjusted_reduction_pct`` (all
        ``None`` when the phase is off - the request-phase columns are
        untouched either way).
    result_window: result values per result packet
        (``traffic.DEFAULT_RESULT_WINDOW`` when ``None``).
    """

    meshes: Sequence[Mesh] = ("4x4_mc2",)
    placements: Sequence[str] = ("edge",)
    affinity: Sequence[str] = ("roundrobin",)
    transforms: Sequence[str] = ("O0", "O1", "O2")
    tiebreaks: Sequence[str] = ("pattern",)
    precisions: Sequence[str] = ("float32", "fixed8")
    models: Sequence[str] = ("lenet",)
    compression: Sequence[str] = ("none",)
    max_packets_per_layer: Optional[int] = 40
    stream_chunk_packets: int = 4096
    count_headers: bool = True
    chunk: int = 2048
    max_cycles: int = 2_000_000
    baseline: str = "O0"
    result_phase: bool = False
    result_window: Optional[int] = None
    # Simulator step implementation ("auto"/"fused"/"pallas"): "auto" is
    # the fused jnp step on every platform; "pallas" is the CPU
    # interpret-mode parity path and raises on TPU (see
    # ``sim._resolve_backend``). Forwarded to every ``simulate_batch``
    # call the sweep makes; all backends are pinned bit-identical.
    backend: str = "auto"
    # Autotuned drain scheduling: path to a ``noc.tune`` winners table
    # (JSON, see ``repro.noc.tune``). When set, each mesh looks up its
    # shape class and the measured winner overrides ``chunk`` and the
    # compaction ratio for that drain; classes absent from the table fall
    # back to ``chunk``. Scheduling only - results stay bit-identical.
    tune_path: Optional[str] = None
    # Closed-loop serving axis (:func:`run_serving`): offered-load points
    # in inferences per 1000 cycles. Empty disables the suite; run_sweep
    # ignores these knobs entirely. Timing is transform-independent (drain
    # dynamics never read payload values - see repro.noc.online), so each
    # load point costs ONE gated drain per (mesh, placement, affinity,
    # model) combo and the whole transform axis joins by BT.
    offered_loads: Sequence[float] = ()
    serving_inferences: int = 8
    compute_latency: int = 0            # per-PE compute cycles
    arrival: str = "uniform"            # online.ARRIVAL_KINDS process
    arrival_seed: int = 0
    # Fault-injection serving axis (:mod:`repro.noc.faults`): soft-error
    # rates swept per offered-load point. Rate 0.0 with no dead links runs
    # the pinned fault-free step (bit-identical to a grid without the
    # axis); every other point drains through the fault-injecting step
    # with ``fault_protect`` flit protection and bounded retransmission.
    # Detection is payload-independent (linear codes), so serving timing
    # stays transform-independent even under faults - the load x rate
    # axis is still priced once per combo. ``deadline`` (cycles) turns on
    # per-inference SLO attainment; ``admit_queue_depth`` turns on
    # overload shedding (see ``online.simulate_online``).
    fault_rates: Sequence[float] = ()
    fault_protect: str = "crc8"
    fault_seed: int = 0
    fault_dead_links: Sequence = ()
    fault_max_retries: int = 3
    fault_ack_latency: int = 32
    deadline: Optional[int] = None
    admit_queue_depth: Optional[int] = None

    def __post_init__(self):
        from .sim import BACKENDS
        if self.backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, "
                             f"got {self.backend!r}")
        unknown = set(self.precisions) - set(_QUANTIZERS)
        if unknown:
            raise ValueError(f"unknown precisions {sorted(unknown)}; "
                             f"supported: {sorted(_QUANTIZERS)}")
        unknown = set(self.placements) - set(PLACEMENTS)
        if unknown:
            raise ValueError(f"unknown placements {sorted(unknown)}; "
                             f"supported: {sorted(PLACEMENTS)}")
        if not self.placements:
            raise ValueError("need at least one MC placement")
        unknown = set(self.affinity) - set(AFFINITIES)
        if unknown:
            raise ValueError(f"unknown affinity {sorted(unknown)}; "
                             f"supported: {sorted(AFFINITIES)}")
        if not self.affinity:
            raise ValueError("need at least one packet->MC affinity")
        if self.baseline not in self.transforms:
            raise ValueError(
                f"baseline {self.baseline!r} not in transforms {self.transforms}")
        unknown = set(self.compression) - set(COMPRESSIONS)
        if unknown:
            raise ValueError(f"unknown compression {sorted(unknown)}; "
                             f"supported: {COMPRESSIONS}")
        if not self.compression:
            raise ValueError("need at least one compression scheme")
        if "msr" in self.compression:
            nonint = set(self.precisions) - {"fixed8"}
            if nonint:
                raise ValueError(
                    "compression 'msr' reads int8 payloads; drop precisions "
                    f"{sorted(nonint)} or sweep compression=('none',)")
        from .online import ARRIVAL_KINDS
        if self.arrival not in ARRIVAL_KINDS:
            raise ValueError(f"arrival must be one of {ARRIVAL_KINDS}, "
                             f"got {self.arrival!r}")
        if any(not load > 0 for load in self.offered_loads):
            raise ValueError("offered_loads must be > 0 "
                             f"(got {tuple(self.offered_loads)})")
        if self.serving_inferences < 1:
            raise ValueError("serving_inferences must be >= 1")
        if self.compute_latency < 0:
            raise ValueError("compute_latency must be >= 0")
        from repro.core.wire import PROTECTION_BITS
        if self.fault_protect not in PROTECTION_BITS:
            raise ValueError(f"fault_protect must be one of "
                             f"{sorted(PROTECTION_BITS)}, "
                             f"got {self.fault_protect!r}")
        if any(not 0.0 <= r <= 1.0 for r in self.fault_rates):
            raise ValueError("fault_rates must lie in [0, 1] "
                             f"(got {tuple(self.fault_rates)})")
        if self.fault_max_retries < 0:
            raise ValueError("fault_max_retries must be >= 0")
        if self.fault_ack_latency < 1:
            raise ValueError("fault_ack_latency must be >= 1")
        if self.deadline is not None and self.deadline <= 0:
            raise ValueError("deadline must be > 0 cycles when set")
        if self.admit_queue_depth is not None and self.admit_queue_depth < 1:
            raise ValueError("admit_queue_depth must be >= 1 when set")

    def variant_axes(self):
        """The per-shape-class variant list, in batch order."""
        return [(prec, tb, tr) for prec in self.precisions
                for tb in self.tiebreaks for tr in self.transforms]


@dataclasses.dataclass
class SweepReport:
    rows: List[dict]
    stats: dict

    def row(self, **match) -> dict:
        hits = [r for r in self.rows
                if all(r[k] == v for k, v in match.items())]
        if len(hits) != 1:
            raise KeyError(f"{len(hits)} rows match {match}")
        return hits[0]


def recovery_overhead_bits(layers: Sequence[LayerTraffic],
                           transform: WireTransform,
                           max_packets_per_layer: Optional[int] = None,
                           paired: bool = True) -> int:
    """Total recovery-index bits a transform must transmit for ``layers``.

    Separated ordering (O2/O3) needs a minimal-bit-width index per (input,
    weight) pair to re-affiliate the streams (paper Sec. IV-C1); the
    ordering window is the packet payload, so the index addresses one of
    ``k`` in-packet positions. O0 and (on the paired request phase) O1
    report zero. ``paired=False`` charges the *single-stream* contract
    instead - element order itself must be restorable, so every
    non-identity reorder (O1 included) owes the index.
    """
    total = 0
    for layer in layers:
        n, k = int(layer.inputs.shape[0]), int(layer.inputs.shape[1])
        if max_packets_per_layer is not None and n > max_packets_per_layer:
            n = max_packets_per_layer
        window = transform.window if transform.window is not None else k
        total += n * k * transform.overhead_bits_per_value(min(window, k),
                                                           paired=paired)
    return total


def cached_ordered_payloads(cache: Dict[tuple, list], model: str,
                            layers: Sequence[LayerTraffic], lanes: int,
                            variants, axes,
                            max_packets_per_layer: Optional[int],
                            timings: Optional[Dict[str, float]] = None,
                            compression: str = "none") -> list:
    """Ordered payloads for ``variants``, cached per (model, lanes,
    transform, precision, compression).

    The transform value is the frozen ``WireTransform`` dataclass, so the
    key carries the ordering name, window, tiebreak, and beam/starts
    settings - distinct tiebreaks or precisions can never collide on an
    entry, while every sweep cell that shares a variant (all meshes, MC
    placements, and packet->MC affinities of one model) reuses one ordering
    pass. Returns the per-layer ``(B, n, F, L)`` stacks in variant order,
    bit-identical to an uncached :func:`repro.noc.traffic.ordered_payloads`
    call over the full variant list.

    ``timings`` (transform name -> seconds, accumulated in place) charges
    each cache *miss* to its ordering - the per-transform packetization
    breakdown the bench trajectory records, so an O3 chain regression is
    attributable against the cheap O0-O2 permutes.
    """
    stacks = []
    for (tr, q), (prec, _, _) in zip(variants, axes):
        key = (model, lanes, tr, prec, compression)
        if key not in cache:
            t0 = time.perf_counter()
            cache[key] = ordered_payloads(
                layers, lanes, [(tr, q)],
                max_packets_per_layer=max_packets_per_layer,
                compression=compression)
            if timings is not None:
                timings[tr.name] = (timings.get(tr.name, 0.0)
                                    + time.perf_counter() - t0)
        stacks.append(cache[key])
    return [np.concatenate([s[li] for s in stacks])
            for li in range(len(stacks[0]))]


def _resolve_mesh(mesh: Mesh) -> tuple:
    if isinstance(mesh, NocConfig):
        return (f"{mesh.rows}x{mesh.cols}_mc{mesh.num_mcs}", mesh)
    return (mesh, mesh_by_name(mesh))


def _place(cfg: NocConfig, placement: str) -> NocConfig:
    """Apply a placement strategy to a resolved mesh.

    ``edge`` keeps the resolved mc_nodes untouched - named meshes already
    use the evenly-spread boundary placement, and an explicit NocConfig's
    hand-picked nodes stay authoritative. Other strategies re-place the
    same MC count.
    """
    if placement == "edge":
        return cfg
    return dataclasses.replace(
        cfg, mc_nodes=mc_placement(cfg.rows, cfg.cols, cfg.num_mcs,
                                   placement))


def drain_estimate(cfg: NocConfig, lengths: np.ndarray) -> float:
    """Cheap lower-bound drain estimate for one (config, stream set) cell.

    The drain cannot beat the injection bound (each MC injects at most one
    flit per cycle, so the longest stream is a floor) nor the hottest-link
    bound (one flit per link per cycle, with per-link loads walked along
    every MC->PE X-Y path in :func:`repro.noc.topology.xy_link_loads`).
    The link bound is what separates boundary MC placements - whose few
    escape links carry everything - from interleaved ones with identical
    injection bounds; on the recorded 16x16 DarkNet run it ranks edge
    (~181k-cycle drain) far above interleaved (~82k). Used only to *order*
    lanes so device-sharded batches stay balanced and slow lanes retire
    last; it carries no correctness weight.
    """
    lengths = np.asarray(lengths, float)[:cfg.num_mcs]
    inj = float(lengths.max()) if lengths.size else 0.0
    link = float(xy_link_loads(cfg, lengths).max()) if lengths.size else 0.0
    return max(inj, link)


def _deal_order(ests: np.ndarray, ndev: int) -> np.ndarray:
    """Lane permutation dealing estimate-sorted lanes round-robin across
    ``ndev`` contiguous device shards; identity when there is nothing to
    balance (one device or uniform estimates)."""
    if ndev <= 1 or np.unique(ests).size <= 1:
        return np.arange(ests.size)
    order = np.argsort(-ests, kind="stable")
    return np.concatenate([order[i::ndev] for i in range(ndev)])


def _take_lanes(traffic: Traffic, idx: np.ndarray) -> Traffic:
    if np.array_equal(idx, np.arange(idx.size)):
        return traffic
    j = jnp.asarray(idx)
    return traffic._replace(
        words=traffic.words[j], dest=traffic.dest[j], meta=traffic.meta[j],
        vc=traffic.vc[j], pkt=traffic.pkt[j], length=traffic.length[j])


def _concat_lanes(parts: Sequence[Traffic]) -> Traffic:
    if len(parts) == 1:
        return parts[0]
    cat = lambda f: jnp.concatenate([getattr(p, f) for p in parts])  # noqa: E731
    # The conservation ledger is sized from num_packets and must cover every
    # lane: result-phase parts legitimately differ in packet count (the
    # (PE, MC) grouping depends on placement/affinity), so take the max -
    # unknown (-1) in any part poisons the metadata.
    counts = [int(p.num_packets) for p in parts]
    return Traffic(words=cat("words"), dest=cat("dest"), meta=cat("meta"),
                   vc=cat("vc"), pkt=cat("pkt"), length=cat("length"),
                   num_packets=-1 if min(counts) < 0 else max(counts))


def _resolve_devices(devices):
    """``"auto"`` -> every local device when there are >1, else None."""
    if isinstance(devices, str):
        if devices != "auto":
            raise ValueError(f"devices must be 'auto', None, or a device "
                             f"sequence, got {devices!r}")
        local = jax.local_devices()
        return local if len(local) > 1 else None
    return devices


def run_sweep(grid: SweepGrid, layers_for_model: LayersFn, *,
              out_path: Optional[str] = None,
              check_conservation: bool = False,
              devices="auto") -> SweepReport:
    """Execute every cell of ``grid``; one packetization per (mesh,
    placement, affinity, model) cell and ONE batched, drain-aware request
    simulation per (mesh, model): all placement x affinity combinations
    ride the same call as extra variant lanes (per-lane ``mc_nodes``),
    ordered by :func:`drain_estimate` so device shards stay balanced, and
    lanes retire as they drain instead of idle-stepping until the most
    congested placement finishes. With ``grid.result_phase`` the PE->MC
    result traffic of every cell drains in one further batched simulation
    per (mesh, model) - an independent second phase whose per-row stats
    merge into the request row (see DESIGN.md "Result phase").

    layers_for_model: model name -> LayerTraffic sequence (the sweep engine
        stays decoupled from how weights are trained or loaded).
    devices: forwarded to :func:`repro.noc.sim.simulate_batch` - the
        default ``"auto"`` shards the variants axis across all local
        devices on multi-device hosts and falls back to the single-device
        vmapped drain otherwise (per-variant results are bit-identical
        either way).
    """
    axes = grid.variant_axes()
    variants = [(by_name(tr, tiebreak=tb), _QUANTIZERS[prec])
                for prec, tb, tr in axes]
    devs = _resolve_devices(devices)
    streamed = grid.max_packets_per_layer is None
    rows: List[dict] = []
    classes = []
    pack_s = sim_s = res_pack_s = res_s = 0.0
    pack_by_tr: Dict[str, float] = {}   # ordering seconds per transform
    stepped_cycles = 0          # request cycle-steps across all variants
    result_cycles = 0           # result-phase cycle-steps
    # Result values depend only on (model, variants) - computed once and
    # reused across every mesh/placement/affinity cell.
    rvalue_cache: Dict[str, list] = {}
    layer_cache: Dict[str, Sequence[LayerTraffic]] = {}
    # Ordered payload words are mesh-independent (the transform sees only
    # packet payloads and the flit width), so every mesh/MC-count cell of a
    # model reuses one ordering pass; entries are keyed per (model, lanes,
    # transform, precision) - see :func:`cached_ordered_payloads` - so
    # grids whose variant lists overlap (and the result phase below) share
    # orderings at variant granularity, not just whole-list granularity.
    # The streamed path deliberately skips this cache - holding every
    # layer's full payload tensor is exactly what it exists to avoid - but
    # still orders once per (mesh, model): one streamed pass feeds every
    # placement x affinity assembler (``build_traffic_streamed_multi``).
    ordered_cache: Dict[tuple, list] = {}
    payload_cache: Dict[tuple, list] = {}
    shape_cache: Dict[tuple, list] = {}
    # Autotuned drain schedule per shape class (``noc.tune`` winners);
    # meshes missing from the table keep the grid's pinned constants.
    if grid.tune_path:
        from .tune import load_tuned, schedule_for
        tuned = load_tuned(grid.tune_path)
        drain_sched = lambda cfg: (  # noqa: E731
            (s.chunk, s.compact_ratio)
            if (s := schedule_for(cfg, tuned)) else (grid.chunk, 0.5))
    else:
        drain_sched = lambda cfg: (grid.chunk, 0.5)  # noqa: E731
    # MC placements of one mesh size share a compiled simulator when their
    # traffic shapes match; pad every member of a size group to the group's
    # max MC-stream count and max stream length. Placement never changes
    # the MC count, so the placement axis rides inside each size group.
    resolved = [_resolve_mesh(m) for m in grid.meshes]
    size_groups: Dict[tuple, List[NocConfig]] = {}
    for _, cfg in resolved:
        key = (cfg.rows, cfg.cols, cfg.num_vcs, cfg.vc_depth, cfg.lanes)
        size_groups.setdefault(key, []).append(cfg)

    # Escape-metadata bits per (model, precision, lanes, compression) and
    # result-stream outlier counts per (model, precision): analytic,
    # value-only quantities shared by every mesh/placement/affinity cell.
    comp_cache: Dict[tuple, int] = {}
    routlier_cache: Dict[tuple, int] = {}
    nv = len(variants)
    ndev = len(devs) if devs else 1
    for mesh_name, base_cfg in resolved:
        # Compression joins as an extra shape class per (mesh, model): MSR
        # shrinks per-packet flit counts, so none/msr cells can never share
        # a packetization skeleton or a compiled drain lane.
        for model, comp in [(m, c) for m in grid.models
                            for c in grid.compression]:
            if model not in layer_cache:
                layer_cache[model] = layers_for_model(model)
            layers = layer_cache[model]

            t0 = time.perf_counter()
            pkey = (model, base_cfg.lanes, comp)
            if pkey not in shape_cache:
                if streamed:
                    # One single-packet geometry probe per model; the
                    # payloads themselves never materialize whole.
                    shape_cache[pkey] = payload_shapes(
                        layers, base_cfg.lanes, variants,
                        max_packets_per_layer=grid.max_packets_per_layer,
                        compression=comp)
                else:
                    # The one-shot path reads the geometry off the
                    # payload arrays it needs anyway - probing all
                    # variants again would double the transform work.
                    payload_cache[pkey] = cached_ordered_payloads(
                        ordered_cache, model, layers, base_cfg.lanes,
                        variants, axes,
                        max_packets_per_layer=grid.max_packets_per_layer,
                        timings=pack_by_tr, compression=comp)
                    shape_cache[pkey] = [(w.shape[1], w.shape[2])
                                         for w in payload_cache[pkey]]
            group = size_groups[(base_cfg.rows, base_cfg.cols,
                                 base_cfg.num_vcs, base_cfg.vc_depth,
                                 base_cfg.lanes)]
            shapes = shape_cache[pkey]
            npackets = sum(n for n, _ in shapes)
            mc_pad = max(c.num_mcs for c in group)

            # Every (MC placement x packet->MC affinity) combination of
            # this (mesh, model) drains in ONE batched call: combos share
            # the traffic shapes (padded below) and differ only in their
            # per-lane mc_nodes / per-MC stream split, so the drain
            # scheduler can retire fast lanes while congested ones keep
            # stepping.
            placed = [(pl, aff, _place(base_cfg, pl))
                      for pl in grid.placements for aff in grid.affinity]
            tables = [affinity_mc_table(cfg) if aff == "nearest" else None
                      for _, aff, cfg in placed]
            lens = [stream_lengths(shapes, cfg.num_mcs, tbl)
                    for (_, _, cfg), tbl in zip(placed, tables)]
            # Affinity skews the per-MC split, so the common stream length
            # covers every placement x affinity combo of every member of
            # the size group - same-size meshes keep sharing one compiled
            # drain under the new axis. The base config's combos are
            # already in `lens`; only other group members recompute.
            t_pad = max(
                [int(ln.max()) for ln in lens]
                + [int(stream_lengths(
                    shapes, gcfg.num_mcs,
                    affinity_mc_table(gcfg) if aff == "nearest" else None
                   ).max())
                   for c in group if c is not base_cfg
                   for pl in grid.placements
                   for aff in grid.affinity
                   for gcfg in (_place(c, pl),)])
            if streamed:
                # ONE ordering pass for every placement x affinity combo:
                # the transform output is mesh-independent, so each chunk
                # is ordered once and scattered into all combo layouts.
                combo_traffics = build_traffic_streamed_multi(
                    layers, [cfg for _, _, cfg in placed], variants,
                    chunk_packets=grid.stream_chunk_packets,
                    num_streams=mc_pad, shapes=shapes, mc_tables=tables,
                    compression=comp)
            else:
                combo_traffics = [
                    assemble_traffic(payload_cache[pkey], cfg,
                                     num_streams=mc_pad, num_variants=nv,
                                     mc_table=tbl)
                    for (_, _, cfg), tbl in zip(placed, tables)]
            parts = [pad_traffic_length(t, t_pad) for t in combo_traffics]
            del combo_traffics
            traffic = _concat_lanes(parts)
            del parts
            mc_rows = np.stack(
                [np.asarray(tuple(cfg.mc_nodes) + (0,) * (mc_pad - cfg.num_mcs),
                            np.int32)
                 for _, _, cfg in placed for _ in range(nv)])
            # Drain-aware lane order: deal estimate-sorted lanes across the
            # device shards so no device ends up with only congested lanes.
            ests = np.asarray([drain_estimate(cfg, ln)
                               for (_, _, cfg), ln in zip(placed, lens)
                               for _ in range(nv)])
            order = _deal_order(ests, ndev)
            inv = np.empty_like(order)
            inv[order] = np.arange(order.size)
            t1 = time.perf_counter()
            d_chunk, d_ratio = drain_sched(placed[0][2])
            res_perm: List[SimResult] = simulate_batch(
                placed[0][2], _take_lanes(traffic, order),
                mc_nodes=mc_rows[order],
                count_headers=grid.count_headers,
                chunk=d_chunk, max_cycles=grid.max_cycles,
                check_conservation=check_conservation, devices=devs,
                backend=grid.backend, compact_ratio=d_ratio)
            results = [res_perm[inv[i]] for i in range(len(order))]
            t2 = time.perf_counter()

            # Result phase: one independent PE->MC drain per (mesh, model)
            # covering every combo's lanes. Streams inject at the PEs
            # (per-lane mc_nodes = pe_nodes) and eject at the MCs. Stream
            # *counts* are padded across the size group; the stream-length
            # axis is padded only across this cell's combos (other group
            # members' result lengths aren't known without building their
            # traffic), so result drains compile once per (mesh, model)
            # rather than once per size group.
            rres: Optional[List[SimResult]] = None
            t2b = t2
            if grid.result_phase:
                if model not in rvalue_cache:
                    rvalue_cache[model] = result_values(
                        layers, variants,
                        max_packets_per_layer=grid.max_packets_per_layer)
                pe_pad = max(c.num_routers - c.num_mcs for c in group)
                rparts = []
                for (_, _, cfg), tbl in zip(placed, tables):
                    rparts.append(build_result_traffic(
                        layers, cfg, variants,
                        max_packets_per_layer=grid.max_packets_per_layer,
                        mc_table=tbl, result_window=grid.result_window,
                        num_streams=pe_pad, values=rvalue_cache[model],
                        compression=comp))
                rnpkts = [int(p.num_packets) for p in rparts]
                rt_pad = max(int(p.words.shape[-2]) for p in rparts)
                # Injection-bound estimate per combo (the longest PE
                # stream floors the drain), dealt across device shards
                # like the request lanes so no shard holds only the
                # congested combos.
                rests = np.asarray([int(np.asarray(p.length).max())
                                    if p.length.size else 0
                                    for p in rparts for _ in range(nv)])
                rtraffic = _concat_lanes(
                    [pad_traffic_length(p, rt_pad) for p in rparts])
                del rparts
                pe_rows = np.stack(
                    [np.asarray(tuple(cfg.pe_nodes)
                                + (0,) * (pe_pad - len(cfg.pe_nodes)),
                                np.int32)
                     for _, _, cfg in placed for _ in range(nv)])
                rorder = _deal_order(rests, ndev)
                rinv = np.empty_like(rorder)
                rinv[rorder] = np.arange(rorder.size)
                t2b = time.perf_counter()
                rres_perm = simulate_batch(
                    placed[0][2], _take_lanes(rtraffic, rorder),
                    mc_nodes=pe_rows[rorder],
                    count_headers=grid.count_headers,
                    chunk=d_chunk, max_cycles=grid.max_cycles,
                    check_conservation=check_conservation, devices=devs,
                    backend=grid.backend, compact_ratio=d_ratio)
                rres = [rres_perm[rinv[i]] for i in range(len(rorder))]
            t3 = time.perf_counter()

            pack_s += t1 - t0
            sim_s += t2 - t1
            res_pack_s += t2b - t2
            res_s += t3 - t2b
            class_cycles = sum(r.cycles for r in results)
            stepped_cycles += class_cycles
            entry = {
                "mesh": mesh_name, "placements": list(grid.placements),
                "affinity": list(grid.affinity),
                "model": model, "compression": comp,
                "variants": len(results),
                "packetize_s": round(t1 - t0, 4),
                "simulate_s": round(t2 - t1, 4),
                "cycles_per_sec": round(class_cycles / (t2 - t1), 1)
                if t2 > t1 else None,
            }
            if rres is not None:
                rc = sum(r.cycles for r in rres)
                result_cycles += rc
                entry["result_packetize_s"] = round(t2b - t2, 4)
                entry["result_simulate_s"] = round(t3 - t2b, 4)
                entry["result_cycles_per_sec"] = (
                    round(rc / (t3 - t2b), 1) if t3 > t2b else None)
            classes.append(entry)

            for pi, (placement, aff, cfg) in enumerate(placed):
                cell = results[pi * nv:(pi + 1) * nv]
                rcell = rres[pi * nv:(pi + 1) * nv] if rres else [None] * nv
                mean_hops = packet_mean_hops(cfg, npackets, tables[pi])
                base_bt = {}
                base_rbt = {}
                for (prec, tb, tr), res, rr in zip(axes, cell, rcell):
                    if tr == grid.baseline:
                        base_bt[(prec, tb)] = res.total_bt
                        base_rbt[(prec, tb)] = rr.total_bt if rr else None
                rw = (grid.result_window if grid.result_window is not None
                      else DEFAULT_RESULT_WINDOW)
                for (prec, tb, tr), (transform, _), res, rr in zip(
                        axes, variants, cell, rcell):
                    overhead = recovery_overhead_bits(
                        layers, transform,
                        max_packets_per_layer=grid.max_packets_per_layer)
                    # MSR escape metadata (per-window outlier count + per-
                    # outlier position and top bits). Whether a value is an
                    # outlier is a property of the value, not of its
                    # position, so the bit budget is transform-independent:
                    # one analytic pass per (model, precision, lanes).
                    ckey = (model, prec, base_cfg.lanes, comp)
                    if ckey not in comp_cache:
                        comp_cache[ckey] = compression_overhead(
                            layers, _QUANTIZERS[prec], base_cfg.lanes, comp,
                            max_packets_per_layer=grid.max_packets_per_layer)
                    comp_overhead = comp_cache[ckey]
                    # Charge each recovery-index bit half a transition (the
                    # toggle expectation of an uninformative bit stream): the
                    # index rides the same links as the payload, so an honest
                    # reduction figure must pay for it (paper Sec. IV-C1).
                    # MSR escape bits get the identical price - same links,
                    # same uninformative-stream toggle expectation.
                    adjusted_bt = res.total_bt + overhead // 2 + comp_overhead // 2
                    base = base_bt[(prec, tb)]
                    if rr:
                        # The result phase is a *single* stream: any
                        # non-identity reorder (O1 included) owes a window
                        # index per value to restore element order. One
                        # result value per request packet.
                        roverhead = npackets * transform.overhead_bits_per_value(
                            min(rw, npackets), paired=False)
                        rcomp = 0
                        if comp == "msr":
                            rokey = (model, prec)
                            if rokey not in routlier_cache:
                                vi = axes.index((prec, tb, tr))
                                routlier_cache[rokey] = int(sum(
                                    int(msr.outlier_mask(lay[vi]).sum())
                                    for lay in rvalue_cache[model]))
                            # Result packets pad to lane-rounded slots, so
                            # the escape window is the padded slot count.
                            rslots = -(-rw // base_cfg.lanes) * base_cfg.lanes
                            rcomp = msr.msr_stream_overhead_bits(
                                rslots, rnpkts[pi], routlier_cache[rokey])
                        radj = rr.total_bt + roverhead // 2 + rcomp // 2
                        rbase = base_rbt[(prec, tb)]
                    rows.append({
                        "mesh": mesh_name, "placement": placement,
                        "affinity": aff, "model": model, "precision": prec,
                        "transform": tr, "tiebreak": tb,
                        "compression": comp,
                        "total_bt": res.total_bt,
                        "adjusted_bt": adjusted_bt,
                        "overhead_bits": overhead,
                        "compression_overhead_bits": comp_overhead,
                        "cycles": res.drain_cycle,
                        "flits": res.injected,
                        "bt_per_flit": res.bt_per_flit,
                        "mean_hops": mean_hops,
                        "reduction_pct": (1 - res.total_bt / base) * 100,
                        "adjusted_reduction_pct": (1 - adjusted_bt / base) * 100,
                        "result_bt": rr.total_bt if rr else None,
                        "result_cycles": rr.drain_cycle if rr else None,
                        "result_flits": rr.injected if rr else None,
                        "result_overhead_bits": roverhead if rr else None,
                        "result_compression_overhead_bits":
                            rcomp if rr else None,
                        "result_adjusted_bt": radj if rr else None,
                        "result_adjusted_reduction_pct": (
                            (1 - radj / rbase) * 100 if rr else None),
                    })

    wall = pack_s + sim_s + res_pack_s + res_s
    stats = {
        "cells": len(rows),
        "shape_classes": classes,
        "packetize_s": round(pack_s, 4),
        # Ordering seconds attributed per transform (one-shot payload-cache
        # misses only; assembler/simulated time excluded) - lets an O3
        # chain regression show up against the cheap O0-O2 permutes.
        "packetize_by_transform": {k: round(v, 4)
                                   for k, v in sorted(pack_by_tr.items())},
        "simulate_s": round(sim_s, 4),
        "wall_s": round(wall, 4),
        "stepped_cycles": stepped_cycles,
        "cycles_per_sec": round(stepped_cycles / sim_s, 1) if sim_s else None,
        "streamed": streamed,
        "devices": len(devs) if devs else 1,
        "result_phase": grid.result_phase,
    }
    if grid.result_phase:
        stats["result_packetize_s"] = round(res_pack_s, 4)
        stats["result_simulate_s"] = round(res_s, 4)
        stats["result_cycles"] = result_cycles
        stats["result_cycles_per_sec"] = (
            round(result_cycles / res_s, 1) if res_s else None)
    report = SweepReport(rows=rows, stats=stats)
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump({"grid": _grid_json(grid), "rows": rows,
                       "stats": stats}, f, indent=1)
    return report


def _grid_json(grid: SweepGrid) -> dict:
    out = dataclasses.asdict(grid)
    out["meshes"] = [_resolve_mesh(m)[0] for m in grid.meshes]
    for key in ("placements", "affinity", "transforms", "tiebreaks",
                "precisions", "models", "compression", "offered_loads"):
        out[key] = list(out[key])
    return out


def run_serving(grid: SweepGrid, layers_for_model: LayersFn, *,
                out_path: Optional[str] = None,
                check_conservation: bool = False,
                devices="auto") -> SweepReport:
    """The closed-loop ``serving`` suite: the BT sweep joined with an
    offered-load latency sweep.

    Runs :func:`run_sweep` (result phase forced on - serving is
    bidirectional by definition) for the per-transform BT rows, then one
    gated closed-loop drain (:func:`repro.noc.online.simulate_online`) per
    (mesh, placement, affinity, model) combo and offered-load point, plus
    a back-to-back saturation probe per combo. Timing is
    transform-independent (drain dynamics never read payload values), so
    the load axis is priced once per combo and the latency/BT frontier is
    the cross product: a transform moves a combo's BT coordinate, a load
    point its latency coordinate.

    The returned report carries the BT rows unchanged; ``stats["serving"]``
    adds ``points`` (one entry per combo x load x fault rate: p50/p99/mean
    latency, measured throughput, completed/truncated counts, gated drain
    cycles; with the degradation axes on, also fault_rate/slo_attainment/
    goodput/shed/failed),
    ``combos`` (per-combo ``saturation_tput``, ``latency_monotone`` - p50
    non-decreasing along the sorted load axis at the lowest fault rate,
    restricted to load points where the admission controller shed
    nothing (shedding caps queueing, so p50 plateaus by design) - and
    the per-transform BT join ``transforms[tr] = {request_bt, result_bt,
    adjusted_bt, ...}`` at the grid's first precision/tiebreak), and the
    serving wall-clock.
    """
    from .online import ArrivalProcess, latency_percentiles, simulate_online

    if not grid.offered_loads:
        raise ValueError("run_serving needs grid.offered_loads (offered "
                         "load points in inferences per 1000 cycles)")
    if grid.max_packets_per_layer is None:
        raise ValueError("run_serving uses the one-shot packetizer; set "
                         "max_packets_per_layer")
    if set(grid.compression) != {"none"}:
        raise ValueError(
            "run_serving prices drain timing once per combo on the O0 "
            "baseline packetization; the compression axis changes flit "
            "geometry per scheme, so serving grids must keep "
            "compression=('none',) (BT-only compression rows come from "
            "run_sweep)")
    base = (grid if grid.result_phase
            else dataclasses.replace(grid, result_phase=True))
    report = run_sweep(base, layers_for_model,
                       check_conservation=check_conservation,
                       devices=devices)

    t0 = time.perf_counter()
    o0 = [(by_name(grid.baseline), _QUANTIZERS[grid.precisions[0]])]
    prec0, tb0 = grid.precisions[0], grid.tiebreaks[0]
    loads = sorted(grid.offered_loads)
    frates = sorted(set(grid.fault_rates))
    fault_axis = bool(frates)
    if not frates:
        frates = [0.0]
    dead = tuple(tuple(int(x) for x in d) for d in grid.fault_dead_links)
    degradation = (fault_axis or bool(dead) or grid.deadline is not None
                   or grid.admit_queue_depth is not None)

    def _fault_model(rate: float):
        # Rate 0 with no dead links is the pinned clean path: faults=None
        # keeps the drain bit-identical to a grid without the fault axis.
        if rate == 0.0 and not dead:
            return None
        from .faults import FaultModel
        return FaultModel(rate=rate, seed=grid.fault_seed,
                          protect=grid.fault_protect, dead_links=dead,
                          max_retries=grid.fault_max_retries,
                          ack_latency=grid.fault_ack_latency)
    points: List[dict] = []
    combos: List[dict] = []
    layer_cache: Dict[str, Sequence[LayerTraffic]] = {}
    for mesh_name, base_cfg in [_resolve_mesh(m) for m in grid.meshes]:
        for model in grid.models:
            if model not in layer_cache:
                layer_cache[model] = layers_for_model(model)
            layers = layer_cache[model]
            for pl in grid.placements:
                for aff in grid.affinity:
                    cfg = _place(base_cfg, pl)
                    tbl = (affinity_mc_table(cfg) if aff == "nearest"
                           else None)
                    req = build_traffic_batch(
                        layers, cfg, o0,
                        max_packets_per_layer=grid.max_packets_per_layer,
                        mc_table=tbl).variant(0)
                    res = build_result_traffic(
                        layers, cfg, o0,
                        max_packets_per_layer=grid.max_packets_per_layer,
                        mc_table=tbl,
                        result_window=grid.result_window).variant(0)
                    combo_key = {"mesh": mesh_name, "placement": pl,
                                 "affinity": aff, "model": model}
                    combo_p50 = []
                    slo_by_load: Dict[float, List] = {}
                    for load in loads:
                        for rate in frates:
                            onl = simulate_online(
                                cfg, req, res,
                                arrivals=ArrivalProcess(grid.arrival, load,
                                                        grid.arrival_seed),
                                num_inferences=grid.serving_inferences,
                                compute_latency=grid.compute_latency,
                                count_headers=grid.count_headers,
                                chunk=grid.chunk,
                                max_cycles=grid.max_cycles,
                                check_conservation=check_conservation,
                                record_bt=False,
                                faults=_fault_model(rate),
                                deadline=grid.deadline,
                                admit_queue_depth=grid.admit_queue_depth)
                            lp = latency_percentiles(onl.latencies)
                            # p50 is only guaranteed non-decreasing in
                            # offered load while every inference is
                            # admitted: once the admission controller
                            # sheds, queueing is capped and p50 plateaus
                            # by design, so those points are excluded
                            # from the monotonicity verdict.
                            if rate == frates[0] and not onl.num_shed:
                                combo_p50.append(lp["p50"])
                            point = {
                                **combo_key, "offered_load": load,
                                "throughput": onl.throughput,
                                "p50_latency": lp["p50"],
                                "p99_latency": lp["p99"],
                                "mean_latency": lp["mean"],
                                "completed": lp["count"],
                                "truncated": lp["truncated"],
                                "request_drain_cycle":
                                    onl.request_drain_cycle,
                                "result_drain_cycle":
                                    onl.result_drain_cycle,
                            }
                            if degradation:
                                point.update({
                                    "fault_rate": rate,
                                    "deadline": grid.deadline,
                                    "slo_attainment": onl.slo_attainment,
                                    "goodput": onl.goodput,
                                    "shed": onl.num_shed,
                                    "failed": onl.num_failed,
                                })
                                slo_by_load.setdefault(load, []).append(
                                    onl.slo_attainment)
                            points.append(point)
                    sat = simulate_online(
                        cfg, req, res,
                        arrivals=ArrivalProcess("backtoback"),
                        num_inferences=grid.serving_inferences,
                        compute_latency=grid.compute_latency,
                        count_headers=grid.count_headers,
                        chunk=grid.chunk, max_cycles=grid.max_cycles,
                        check_conservation=check_conservation,
                        record_bt=False)
                    transforms = {}
                    for tr in grid.transforms:
                        row = report.row(**combo_key, transform=tr,
                                         precision=prec0, tiebreak=tb0)
                        transforms[tr] = {
                            "request_bt": row["total_bt"],
                            "request_adjusted_bt": row["adjusted_bt"],
                            "result_bt": row["result_bt"],
                            "result_adjusted_bt": row["result_adjusted_bt"],
                            "adjusted_reduction_pct":
                                row["adjusted_reduction_pct"],
                        }
                    combo = {
                        **combo_key,
                        "saturation_tput": sat.throughput,
                        "latency_monotone": all(
                            b >= a for a, b in zip(combo_p50, combo_p50[1:])
                            if a is not None and b is not None),
                        "transforms": transforms,
                    }
                    if fault_axis and grid.deadline is not None:
                        # SLO attainment non-increasing along the sorted
                        # fault-rate axis at every load (flip schedules are
                        # nested in rate, so this holds by construction).
                        combo["slo_monotone_in_fault"] = all(
                            a >= b
                            for curve in slo_by_load.values()
                            for a, b in zip(curve, curve[1:])
                            if a is not None and b is not None)
                    combos.append(combo)
    report.stats["serving"] = {
        "offered_loads": loads,
        "inferences": grid.serving_inferences,
        "compute_latency": grid.compute_latency,
        "arrival": grid.arrival,
        "arrival_seed": grid.arrival_seed,
        "precision": prec0, "tiebreak": tb0,
        "conservation_checked": bool(check_conservation),
        "fault_rates": frates if fault_axis else [],
        "fault_protect": grid.fault_protect if degradation else None,
        "fault_dead_links": [list(d) for d in dead],
        "deadline": grid.deadline,
        "admit_queue_depth": grid.admit_queue_depth,
        "points": points,
        "combos": combos,
        "serving_s": round(time.perf_counter() - t0, 4),
    }
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump({"grid": _grid_json(grid), "rows": report.rows,
                       "stats": report.stats}, f, indent=1)
    return report
