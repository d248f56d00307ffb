"""Fused-step overhaul regression suite.

Pins the fused-state router step (packed sideband lane, closed-form
routing, receiver-side pushes, optional conservation ledger, hardware
popcount recorder) bit-for-bit against the frozen PR-3 unfused step
(``repro.noc._reference.simulate_unfused``) on the full 36-cell pinned
reference grid plus one congested 8x8/MC8 batch compared state leaf by
state leaf, and the drain scheduler's variant retirement/compaction
against the plain batched drain.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bits import popcount, popcount_hw
from repro.core.wire import by_name
from repro.data import glyph_batch
from repro.models import LeNet, init_params
from repro.noc import (PAPER_NOCS, LayerTraffic, NocConfig, SweepGrid,
                       Traffic, build_traffic, build_traffic_batch,
                       make_noc, mesh_by_name, run_sweep, simulate,
                       simulate_batch)
from repro.noc.sim import (META_PAYLOAD, META_TAIL, SIDE_META_SHIFT,
                           SIDE_VC_SHIFT, _DEST_MASK, _chunk_runner,
                           _mesh_key, fuse_traffic, make_state,
                           pack_sideband)
from repro.noc import _reference
from repro.noc._reference import simulate_unfused
from repro.noc.sweep import _deal_order, drain_estimate
from repro.noc.topology import mean_hop_counts
from repro.quant import quantize_fixed8

CHUNK = 128

# The pinned 36-cell equivalence grid (matches benchmarks/fig12.PINNED):
# 3 paper meshes x 2 precisions x 2 tiebreaks x 3 orderings.
PINNED_MESHES = tuple(PAPER_NOCS)
PINNED_PRECISIONS = ("float32", "fixed8")
PINNED_TIEBREAKS = ("stable", "pattern")
PINNED_ORDERINGS = ("O0", "O1", "O2")
MAX_PACKETS = 8
# One more case of the parity test: the benchmark's 8x8/MC8 mesh with three
# vmapped lanes of hotspot traffic, so credits bind and MC injections stall
# on full local FIFOs; every SimState leaf is compared, not just results.
CONGESTED = "8x8_mc8_congested_x3"


@pytest.fixture(scope="module")
def pinned_layers():
    model = LeNet()
    params = init_params(model.specs(), jax.random.PRNGKey(1))
    x, _ = glyph_batch(jax.random.PRNGKey(7), 1)
    return model.layer_traffic(params, x[0])


def _quant(name):
    return None if name == "float32" else (lambda t: quantize_fixed8(t).values)


def test_popcount_hw_matches_swar_oracle():
    """The lax.population_count recorder path equals the SWAR circuit on
    random words (incl. all-ones/zero edge patterns) for every wire dtype
    the simulator carries."""
    key = jax.random.PRNGKey(0)
    words = jax.random.randint(key, (512,), 0, 2**31 - 1, jnp.int32)
    words = jax.lax.bitcast_convert_type(words, jnp.uint32)
    words = jnp.concatenate([words, jnp.array([0, 0xFFFFFFFF, 1 << 31],
                                              jnp.uint32)])
    assert np.array_equal(np.asarray(popcount_hw(words)),
                          np.asarray(popcount(words)))
    f32 = jax.random.normal(key, (257,), jnp.float32)
    assert np.array_equal(np.asarray(popcount_hw(f32)),
                          np.asarray(popcount(f32)))


def test_sideband_roundtrip():
    """dest/META/VC survive the packed sideband word exactly."""
    dest = jnp.arange(512, dtype=jnp.int32)
    meta = jnp.tile(jnp.array([0, META_PAYLOAD, META_PAYLOAD | META_TAIL,
                               META_TAIL], jnp.int32), 128)
    vc = jnp.tile(jnp.arange(32, dtype=jnp.int32), 16)
    side = pack_sideband(dest, meta, vc).astype(jnp.int32)
    assert np.array_equal(np.asarray(side & _DEST_MASK), np.asarray(dest))
    assert np.array_equal(np.asarray((side >> SIDE_META_SHIFT) & 3),
                          np.asarray(meta))
    assert np.array_equal(np.asarray(side >> SIDE_VC_SHIFT), np.asarray(vc))


def test_fuse_traffic_layout(pinned_layers):
    cfg = PAPER_NOCS["4x4_mc2"]
    tr = build_traffic(pinned_layers[:1], cfg, by_name("O1"),
                       max_packets_per_layer=4)
    wire = fuse_traffic(tr, track_pkt=True)
    l = cfg.lanes
    assert wire.wire.shape == tr.words.shape[:-1] + (l + 2,)
    assert np.array_equal(np.asarray(wire.wire[..., :l]),
                          np.asarray(tr.words))
    assert np.array_equal(
        np.asarray(wire.wire[..., l + 1]).astype(np.int32),
        np.asarray(tr.pkt))


def _hotspot_batch(cfg, lanes=3, packets=40, payload=4, seed=0):
    """(lanes, M, T) Traffic: every MC stream sends ``packets`` packets of
    one header and ``payload`` payload flits to a few hotspot routers, VCs
    dealt round-robin, stream lengths varied per MC."""
    rng = np.random.default_rng(seed)
    m, nr, per = cfg.num_mcs, cfg.num_routers, payload + 1
    t = packets * per
    meta = np.full(per, META_PAYLOAD, np.int32)
    meta[0], meta[-1] = 0, META_PAYLOAD | META_TAIL
    hot = [np.array([nr - 1]), np.array([0, nr - 1]), rng.choice(nr, 3)]
    dest = np.zeros((lanes, m, t), np.int32)
    vc = np.zeros((lanes, m, t), np.int32)
    pkt = np.zeros((lanes, m, t), np.int32)
    for b in range(lanes):
        for i in range(m):
            ids = i * packets + np.arange(packets)
            dest[b, i] = np.repeat(rng.choice(hot[b % 3], packets), per)
            vc[b, i] = np.repeat(ids % cfg.num_vcs, per)
            pkt[b, i] = np.repeat(ids, per)
    length = np.tile(t - per * (np.arange(m) % 3), (lanes, 1))
    words = rng.integers(0, 2**32, (lanes, m, t, cfg.lanes), dtype=np.uint32)
    return Traffic(jnp.asarray(words), jnp.asarray(dest),
                   jnp.asarray(np.tile(meta, (lanes, m, packets))),
                   jnp.asarray(vc), jnp.asarray(pkt),
                   jnp.asarray(length, jnp.int32), num_packets=m * packets)


def _assert_congested_state_parity(chunk=128, chunks=3):
    """Step the fused drain (untracked and tracked) and the unfused
    reference side by side and compare every SimState leaf at each chunk
    boundary. The phantom FIFO row is the reference's dump slot for
    masked-out scatters, so only real routers' FIFO contents compare."""
    cfg = make_noc(8, 8, 8, "interleaved")
    tr = _hotspot_batch(cfg)
    lanes, m = tr.length.shape
    nr, l, d = cfg.num_routers, cfg.lanes, cfg.vc_depth
    npkt = tr.num_packets
    mc = jnp.asarray(cfg.mc_nodes, jnp.int32)
    key = _mesh_key(cfg)
    stack = lambda st: jax.tree.map(  # noqa: E731
        lambda x: jnp.stack([x] * lanes), st)
    ref_run = _reference._unfused_chunk_runner(key, True, chunk, True)
    ref = stack(_reference.make_state(cfg, m, npkt))
    runs = {}
    for track in (False, True):
        runs[track] = (_chunk_runner(key, True, chunk, True, track, "fused"),
                       stack(make_state(cfg, m, npkt=npkt if track else 0)),
                       fuse_traffic(tr, track))
    mcb = jnp.broadcast_to(mc, (lanes, m))
    vidx = np.arange(cfg.num_vcs)[None, None, None, :, None]
    full_link = full_local = stalled = False
    for k in range(chunks):
        ref = ref_run(ref, tr, mc)
        want = jax.tree.map(np.asarray, ref._asdict())
        cnt = want["count"]
        full_link |= bool((cnt[:, :nr, :4] == d).any())
        full_local |= bool((cnt[:, list(cfg.mc_nodes), 4] == d).any())
        stalled |= bool((want["inj_ptr"] < np.minimum(
            want["cycle"][:, None], np.asarray(tr.length))).any())
        for track in (False, True):
            run, st, wire = runs[track]
            st, _ = run(st, wire, mcb)
            runs[track] = (run, st, wire)
            got = jax.tree.map(np.asarray, st._asdict())
            where = (k, track)
            for leaf in ("head", "count", "rr", "link_last", "link_bt",
                         "link_flits", "inj_ptr", "inj_last", "inj_bt",
                         "ejected", "cycle", "drained_at"):
                assert np.array_equal(got[leaf], want[leaf]), (leaf, where)
            fifo = got["fifo"][:, :nr]
            side = fifo[..., l].astype(np.int32)
            assert np.array_equal(fifo[..., :l], want["words"][:, :nr]), where
            assert np.array_equal(side & _DEST_MASK,
                                  want["dest"][:, :nr]), where
            assert np.array_equal((side >> SIDE_META_SHIFT) & 3,
                                  want["meta"][:, :nr]), where
            # a written slot carries its FIFO's VC; an unwritten one is 0
            assert (((side >> SIDE_VC_SHIFT) == vidx)
                    | (side == 0)).all(), where
            if track:
                assert np.array_equal(fifo[..., l + 1].astype(np.int32),
                                      want["pkt"][:, :nr]), where
                assert np.array_equal(got["eject_pkt"],
                                      want["eject_pkt"]), where
    assert int(want["ejected"].min()) < int(np.asarray(tr.length).sum(1).min())
    assert full_link and full_local and stalled


@pytest.mark.parametrize("mesh", PINNED_MESHES + (CONGESTED,))
def test_fused_step_bit_identical_to_unfused(pinned_layers, mesh):
    """All 36 pinned reference cells: total_bt, link_bt, AND the exact
    drain_cycle of the fused step equal the frozen PR-3 unfused step; and
    in the congested batch every state leaf equals the reference's."""
    if mesh == CONGESTED:
        _assert_congested_state_parity()
        return
    cfg = mesh_by_name(mesh)
    for prec in PINNED_PRECISIONS:
        for tb in PINNED_TIEBREAKS:
            for o in PINNED_ORDERINGS:
                tr = build_traffic(pinned_layers, cfg,
                                   by_name(o, tiebreak=tb),
                                   quantizer=_quant(prec),
                                   max_packets_per_layer=MAX_PACKETS)
                ref = simulate_unfused(cfg, tr, chunk=CHUNK)
                new = simulate(cfg, tr, chunk=CHUNK)
                cell = (mesh, prec, tb, o)
                assert new.total_bt == ref.total_bt, cell
                assert new.drain_cycle == ref.drain_cycle, cell
                assert new.ejected == ref.ejected == new.injected, cell
                assert np.array_equal(new.link_bt, ref.link_bt), cell
                assert np.array_equal(new.inj_bt, ref.inj_bt), cell


def test_fused_step_unusual_geometry(pinned_layers):
    """Parity also holds off the paper grid: non-square mesh, 3 VCs,
    narrow flits, headers excluded from the recorder."""
    cfg = NocConfig(rows=3, cols=5, mc_nodes=(0, 14), num_vcs=3, lanes=8)
    tr = build_traffic(pinned_layers, cfg, by_name("O1"),
                       max_packets_per_layer=6)
    ref = simulate_unfused(cfg, tr, chunk=64, count_headers=False)
    new = simulate(cfg, tr, chunk=64, count_headers=False)
    assert new.total_bt == ref.total_bt
    assert new.drain_cycle == ref.drain_cycle
    assert np.array_equal(new.link_bt, ref.link_bt)


def test_sideband_capacity_guard():
    with pytest.raises(ValueError, match="sideband"):
        simulate(make_noc(32, 32, 4),
                 build_traffic([LayerTraffic(jnp.ones((2, 4)),
                                             jnp.ones((2, 4)))],
                               make_noc(32, 32, 4), by_name("O0")))


def _hetero_batch(cfg, lane_packets, k=6, seed=0):
    """Batched Traffic whose lanes carry *different* packet counts (and so
    different drain times) - the retirement scheduler's target case."""
    from repro.noc.traffic import stack_traffics
    key = jax.random.PRNGKey(seed)
    singles = []
    for i, n in enumerate(lane_packets):
        ki = jax.random.fold_in(key, i)
        layer = LayerTraffic(
            jax.random.normal(ki, (n, k)),
            jax.random.normal(jax.random.fold_in(ki, 1), (n, k)) * 0.4)
        singles.append(build_traffic([layer], cfg, by_name("O0")))
    return stack_traffics(singles)


def _assert_same_results(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.total_bt == y.total_bt
        assert x.drain_cycle == y.drain_cycle
        assert x.ejected == y.ejected
        assert np.array_equal(x.link_bt, y.link_bt)
        assert np.array_equal(x.inj_bt, y.inj_bt)


def test_retirement_matches_plain_drain():
    """Heterogeneous lanes (incl. an empty one) drained with retirement +
    compaction give exactly the plain batched drain's per-variant results;
    the small chunk forces several retire/compact events."""
    cfg = NocConfig(rows=3, cols=3, mc_nodes=(0,), lanes=4)
    batch = _hetero_batch(cfg, [40, 1, 0, 13, 26, 2])
    fast = simulate_batch(cfg, batch, chunk=32, retire=True,
                          check_conservation=True)
    plain = simulate_batch(cfg, batch, chunk=32, retire=False,
                           check_conservation=True)
    _assert_same_results(fast, plain)
    # exact per-lane drain cycles really do differ across lanes
    drains = [r.drain_cycle for r in fast]
    assert len(set(drains)) > 2


def test_retirement_matches_single_drains():
    """Each retired lane's result equals its standalone simulate()."""
    cfg = NocConfig(rows=3, cols=3, mc_nodes=(0, 8), lanes=4)
    batch = _hetero_batch(cfg, [21, 3, 9], seed=3)
    fast = simulate_batch(cfg, batch, chunk=16, retire=True)
    for i in range(3):
        single = simulate(cfg, batch.variant(i), chunk=16)
        assert fast[i].total_bt == single.total_bt
        assert fast[i].drain_cycle == single.drain_cycle
        assert np.array_equal(fast[i].link_bt, single.link_bt)


def test_mc_nodes_lane_axis_matches_separate_calls(pinned_layers):
    """Per-lane mc_nodes (how the sweep engine merges MC placements into
    one drain) is bit-identical to per-placement simulate_batch calls."""
    base = make_noc(4, 4, 2, lanes=8)
    inter = make_noc(4, 4, 2, "interleaved", lanes=8)
    variants = [(by_name(o), None) for o in ("O0", "O1")]
    batch = build_traffic_batch(pinned_layers, base, variants,
                                max_packets_per_layer=6)
    merged = Traffic(*(
        (jnp.concatenate([x, x]) if getattr(x, "ndim", 0) else x)
        for x in batch))
    mc = np.stack([np.asarray(base.mc_nodes, np.int32)] * 2
                  + [np.asarray(inter.mc_nodes, np.int32)] * 2)
    got = simulate_batch(base, merged, chunk=CHUNK, mc_nodes=mc)
    want = (simulate_batch(base, batch, chunk=CHUNK)
            + simulate_batch(inter, batch, chunk=CHUNK))
    _assert_same_results(got, want)


def test_merged_placement_sweep_matches_split_sweeps(pinned_layers):
    """run_sweep's drain-aware merged-placement batching returns the same
    rows as running each placement in its own grid."""
    kw = dict(meshes=("4x4_mc2",), transforms=("O0", "O1"),
              precisions=("fixed8",), models=("lenet",),
              max_packets_per_layer=6, chunk=CHUNK)
    merged = run_sweep(SweepGrid(placements=("edge", "interleaved"), **kw),
                       lambda _n: pinned_layers)
    keep = ("mesh", "placement", "model", "precision", "transform",
            "total_bt", "cycles", "flits")
    split_rows = []
    for pl in ("edge", "interleaved"):
        split_rows += run_sweep(SweepGrid(placements=(pl,), **kw),
                                lambda _n: pinned_layers).rows
    assert [{k: r[k] for k in keep} for r in merged.rows] == \
        [{k: r[k] for k in keep} for r in split_rows]


def test_drain_estimate_orders_congested_placements():
    """The injection bound ties across placements; the link-congestion
    proxy must rank boundary MCs above interleaved MCs (matching the
    measured 16x16 DarkNet drains: edge 181k vs interleaved 82k)."""
    lengths = np.full(16, 82_000)
    edge = make_noc(16, 16, 16, "edge")
    inter = make_noc(16, 16, 16, "interleaved")
    assert drain_estimate(edge, lengths) > drain_estimate(inter, lengths)
    assert drain_estimate(inter, lengths) >= lengths.max()
    hops = mean_hop_counts(edge)
    assert hops.shape == (16,) and (hops > 0).all()


def test_deal_order_balances_devices():
    ests = np.array([10.0, 10.0, 10.0, 1.0, 1.0, 1.0])
    order = _deal_order(ests, 2)
    # each contiguous half (device shard) gets one mix of slow+fast lanes
    assert sorted(order.tolist()) == list(range(6))
    halves = [set(order[:3]), set(order[3:])]
    for h in halves:
        assert h & {0, 1, 2} and h & {3, 4, 5}
    # identity when there is nothing to balance
    assert np.array_equal(_deal_order(ests, 1), np.arange(6))
    assert np.array_equal(_deal_order(np.ones(4), 2), np.arange(4))


def test_num_packets_metadata(pinned_layers):
    cfg = PAPER_NOCS["4x4_mc2"]
    tr = build_traffic(pinned_layers, cfg, by_name("O0"),
                       max_packets_per_layer=5)
    assert tr.num_packets == int(np.asarray(tr.pkt).max()) + 1
    # hand-built Traffic falls back to the legacy host pull
    legacy = tr._replace(num_packets=-1)
    from repro.noc.sim import _npkt
    assert _npkt(legacy) == _npkt(tr)
