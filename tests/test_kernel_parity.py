"""Kernel-vs-reference parity beyond the seed sweeps: every Pallas path
(popcount, bt_count, bitonic_sort, chain-select, and the router step -
interpret mode on CPU) against the repro.kernels.ref / numpy oracles
across wire dtypes (fp32, bf16, int8) and odd, padding-exercising shapes.
Pins the kernel semantics the compiled Mosaic path inherits on TPU -
except the router step, which Mosaic rejects (its gathers): it is a CPU
parity path only, and the TPU drain runs the fused step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bits import popcount as popcount_bits, unsigned_view
from repro.core.wire import by_name
from repro.data import glyph_batch
from repro.kernels import bt_boundaries, popcount, sort_windows_desc
from repro.kernels.min_hamming import (chain_select_pallas,
                                       min_hamming_chain,
                                       min_hamming_chain_reference)
from repro.kernels.ref import (bt_boundaries_ref, popcount_ref,
                               sort_windows_desc_ref)
from repro.models import LeNet, init_params
from repro.noc import PAPER_NOCS, mesh_by_name
from repro.noc._reference import simulate_unfused
from repro.noc.sim import _resolve_backend, simulate, simulate_batch
from repro.noc.traffic import build_traffic, stack_traffics
from repro.quant import quantize_fixed8

WIRE_DTYPES = ["float32", "bfloat16", "int8"]


def _rand(key, shape, dtype):
    if dtype == "float32":
        return jax.random.normal(key, shape, jnp.float32)
    if dtype == "bfloat16":
        return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
    if dtype == "int8":
        return jax.random.randint(key, shape, -128, 128,
                                  jnp.int32).astype(jnp.int8)
    raise ValueError(dtype)


@pytest.mark.parametrize("dtype", WIRE_DTYPES)
@pytest.mark.parametrize("shape", [(1,), (127,), (129,), (8, 128 + 1),
                                   (2, 3, 5, 7)])
def test_popcount_parity_odd_shapes(shape, dtype):
    """Sizes straddling the (8, 128) tile contract: padding must never leak
    into results, for every wire dtype."""
    x = _rand(jax.random.PRNGKey(sum(shape) * 31 + len(dtype)), shape, dtype)
    np.testing.assert_array_equal(np.asarray(popcount(x)),
                                  np.asarray(popcount_ref(x)))


@pytest.mark.parametrize("dtype", WIRE_DTYPES)
def test_popcount_parity_extremes(dtype):
    """All-zeros and all-ones bit patterns, the popcount range endpoints."""
    zeros = jnp.zeros((9,), jnp.int32).astype(jnp.dtype(dtype))
    assert bool(jnp.all(popcount(zeros) == 0))
    width = jnp.dtype(unsigned_view(zeros).dtype).itemsize * 8
    all_ones = jnp.zeros((9,), unsigned_view(zeros).dtype) - 1
    assert bool(jnp.all(popcount(all_ones) == width))


@pytest.mark.parametrize("dtype", WIRE_DTYPES)
@pytest.mark.parametrize("nf,lanes", [(2, 1), (3, 16), (17, 16), (9, 130)])
def test_bt_boundaries_parity_wire_dtypes(nf, lanes, dtype):
    """The BT recorder on flit streams of every wire dtype, including lane
    counts off the 128 tile and single-lane links."""
    w = _rand(jax.random.PRNGKey(nf * 1000 + lanes), (nf, lanes), dtype)
    a, b = bt_boundaries(w), bt_boundaries_ref(unsigned_view(w))
    assert a.shape == (nf - 1,)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bt_boundaries_single_flit_stream():
    """A one-flit stream has no boundaries - the empty-output path."""
    w = jnp.ones((1, 16), jnp.uint32)
    assert bt_boundaries(w).shape == (0,)


@pytest.mark.parametrize("dtype", WIRE_DTYPES)
@pytest.mark.parametrize("rows,w", [(1, 128), (3, 128), (7, 256), (9, 512)])
def test_bitonic_sort_payload_dtypes(rows, w, dtype):
    """Windowed descending sort with payloads in each wire dtype, at row
    counts that force the ROW_TILE padding path. Keys are real popcounts
    (heavy ties); bitonic nets are unstable, so parity is: exact key
    sequences + per-row (key, payload-bits) multisets."""
    key = jax.random.PRNGKey(rows * w + len(dtype))
    payload = _rand(key, (rows, w), dtype)
    keys = popcount_bits(payload)
    sk, sp = sort_windows_desc(keys, payload)
    rk, rp = sort_windows_desc_ref(keys, payload)
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(rk))
    assert sp.dtype == payload.dtype
    assert bool(jnp.all(sk[:, :-1] >= sk[:, 1:]))
    sp_bits = np.asarray(unsigned_view(sp))
    in_bits = np.asarray(unsigned_view(payload))
    ks = np.asarray(keys)
    for i in range(rows):
        got = sorted(zip(np.asarray(sk[i]).tolist(), sp_bits[i].tolist()))
        want = sorted(zip(ks[i].tolist(), in_bits[i].tolist()))
        assert got == want


@pytest.mark.parametrize("dtype", WIRE_DTYPES)
def test_bitonic_sort_matches_descending_perm_semantics(dtype):
    """The kernel's (key-sorted) output stream must produce the same BT as
    the pure-jnp ordering path - the property later perf work relies on
    when swapping one for the other."""
    from repro.core.ordering import descending_order
    vals = _rand(jax.random.PRNGKey(5), (4, 128), dtype)
    keys = popcount_bits(vals)
    sk, sv = sort_windows_desc(keys, vals)
    ordered = descending_order(vals.reshape(-1), window=128)
    np.testing.assert_array_equal(
        np.asarray(popcount_bits(sv)).reshape(-1),
        np.asarray(popcount_bits(ordered.values)))


# ---------------------------------------------------------------------------
# Router-step Pallas kernel (interpret mode on CPU)
# ---------------------------------------------------------------------------

# The pinned 36-cell equivalence grid (matches benchmarks/fig12.PINNED):
# 3 paper meshes x 2 precisions x 2 tiebreaks x 3 orderings, 8 packets.
PINNED_MESHES = tuple(PAPER_NOCS)
PINNED_CELLS = [(prec, tb, o)
                for prec in ("float32", "fixed8")
                for tb in ("stable", "pattern")
                for o in ("O0", "O1", "O2")]
MAX_PACKETS = 8
CHUNK = 128


@pytest.fixture(scope="module")
def pinned_layers():
    model = LeNet()
    params = init_params(model.specs(), jax.random.PRNGKey(1))
    x, _ = glyph_batch(jax.random.PRNGKey(7), 1)
    return model.layer_traffic(params, x[0])


def _quant(name):
    return None if name == "float32" else (lambda t: quantize_fixed8(t).values)


def _pinned_traffics(layers, cfg):
    return [build_traffic(layers, cfg, by_name(o, tiebreak=tb),
                          quantizer=_quant(prec),
                          max_packets_per_layer=MAX_PACKETS)
            for prec, tb, o in PINNED_CELLS]


@pytest.mark.parametrize("mesh", PINNED_MESHES)
def test_router_kernel_matches_fused_36_cells(pinned_layers, mesh):
    """Interpret-mode Pallas == fused step on every pinned cell: total_bt,
    link_bt, and the exact drain_cycle. One batched drain per mesh covers
    the 12 cells AND the vmap-over-pallas_call batching rule."""
    cfg = mesh_by_name(mesh)
    batch = stack_traffics(_pinned_traffics(pinned_layers, cfg))
    fused = simulate_batch(cfg, batch, chunk=CHUNK, backend="fused")
    pallas = simulate_batch(cfg, batch, chunk=CHUNK, backend="pallas")
    for cell, f, p in zip(PINNED_CELLS, fused, pallas):
        assert p.total_bt == f.total_bt, (mesh, cell)
        assert p.drain_cycle == f.drain_cycle, (mesh, cell)
        assert p.ejected == f.ejected == p.injected, (mesh, cell)
        assert np.array_equal(p.link_bt, f.link_bt), (mesh, cell)
        assert np.array_equal(p.inj_bt, f.inj_bt), (mesh, cell)


def test_router_kernel_matches_frozen_reference(pinned_layers):
    """Single-sim Pallas drains == the frozen PR-3 reference step on the
    4x4 mesh's 12 pinned cells (fused == reference on all 36 is pinned in
    tests/test_noc_step.py, closing the three-way equality)."""
    cfg = mesh_by_name(PINNED_MESHES[0])
    for cell, tr in zip(PINNED_CELLS, _pinned_traffics(pinned_layers, cfg)):
        ref = simulate_unfused(cfg, tr, chunk=CHUNK)
        new = simulate(cfg, tr, chunk=CHUNK, backend="pallas")
        assert new.total_bt == ref.total_bt, cell
        assert new.drain_cycle == ref.drain_cycle, cell
        assert np.array_equal(new.link_bt, ref.link_bt), cell


def test_router_backend_validation(monkeypatch):
    cfg = mesh_by_name("2x2_mc1")
    tr = build_traffic([], cfg, by_name("O0"))
    with pytest.raises(ValueError, match="backend"):
        simulate(cfg, tr, backend="mosaic2000")
    # One step per platform: auto is the fused step everywhere, tracked or
    # not; pallas is the CPU interpret-mode parity path.
    for track in (False, True):
        assert _resolve_backend("auto", track) == "fused"
        assert _resolve_backend("fused", track) == "fused"
    assert _resolve_backend("pallas", False) == "pallas"
    # On a TPU the kernel does not compile: auto stays fused, and an
    # explicit pallas raises the clear error before any drain starts.
    import repro.kernels.ops as ops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert _resolve_backend("auto", False) == "fused"
    with pytest.raises(ValueError, match="does not compile for TPU"):
        simulate(cfg, tr, backend="pallas")


# ---------------------------------------------------------------------------
# Batched O3 chain and its Pallas select body
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("r,w,zfrac", [(1, 1, 0.0), (3, 5, 0.3), (5, 17, 0.5),
                                       (4, 40, 0.2), (8, 2, 0.9)])
def test_batched_chain_matches_reference_oracle(r, w, zfrac):
    """The batched beam-select chain (argmin select, one scan over all
    windows) == the per-window numpy oracle, perm/cost/nonzeros exact."""
    rng = np.random.default_rng(r * 100 + w)
    u = rng.integers(0, 2**32, (r, w), dtype=np.uint32)
    u[rng.random((r, w)) < zfrac] = 0
    res = min_hamming_chain(u)
    perm, cost, z = min_hamming_chain_reference(u)
    np.testing.assert_array_equal(np.asarray(res.perm), perm)
    np.testing.assert_array_equal(np.asarray(res.cost), cost)
    np.testing.assert_array_equal(np.asarray(res.nonzeros), z)


def test_batched_chain_affiliated_matches_reference_oracle():
    """Two-plane (affiliated O3a) chains on the summed pair distance."""
    rng = np.random.default_rng(9)
    a = rng.integers(0, 2**32, (4, 9), dtype=np.uint32)
    b = rng.integers(0, 2**32, (4, 9), dtype=np.uint32)
    a[rng.random((4, 9)) < 0.3] = 0
    res = min_hamming_chain([a, b])
    perm, cost, z = min_hamming_chain_reference([a, b])
    np.testing.assert_array_equal(np.asarray(res.perm), perm)
    np.testing.assert_array_equal(np.asarray(res.cost), cost)
    np.testing.assert_array_equal(np.asarray(res.nonzeros), z)


@pytest.mark.parametrize("r,w,planes", [(1, 4, 1), (3, 17, 1), (5, 130, 2),
                                        (8, 128, 1), (2, 300, 2)])
def test_chain_select_pallas_matches_key_argsort(r, w, planes):
    """The Pallas distance+select body == stable argsort of the chain's
    selection key (keys embed the lane index, so they are pairwise
    distinct and stability is vacuous - any correct sort agrees)."""
    rng = np.random.default_rng(r * 1000 + w + planes)
    xors = [rng.integers(0, 2**32, (r, w), dtype=np.uint32)
            for _ in range(planes)]
    penalty = rng.choice(
        np.array([0, 1 << 28, 1 << 30], np.int32), (r, w)).astype(np.int32)
    dvec, order = chain_select_pallas(
        [jnp.asarray(x) for x in xors], jnp.asarray(penalty))
    want_d = sum(np.unpackbits(x.view(np.uint8), axis=-1)
                 .reshape(r, w, 32).sum(-1).astype(np.int32) for x in xors)
    np.testing.assert_array_equal(np.asarray(dvec), want_d)
    key = want_d * np.int32(w) + np.arange(w, dtype=np.int32) + penalty
    np.testing.assert_array_equal(np.asarray(order),
                                  np.argsort(key, axis=-1, kind="stable"))
