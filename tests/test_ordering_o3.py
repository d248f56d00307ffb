"""O3 min-Hamming ordering correctness suite.

The chain kernel (``repro.kernels.min_hamming``) and its ordering wrappers
are pinned four ways: every output is a valid permutation that inverts
bit-exactly, the chain never costs more than the zeros-to-tail identity
order, the kernel matches a brute-force optimal-Hamming-path oracle on
exhaustive families of <= 6-value windows, and the flit deal preserves the
result-phase slicing contract (non-zeros confined to the leading flits).
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ordering
from repro.core.bits import unsigned_view
from repro.core.wire import by_name, measure
from repro.kernels.min_hamming import chain_cost, min_hamming_chain

rng = np.random.default_rng(11)


def _popc(x):
    return bin(int(x)).count("1")


def _path_cost(vals, order):
    return sum(_popc(int(vals[order[i]]) ^ int(vals[order[i + 1]]))
               for i in range(len(order) - 1))


def _optimal_cost(vals):
    return min(_path_cost(vals, p)
               for p in itertools.permutations(range(len(vals))))


# --- kernel-level properties ------------------------------------------------

def test_chain_is_valid_permutation():
    vals = rng.integers(0, 256, size=(40, 13)).astype(np.uint8)
    vals[rng.random(vals.shape) < 0.3] = 0
    res = min_hamming_chain(jnp.asarray(vals))
    perm = np.asarray(res.perm)
    for r in range(vals.shape[0]):
        assert sorted(perm[r].tolist()) == list(range(13))


def test_chain_zeros_to_tail():
    """Padding zeros chain last, in original order - the slicing contract."""
    vals = rng.integers(0, 200, size=(30, 9)).astype(np.uint32)
    vals[rng.random(vals.shape) < 0.4] = 0
    res = min_hamming_chain(jnp.asarray(vals))
    perm = np.asarray(res.perm)
    z = np.asarray(res.nonzeros)
    for r in range(vals.shape[0]):
        seq = vals[r][perm[r]]
        assert np.all(seq[z[r]:] == 0)
        assert np.all(seq[:z[r]] != 0)
        # tail zeros keep their original relative order (stable partition)
        tail = perm[r][z[r]:]
        assert np.all(np.diff(tail) > 0) or tail.size <= 1


def test_chain_cost_le_identity():
    """The chain never costs more than not reordering: cost <= the
    zeros-to-tail identity order (== plain identity on zero-free windows)."""
    clean = rng.integers(1, 256, size=(50, 11)).astype(np.uint8)
    res = min_hamming_chain(jnp.asarray(clean))
    for r in range(50):
        assert int(np.asarray(res.cost)[r]) <= _path_cost(
            clean[r], list(range(11)))

    dirty = clean.copy()
    dirty[rng.random(dirty.shape) < 0.35] = 0
    res = min_hamming_chain(jnp.asarray(dirty))
    for r in range(50):
        part = [i for i in range(11) if dirty[r][i] != 0] + \
            [i for i in range(11) if dirty[r][i] == 0]
        assert int(np.asarray(res.cost)[r]) <= _path_cost(dirty[r], part)


def test_chain_cost_column_matches_chain_cost_fn():
    vals = rng.integers(0, 256, size=(20, 8)).astype(np.uint8)
    res = min_hamming_chain(jnp.asarray(vals))
    assert np.array_equal(np.asarray(chain_cost(jnp.asarray(vals), res.perm)),
                          np.asarray(res.cost))


# Exhaustive families of <= 6-value windows; multi-start greedy at the
# default beam must equal the brute-force optimal path on every one.
_ORACLE_FAMILIES = {
    "w2": [t for t in itertools.product(range(1, 16), repeat=2)],
    "w3": [t for t in itertools.product(range(1, 8), repeat=3)],
    "w4": [t for t in itertools.product((1, 2, 3, 5), repeat=4)],
    "w5": [t for t in itertools.product((1, 2, 3, 5), repeat=5)],
    "w6": [t for t in itertools.product((1, 2, 3), repeat=6)],
}


@pytest.mark.parametrize("family", sorted(_ORACLE_FAMILIES))
def test_chain_matches_bruteforce_oracle(family):
    fam = _ORACLE_FAMILIES[family]
    arr = np.asarray(fam, np.uint32)
    res = min_hamming_chain(jnp.asarray(arr))
    cost = np.asarray(res.cost)
    mismatches = [(fam[i], int(cost[i]), _optimal_cost(fam[i]))
                  for i in range(len(fam))
                  if int(cost[i]) != _optimal_cost(fam[i])]
    assert not mismatches, mismatches[:5]


# --- ordering-level properties ---------------------------------------------

def test_min_hamming_perm_windows():
    """Flat chain perm: valid within each window, offsets like
    descending_perm, inverse recovers the stream bit-exactly."""
    vals = jnp.asarray(rng.integers(0, 2 ** 30, size=48).astype(np.uint32))
    perm = ordering.min_hamming_perm(vals, window=16)
    p = np.asarray(perm)
    for wstart in range(0, 48, 16):
        win = p[wstart:wstart + 16]
        assert sorted(win.tolist()) == list(range(wstart, wstart + 16))
    chained = ordering.apply_permutation(vals, perm)
    inv = ordering.inverse_permutation(perm)
    assert np.array_equal(np.asarray(chained)[np.asarray(inv)],
                          np.asarray(vals))


def test_min_hamming_order_inverse_roundtrip():
    """The dealt O3 ordering round-trips bit-identically, including
    streams that need window padding and flit (Wp) padding."""
    for n, w, lanes in [(37, 16, 4), (30, 10, 4), (12, None, 8), (5, 7, 3)]:
        vals = jnp.asarray(rng.integers(0, 255, size=n).astype(np.uint8))
        o = ordering.min_hamming_order(vals, window=w, lanes=lanes)
        inv = ordering.inverse_permutation(o.perm)
        back = np.asarray(o.values)[np.asarray(inv)]
        padded_len = back.shape[0]
        wreal = w if (w is not None and w < n) else n
        nw = -(-n // wreal)
        wp = padded_len // nw
        orig = np.zeros(nw * wreal, back.dtype)
        orig[:n] = np.asarray(unsigned_view(vals))
        assert np.array_equal(back.reshape(nw, wp)[:, :wreal].reshape(-1),
                              orig), (n, w, lanes)
        assert np.all(back.reshape(nw, wp)[:, wreal:] == 0)


def test_min_hamming_deal_confines_nonzeros():
    """The column-major deal keeps non-zeros in the first ceil(z / lanes)
    flits of each window - the contract the result packetizer slices by."""
    lanes, w = 4, 16
    vals = rng.integers(0, 250, size=64).astype(np.uint8)
    vals[rng.random(64) < 0.5] = 0
    o = ordering.min_hamming_order(jnp.asarray(vals), window=w, lanes=lanes)
    for win in np.asarray(o.values).reshape(-1, w):
        z = int((win != 0).sum())
        fr = max(-(-z // lanes), 1)
        nz_flits = np.nonzero(win.reshape(-1, lanes).any(axis=1))[0]
        assert nz_flits.size == 0 or nz_flits.max() < fr


def test_separated_and_affiliated_variants():
    ins = jnp.asarray(rng.integers(0, 256, size=32).astype(np.uint8))
    wts = jnp.asarray(rng.integers(0, 256, size=32).astype(np.uint8))
    sep = ordering.separated_min_hamming_order(ins, wts, window=16, lanes=4)
    aff = ordering.affiliated_min_hamming_order(ins, wts, window=16, lanes=4)
    # affiliated: ONE shared perm keeps pairs matched (the zero-overhead
    # claim); separated chains are independent
    assert np.array_equal(np.asarray(aff.input_perm),
                          np.asarray(aff.weight_perm))
    for po in (sep, aff):
        assert sorted(np.asarray(po.inputs).tolist()) == \
            sorted(np.asarray(unsigned_view(ins)).tolist())
        assert sorted(np.asarray(po.weights).tolist()) == \
            sorted(np.asarray(unsigned_view(wts)).tolist())
    with pytest.raises(ValueError, match="lane count"):
        ordering.min_hamming_order(ins, window=16)
    with pytest.raises(ValueError, match="equal length"):
        ordering.affiliated_min_hamming_order(ins[:8], wts, lanes=4)


# --- transform-level: O3/O3a against the paper orderings --------------------

def test_o3_transform_beats_o0_and_charges_index():
    vals = jnp.asarray(rng.integers(0, 256, size=256).astype(np.uint8))
    bt = {name: measure(by_name(name, window=64).apply_single(vals, 8))
          ["total_bt"] for name in ("O0", "O1", "O2", "O3")}
    assert bt["O3"] <= bt["O0"]
    assert bt["O3"] <= bt["O2"]
    for name, (pb, sb) in {"O0": (0, 0), "O1": (0, 6), "O2": (6, 6),
                           "O3": (6, 6), "O3a": (0, 6), "desc": (6, 6)}.items():
        tr = by_name(name, window=64)
        assert tr.overhead_bits_per_value(64, paired=True) == pb, name
        assert tr.overhead_bits_per_value(64, paired=False) == sb, name


def test_o3_paired_apply_preserves_multisets():
    ins = jnp.asarray(rng.integers(0, 256, size=128).astype(np.uint8))
    wts = jnp.asarray(rng.integers(0, 256, size=128).astype(np.uint8))
    for name in ("O3", "O3a"):
        st = by_name(name, window=32).apply(ins, wts, 8)
        words = np.asarray(st.words)
        assert sorted(words[:, :4].reshape(-1).tolist()) == \
            sorted(np.asarray(unsigned_view(ins)).tolist())
        assert sorted(words[:, 4:].reshape(-1).tolist()) == \
            sorted(np.asarray(unsigned_view(wts)).tolist())


def test_o3_kernel_validation_errors():
    vals = jnp.zeros((2, 4), jnp.uint8)
    with pytest.raises(ValueError, match="beam"):
        min_hamming_chain(vals, beam=0)
    with pytest.raises(ValueError, match="starts"):
        min_hamming_chain(vals, starts=0)
    with pytest.raises(ValueError, match="shape"):
        min_hamming_chain((jnp.zeros((2, 4), jnp.uint8),
                           jnp.zeros((2, 5), jnp.uint8)))


@pytest.mark.parametrize("name", ["O1", "O3", "O3a"])
def test_packet_fns_order_o3_on_host(name):
    """The O3 family is packetized on the host CPU (its batched chain scan
    is wrong on a TPU); the one-shot and streamed packet functions agree
    and hand back host arrays for it."""
    from repro.noc import traffic

    tr = by_name(name)
    assert tr.host_only == name.startswith("O3")
    i = jnp.asarray(rng.integers(-128, 128, (6, 24)), jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (6, 24)), jnp.int8)
    shot = traffic._packet_fn(tr, 16)(i, w)
    chunk = traffic._packet_chunk_fn(tr, 16)(i, w)
    np.testing.assert_array_equal(np.asarray(shot).astype(np.uint32),
                                  np.asarray(chunk))
    if tr.host_only:
        assert chunk.devices() == {jax.devices("cpu")[0]} and chunk.committed
