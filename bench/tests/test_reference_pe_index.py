"""Per-packet destinations in the plain reference: a host layer may give
each packet's PE index; without one, packet g goes to index
``g % len(pes)``, bit for bit as before."""
import numpy as np
import pytest

from conftest import TINY_CONFIG, tiny_layers, tiny_traffic

import cell
import check
import reference

NOC = TINY_CONFIG["noc"]
ROWS, COLS = NOC["rows"], NOC["cols"]
STREAM_FIELDS = ("pkt_dest", "pkt_vc", "pkt_flits", "pkt_start",
                 "stream_len", "stream_off", "flit_dest", "flit_vc")


def _grid():
    """The grid of ``test_reference_matches_the_program_on_a_tiny_cell``."""
    traffic = tiny_traffic(("O0", "O1", "O2", "O3"))
    traffic["grid"]["placements"] = ["interleaved", "edge"]
    traffic["grid"]["affinity"] = ["roundrobin", "nearest"]
    return traffic, tiny_layers(1, shapes=((20, 25), (8, 30), (5, 17)))


def _num_pes():
    return ROWS * COLS - NOC["num_mcs"]


def _with_index(host, index_of):
    """Each layer with the PE index ``index_of(g)`` of its global packet
    ids g."""
    out, g0 = [], 0
    for i, w in host:
        g = g0 + np.arange(i.shape[0])
        out.append((i, w, index_of(g)))
        g0 += i.shape[0]
    return out


def _flits(layer):
    return -(-layer[0].shape[1] // (NOC["lanes"] // 2))


def _streams(layers, placement, affinity="roundrobin"):
    """The layers' streams, with their own PE indices where they give
    them."""
    mcs = reference.mc_nodes(ROWS, COLS, NOC["num_mcs"], placement)
    pes = [x for x in range(ROWS * COLS) if x not in set(mcs)]
    pe_index = reference.pe_indices(layers, len(pes))
    shapes = [(layer[0].shape[0], _flits(layer)) for layer in layers]
    table = reference.mc_table(ROWS, COLS, mcs, pes, affinity)
    return reference.Streams(shapes, ROWS, COLS, mcs, NOC["num_vcs"],
                             NOC["lanes"], pe_index, table), pes


def test_round_robin_pe_index_gives_todays_rows_and_the_programs():
    from repro.noc import run_sweep
    traffic, (prog, host) = _grid()
    indexed = _with_index(host, lambda g: g % _num_pes())
    ref = reference.reference_rows(host, TINY_CONFIG, traffic)
    assert reference.reference_rows(indexed, TINY_CONFIG, traffic) == ref
    for placement in traffic["grid"]["placements"]:
        for affinity in traffic["grid"]["affinity"]:
            plain, _ = _streams(host, placement, affinity)
            given, _ = _streams(indexed, placement, affinity)
            for f in STREAM_FIELDS:
                np.testing.assert_array_equal(getattr(given, f),
                                              getattr(plain, f), err_msg=f)
    report = run_sweep(cell.sweep_grid(TINY_CONFIG, traffic),
                       lambda _m: prog, devices=None)
    numbers, due, wrong = check.compare([report.rows], ref)
    assert check.passed(numbers), numbers
    assert due == 16 and wrong == 0


def test_pe_index_all_zero_sends_every_flit_to_the_first_pe():
    traffic = tiny_traffic(("O0",))
    _prog, host = tiny_layers(2)
    zero = _with_index(host, np.zeros_like)
    st, pes = _streams(zero, "interleaved")
    assert (st.flit_dest == pes[0]).all() and (st.pkt_dest == pes[0]).all()
    # the header's first word is the destination
    words = st.words([np.zeros((layer[0].shape[0], _flits(layer),
                                NOC["lanes"]), np.uint32) for layer in zero])
    assert (words[st.pkt_start, 0] == pes[0]).all()
    spread = reference.reference_rows(host, TINY_CONFIG, traffic)
    hot = reference.reference_rows(zero, TINY_CONFIG, traffic)
    assert hot[0]["flits"] == spread[0]["flits"]
    assert hot[0]["cycles"] > spread[0]["cycles"]
    # nearest affinity: every packet is served by the first PE's MC
    st, pes = _streams(zero, "edge", affinity="nearest")
    assert len(set(st.stream_len.nonzero()[0])) == 1


@pytest.mark.parametrize("bad", [-1, "num_pes"])
def test_pe_index_out_of_range_raises(bad):
    traffic = tiny_traffic(("O0",))
    _prog, host = tiny_layers(2)
    value = _num_pes() if bad == "num_pes" else bad
    layers = _with_index(host, lambda g: np.where(g == 7, value, 0))
    with pytest.raises(IndexError):
        reference.reference_rows(layers, TINY_CONFIG, traffic)


def test_pe_index_of_the_wrong_length_raises():
    _prog, host = tiny_layers(2)
    layers = [(i, w, np.zeros(i.shape[0] + 1, np.int64)) for i, w in host]
    with pytest.raises(ValueError):
        reference.reference_rows(layers, TINY_CONFIG, tiny_traffic(("O0",)))
