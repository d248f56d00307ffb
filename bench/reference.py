"""Plain NumPy reference for one sweep cell: packetize, drain, count BT.

It imports nothing of the program under test. From the same layer operands
(one (inputs, weights) matrix pair per layer, one row per neuron) it builds
the MC injection streams and drains them through a cycle-level model of the
mesh, and returns per lane the numbers a sweep row reports: total bit
transitions, drain cycle and flits.

The model follows the published description of the simulated NoC: X-Y
routing on a 2-D mesh, 4 virtual channels of 4 flits per input port, a
packet keeps its VC end to end, credits are read at the start of a cycle,
round-robin switch allocation per output port over the (in-port, VC) slots,
one flit per link per cycle, one flit injected per MC per cycle, and a
recorder that adds popcount(previous word XOR current word) on every link
(inter-router, ejection and MC injection links).

Routing never reads payload words, so the flit schedule of a lane depends
only on its stream geometry. The drain is therefore modelled once per
(placement, affinity) on flit identities, and each ordering lane's BT is
summed afterwards over the (previous, current) flit pairs every link saw.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

PORT_N, PORT_E, PORT_S, PORT_W, PORT_L = 0, 1, 2, 3, 4
NUM_PORTS = 5
OPPOSITE = np.array([PORT_S, PORT_W, PORT_N, PORT_E, PORT_L])

# O3 chain score encoding: a visited candidate loses to any zero-region one,
# a zero-region one to any live one.
_VISITED = 1 << 30
_ZONE = 1 << 28


# --- wire format ------------------------------------------------------------

def quantize_fixed(x: np.ndarray, bits: int = 8) -> np.ndarray:
    """Per-tensor power-of-two fixed point: ``f = (bits-1) - ceil(log2
    max|x|)`` clamped to ``[0, bits-1]``; round half to even, saturate.
    Returns the two's-complement byte of each value (uint8)."""
    x = np.asarray(x, np.float32)
    amax = max(float(np.max(np.abs(x))) if x.size else 0.0, 1e-12)
    mant, exp = np.frexp(np.float32(amax))      # amax = mant * 2**exp
    int_bits = int(exp) - 1 if mant == 0.5 else int(exp)
    frac = min(max(bits - 1 - int_bits, 0), bits - 1)
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    q = np.clip(np.round(x * np.float32(2.0 ** frac)), lo, hi)
    return q.astype(np.int8).view(np.uint8)


_POP8 = np.array([bin(i).count("1") for i in range(256)], np.uint8)


def _pop(x: np.ndarray) -> np.ndarray:
    return np.bitwise_count(x).astype(np.int64)


# --- orderings (one window = one packet's k operands) -------------------------

def _desc_perm(u: np.ndarray) -> np.ndarray:
    """Rows of ``u`` (n, k) sorted by popcount, then value, descending;
    original position breaks the remaining ties."""
    cnt = _pop(u)
    return np.lexsort((-u.astype(np.int64), -cnt), axis=-1)


def _chain(q: np.ndarray, beam: int = 2,
           starts: int = 8) -> Tuple[np.ndarray, np.ndarray]:
    """Min-Hamming chain of every window (row) of ``q`` (R, W) uint8.

    Greedy nearest neighbour from ``starts`` starting values spread over the
    descending-popcount ranks, each step choosing among the ``beam`` nearest
    candidates by (distance + distance to the nearest value left, distance,
    index). Zeros go to the tail; the cheapest chain is kept unless the
    zeros-to-tail identity order costs no more. Returns window-local
    permutations (R, W) and the count of non-zero values per window.
    """
    r, w = q.shape
    pops = _POP8[q]
    nz = pops > 0
    z = nz.sum(1)
    part = np.argsort(~nz, axis=1, kind="stable")
    q = np.take_along_axis(q, part, 1)
    cid = (_POP8[q[:, :-1] ^ q[:, 1:]].sum(1, dtype=np.int64) if w > 1
           else np.zeros(r, np.int64))
    dperm = np.argsort(-np.take_along_axis(pops, part, 1).astype(np.int16),
                       axis=1, kind="stable")
    ranks = (np.arange(starts)[None, :] * z[:, None]) // starts
    start = np.take_along_axis(dperm, ranks, 1)                  # (R, S)

    idx = np.arange(w, dtype=np.int32)
    k1, k2 = 130 * w, w
    ri = np.arange(r)[:, None]
    si = np.arange(starts)[None, :]
    # pen: the visited / zero-region penalty of every candidate, per start.
    pen = np.broadcast_to(np.where(idx[None, :] >= z[:, None], _ZONE, 0)
                          .astype(np.int32)[:, None, :], (r, starts, w)).copy()
    pen[ri, si, start] += _VISITED
    order = np.zeros((r, starts, w), np.int64)
    order[:, :, 0] = start
    cur = start
    cost = np.zeros((r, starts), np.int64)
    qs = q[:, None, :]                                           # (R, 1, W)
    big = np.int32(np.iinfo(np.int32).max)
    for i in range(1, w):
        dvec = _POP8[q[ri, cur][..., None] ^ qs]                 # (R, S, W)
        key = dvec.astype(np.int32) * k2 + idx + pen
        cands = []
        for _ in range(beam):
            c = key.argmin(2)
            cands.append(c)
            key[ri, si, c] = big
        cand = np.stack(cands, 2)                                # (R, S, B)
        d_b = np.take_along_axis(dvec, cand, 2).astype(np.int64)
        free = pen == 0                                          # unvisited, live
        d2 = _POP8[np.take_along_axis(np.broadcast_to(qs, pen.shape), cand,
                                      2)[..., None] ^ qs[:, :, None, :]]
        ok = free[:, :, None, :] & (idx != cand[..., None])
        la = np.where(ok, d2, np.uint8(255)).min(3).astype(np.int64)
        la = np.where(la == 255, 0, la)
        score = ((d_b + la) * k1 + d_b * k2 + cand
                 + np.take_along_axis(pen, cand, 2))
        nxt = np.take_along_axis(cand, score.argmin(2)[..., None], 2)[..., 0]
        pen[ri, si, nxt] += _VISITED
        cost += np.take_along_axis(dvec, nxt[..., None], 2)[..., 0]
        order[:, :, i] = nxt
        cur = nxt
    best = cost.argmin(1)
    greedy = cost[np.arange(r), best] < cid
    chain = np.where(greedy[:, None], order[np.arange(r), best], idx[None, :])
    return np.take_along_axis(part, chain, 1), z


def _deal(perm: np.ndarray, z: np.ndarray, lanes: int) -> np.ndarray:
    """Deal each chain column-major over the window's first
    ``ceil(z / lanes)`` flits; padding zeros fill the free slots in order."""
    r, wp = perm.shape
    out = np.zeros_like(perm)
    idx = np.arange(wp)
    for zr in np.unique(z):
        sel = np.flatnonzero(z == zr)
        fr = max(-(-int(zr) // lanes), 1)
        nzslot = (idx % fr) * lanes + idx // fr
        used = np.zeros(wp, bool)
        used[nzslot[:zr]] = True
        free = np.argsort(used, kind="stable")
        slot = np.where(idx < zr, nzslot, free[np.maximum(idx - zr, 0)])
        out[sel[:, None], slot[None, :]] = perm[sel]
    return out


def _min_hamming(u: np.ndarray, lanes: int) -> np.ndarray:
    """O3 order of each row of ``u`` (n, k): rows padded to a lanes multiple,
    chained, dealt. Returns the ordered values (n, ceil(k/lanes)*lanes)."""
    n, k = u.shape
    wp = -(-k // lanes) * lanes
    pad = np.zeros((n, wp), u.dtype)
    pad[:, :k] = u
    perm, z = _chain(pad)
    return np.take_along_axis(pad, _deal(perm, z, lanes), 1)


def order_packets(inp: np.ndarray, wgt: np.ndarray, transform: str,
                  half: int) -> Tuple[np.ndarray, np.ndarray]:
    """One layer's packets (n, k) of wire bytes, ordered within each packet."""
    if transform == "O0":
        return inp, wgt
    if transform == "O1":
        p = _desc_perm(wgt)
        return (np.take_along_axis(inp, p, 1), np.take_along_axis(wgt, p, 1))
    if transform == "O2":
        return (np.take_along_axis(inp, _desc_perm(inp), 1),
                np.take_along_axis(wgt, _desc_perm(wgt), 1))
    if transform == "O3":
        return _min_hamming(inp, half), _min_hamming(wgt, half)
    raise KeyError(f"the reference has no ordering {transform!r}")


def pack_paired(inp: np.ndarray, wgt: np.ndarray, lanes: int) -> np.ndarray:
    """(n, k) inputs and weights -> (n, F, lanes) flit words: inputs in the
    left half of each flit, weights in the right, zero padded."""
    half = lanes // 2
    n, k = inp.shape
    f = -(-k // half)
    out = np.zeros((n, f, lanes), np.uint32)
    pi = np.zeros((n, f * half), np.uint32)
    pw = np.zeros((n, f * half), np.uint32)
    pi[:, :k], pw[:, :k] = inp, wgt
    out[:, :, :half] = pi.reshape(n, f, half)
    out[:, :, half:] = pw.reshape(n, f, half)
    return out


# --- mesh geometry --------------------------------------------------------------

def _border(rows: int, cols: int):
    b = [(0, c) for c in range(cols)]
    b += [(r, cols - 1) for r in range(1, rows)]
    b += [(rows - 1, c) for c in range(cols - 2, -1, -1)]
    b += [(r, 0) for r in range(rows - 2, 0, -1)]
    return list(dict.fromkeys(b))


def mc_nodes(rows: int, cols: int, n: int, placement: str) -> Tuple[int, ...]:
    """Router ids of the MCs under ``edge``, ``corner`` or ``interleaved``."""
    if placement == "interleaved":
        return tuple(int(i * rows * cols / n) for i in range(n))
    border = _border(rows, cols)
    if placement == "edge":
        step = len(border) / n
        picks = [border[int(i * step)] for i in range(n)]
    elif placement == "corner":
        picks = list(dict.fromkeys([(0, 0), (rows - 1, cols - 1),
                                    (0, cols - 1), (rows - 1, 0)]))[:n]
        rest = [b for b in border if b not in set(picks)]
        if n > len(picks):
            step = len(rest) / (n - len(picks))
            picks += [rest[int(i * step)] for i in range(n - len(picks))]
    else:
        raise KeyError(f"unknown placement {placement!r}")
    return tuple(r * cols + c for r, c in picks)


def mc_table(rows: int, cols: int, mcs, pes, affinity: str):
    """Serving MC of each PE: None for round-robin over packets, else the
    hop-nearest MC, ties to the least loaded, then the lower index."""
    if affinity == "roundrobin":
        return None
    if affinity != "nearest":
        raise KeyError(f"unknown affinity {affinity!r}")
    table = np.zeros(len(pes), np.int64)
    load = np.zeros(len(mcs), np.int64)
    for i, pe in enumerate(pes):
        hops = np.array([abs(pe // cols - m // cols) + abs(pe % cols - m % cols)
                         for m in mcs])
        best = np.flatnonzero(hops == hops.min())
        table[i] = best[np.argmin(load[best])]
        load[table[i]] += 1
    return table


# --- stream assembly --------------------------------------------------------------

class Streams:
    """Per-MC injection streams of one (placement, affinity) layout.

    Packet g (numbered across layers) goes to PE ``pes[pe_index[g]]``
    (see :func:`pe_indices`), is served by MC ``g % M`` (or the affinity
    table's MC for that PE), rides VC ``(earlier packets at its MC) %
    num_vcs``, and is one header flit (words: dest, g, payload flits)
    followed by its payload flits, in packet order within each MC's stream.
    """

    def __init__(self, shapes: Sequence[Tuple[int, int]], rows: int,
                 cols: int, mcs, num_vcs: int, lanes: int,
                 pe_index: np.ndarray, table=None):
        pes = [x for x in range(rows * cols) if x not in set(mcs)]
        m = len(mcs)
        npk = [n for n, _ in shapes]
        g = np.arange(sum(npk), dtype=np.int64)
        fl = np.concatenate([np.full(n, f + 1, np.int64)
                             for n, f in shapes]) if npk else g
        mc = g % m if table is None else table[pe_index]
        # Stable sort by MC keeps packet order inside each stream.
        by_mc = np.argsort(mc, kind="stable")
        before = np.empty_like(g)
        counts = np.bincount(mc, minlength=m)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        before[by_mc] = np.arange(g.size) - np.repeat(starts, counts)
        self.m, self.lanes = m, lanes
        self.mc_nodes = np.asarray(mcs, np.int64)
        self.pkt_dest = np.asarray(pes, np.int64)[pe_index]
        self.pkt_vc = before % num_vcs
        self.pkt_flits = fl
        # Global flit numbering: stream s's flits are consecutive from
        # stream_off[s], so in (MC, packet) order a packet's first flit is
        # the running flit count of the packets before it.
        self.stream_len = np.bincount(mc, weights=fl,
                                      minlength=m).astype(np.int64)
        self.stream_off = np.concatenate(
            [[0], np.cumsum(self.stream_len)[:-1]]).astype(np.int64)
        self.pkt_start = np.empty_like(g)
        self.pkt_start[by_mc] = np.cumsum(fl[by_mc]) - fl[by_mc]
        self.total = int(self.stream_len.sum())
        flit_pkt = np.empty(self.total, np.int64)
        flit_pkt[np.repeat(self.pkt_start, fl) + _ranges(fl)] = np.repeat(g, fl)
        self.flit_dest = self.pkt_dest[flit_pkt]
        self.flit_vc = self.pkt_vc[flit_pkt]

    def words(self, payloads: Sequence[np.ndarray]) -> np.ndarray:
        """(total flits, lanes) uint32 words for one lane, from each layer's
        (n, F, lanes) payload flits."""
        out = np.zeros((self.total, self.lanes), np.uint32)
        hdr = self.pkt_start
        out[hdr, 0] = self.pkt_dest
        out[hdr, 1] = np.arange(hdr.size) & 0xFFFFFFFF
        out[hdr, 2] = self.pkt_flits - 1
        g0 = 0
        for pay in payloads:
            n, f, _ = pay.shape
            rows = (self.pkt_start[g0:g0 + n, None] + 1
                    + np.arange(f)[None, :]).reshape(-1)
            out[rows] = pay.reshape(n * f, self.lanes)
            g0 += n
        return out


def _ranges(lengths: np.ndarray) -> np.ndarray:
    """Concatenated ``arange(l)`` for every l in ``lengths``."""
    total = int(lengths.sum())
    ends = np.cumsum(lengths)
    return np.arange(total) - np.repeat(ends - lengths, lengths)


# --- the drain ----------------------------------------------------------------------

def drain_schedule(st: Streams, rows: int, cols: int, num_vcs: int = 4,
                   depth: int = 4, max_cycles: int = 2_000_000):
    """Cycle-level drain of ``st`` on flit identities.

    Returns ``(drain_cycle, link, flit)``: the cycle count at which the
    last flit ejected, and for every link traversal (in cycle order) the
    output link ``router * 5 + port`` and the global flit id it carried.
    """
    nr, p, v = rows * cols, NUM_PORTS, num_vcs
    nslot = p * v
    fifo = np.zeros((nr * p * v, depth), np.int64)
    head = np.zeros(nr * p * v, np.int64)
    count = np.zeros(nr * p * v, np.int64)
    rr = np.zeros(nr * p, np.int64)
    delta = np.array([-cols, 1, cols, -1, 0])
    ptr = np.zeros(st.m, np.int64)
    active_streams = np.flatnonzero(st.stream_len > 0)
    links, flits = [], []
    ejected, cycle, total = 0, 0, st.total
    while ejected < total:
        if cycle >= max_cycles:
            raise RuntimeError(f"reference drain exceeded {max_cycles} cycles")
        q = np.flatnonzero(count)
        if q.size:
            f = fifo[q, head[q]]
            r = q // nslot
            slot = q % nslot
            vc = q % v
            d = st.flit_dest[f]
            rr_, rc = r // cols, r % cols
            dr, dc = d // cols, d % cols
            out = np.where(dc > rc, PORT_E, np.where(
                dc < rc, PORT_W, np.where(dr > rr_, PORT_S, np.where(
                    dr < rr_, PORT_N, PORT_L))))
            eject = out == PORT_L
            down = ((r + delta[out]) * p + OPPOSITE[out]) * v + vc
            down = np.where(eject, 0, down)
            req = eject | (count[down] < depth)
            q, f, r, slot, out, down, eject = (
                q[req], f[req], r[req], slot[req], out[req], down[req],
                eject[req])
            link = r * p + out
            rel = (slot - rr[link]) % nslot
            o = np.lexsort((rel, link))
            first = np.ones(o.size, bool)
            first[1:] = link[o[1:]] != link[o[:-1]]
            win = o[first]
            wq, wf, wlink, wout = q[win], f[win], link[win], out[win]
            wslot, wdown, wej = slot[win], down[win], eject[win]
            head[wq] = (head[wq] + 1) % depth
            count[wq] -= 1
            rr[wlink] = (wslot + 1) % nslot
            links.append(wlink)
            flits.append(wf)
            ejected += int(wej.sum())
            push = ~wej
            pq, pf = wdown[push], wf[push]
            fifo[pq, (head[pq] + count[pq]) % depth] = pf
            count[pq] += 1
        if active_streams.size:
            s = active_streams
            f = st.stream_off[s] + ptr[s]
            lq = (st.mc_nodes[s] * p + PORT_L) * v + st.flit_vc[f]
            can = count[lq] < depth
            s, f, lq = s[can], f[can], lq[can]
            fifo[lq, (head[lq] + count[lq]) % depth] = f
            count[lq] += 1
            ptr[s] += 1
            active_streams = active_streams[ptr[active_streams]
                                            < st.stream_len[active_streams]]
        cycle += 1
    cat = (lambda a: np.concatenate(a) if a else np.zeros(0, np.int64))
    return cycle, cat(links), cat(flits)


def link_order(links: np.ndarray, flits: np.ndarray):
    """The traversals grouped by link, in cycle order within each link:
    (flit ids, True where a flit is the first its link carried)."""
    o = np.argsort(links, kind="stable")
    lk = links[o]
    first = np.ones(lk.size, bool)
    first[1:] = lk[1:] != lk[:-1]
    return flits[o], first


def total_bt(st: Streams, words: np.ndarray, seq: np.ndarray,
             first: np.ndarray, block: int = 1 << 20) -> int:
    """Link transitions of the traversals (each link starts from an idle
    all-zero word) plus every MC injection link's."""
    bt = 0
    for i in range(0, seq.size, block):
        j = min(seq.size, i + block)
        w = words[seq[max(i - 1, 0):j]]
        x = w[1:] ^ w[:-1] if i else np.concatenate([w[:1], w[1:] ^ w[:-1]])
        f = first[i:j]
        x[f] = w[-x.shape[0]:][f]
        bt += int(np.bitwise_count(x).sum(dtype=np.int64))
    for s in range(st.m):
        w = words[st.stream_off[s]:st.stream_off[s] + st.stream_len[s]]
        if w.size:
            bt += int(_pop(w[0]).sum()) + int(_pop(w[1:] ^ w[:-1]).sum())
    return bt


# --- one cell ---------------------------------------------------------------------

def pe_indices(layers: Sequence[Tuple[np.ndarray, ...]],
               num_pes: int) -> np.ndarray:
    """Every packet's PE index, across layers: a layer's own ``pe_index``
    where it gives one, else ``g % num_pes`` of the global packet id g."""
    out, g0 = [np.zeros(0, np.int64)], 0
    for layer in layers:
        n = layer[0].shape[0]
        own = (np.asarray(layer[2], np.int64) if len(layer) == 3
               else (g0 + np.arange(n, dtype=np.int64)) % num_pes)
        if own.shape != (n,):
            raise ValueError(f"a layer of {n} packets has a pe_index of "
                             f"shape {own.shape}")
        if n and (own.min() < 0 or own.max() >= num_pes):
            raise IndexError(f"pe_index outside [0, {num_pes}): "
                             f"{own.min()}..{own.max()}")
        out.append(own)
        g0 += n
    return np.concatenate(out)


def reference_rows(layers: Sequence[Tuple[np.ndarray, ...]],
                   config: dict, traffic: dict, bits: Optional[int] = None,
                   log=None) -> List[Dict]:
    """Rows ``{placement, affinity, transform, total_bt, cycles, flits}``
    for every (placement, affinity, transform) of the cell, in the sweep's
    row order. ``bits`` overrides the configuration's fixed-point width
    (the control computes at a narrower one).

    A layer is ``(inputs, weights)`` or ``(inputs, weights, pe_index)``:
    ``pe_index`` is an int array, one entry per packet (row), that picks
    the packet's destination from the placement's PE list (the mesh's
    routers without an MC, in router order), so that it holds under every
    placement. A layer without one deals its packets round-robin, packet g
    to index ``g % len(pes)``."""
    noc = config["noc"]
    rows, cols, nmc = noc["rows"], noc["cols"], noc["num_mcs"]
    lanes, nvc, depth = noc["lanes"], noc["num_vcs"], noc["vc_depth"]
    if config["precision"] != "fixed8":
        raise KeyError(f"the reference has no precision {config['precision']!r}")
    bits = 8 if bits is None else bits
    quant = [(quantize_fixed(i, bits), quantize_fixed(w, bits))
             for i, w, *_ in layers]
    half = lanes // 2
    axes = traffic["grid"]
    if any(tb != "pattern" for tb in axes["tiebreaks"]):
        raise KeyError("the reference breaks popcount ties by pattern only")
    payloads = {}
    for tr in axes["transforms"]:
        payloads[tr] = [pack_paired(*order_packets(i, w, tr, half), lanes)
                        for i, w in quant]
        if log:
            log(f"reference packetized {tr}")
    shapes = [(p.shape[0], p.shape[1]) for p in payloads[axes["transforms"][0]]]
    out = []
    for placement in axes["placements"]:
        mcs = mc_nodes(rows, cols, nmc, placement)
        pes = [x for x in range(rows * cols) if x not in set(mcs)]
        for aff in axes["affinity"]:
            st = Streams(shapes, rows, cols, mcs, nvc, lanes,
                         pe_indices(layers, len(pes)),
                         mc_table(rows, cols, mcs, pes, aff))
            cycles, links, flits = drain_schedule(st, rows, cols, nvc, depth)
            if log:
                log(f"reference drained {placement}/{aff}: {cycles} cycles")
            seq, first = link_order(links, flits)
            for tr in axes["transforms"]:
                out.append({"placement": placement, "affinity": aff,
                            "transform": tr,
                            "total_bt": total_bt(st, st.words(payloads[tr]),
                                                 seq, first),
                            "cycles": cycles, "flits": st.total})
    return out
