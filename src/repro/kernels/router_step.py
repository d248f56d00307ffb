"""Pallas kernel: one NoC router cycle over the whole mesh state.

This is the per-cycle hot loop of ``repro.noc.sim`` - sideband front
gather, X-Y route computation, credit check, masked-min round-robin
arbitration, the combined push+inject scatter, and the Fig. 8 BT
accumulate - as a single ``pl.pallas_call`` body. The surrounding scan,
the injection-row gather from the (M, T, LF) wire tensor (which cannot
live in VMEM at DarkNet scale), and the drain bookkeeping stay in
``noc.sim``; the kernel sees one cycle's state plus the M pre-gathered
injection rows and returns the next state.

Backend contract: this kernel is an interpret-mode parity path. With
``interpret=True`` on CPU the parity suite pins it cycle-for-cycle
against the fused step and the frozen ``noc._reference`` step. It is not
on any chip path: the TPU's Mosaic compiler rejects its gathers ("Only
2D gather is supported") at every mesh size, so ``make_router_step``
refuses ``interpret=False`` with :data:`TPU_UNSUPPORTED`, and
``noc.sim``'s ``backend="auto"`` resolves to the fused jnp step on every
platform. The arithmetic is copied from ``noc.sim._make_step`` op for op
- same gathers, same select chains, same scatter - so the two backends
are bit-identical by construction, and the parity tests keep them that
way. The BT recorder uses the SWAR popcount form
(``kernels/popcount.py``) instead of ``lax.population_count``; the two
are pinned equal by the kernel parity suite.

The conservation ledger (``check_conservation``) is a debug path and is
not carried here - tracked drains run the fused step, and ``noc.sim``
refuses an explicit ``backend="pallas"`` with the ledger armed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.noc.topology import (NocConfig, NUM_PORTS, PORT_E, PORT_LOCAL,
                                PORT_N, PORT_S, PORT_W)

__all__ = ["router_step_pallas", "make_router_step", "TPU_UNSUPPORTED"]

TPU_UNSUPPORTED = (
    "the Pallas router kernel does not compile for TPU: Mosaic rejects its "
    "gathers (only 2D gathers are supported). Use backend='auto' or "
    "'fused' there; backend='pallas' is the CPU interpret-mode parity path.")

# Sideband layout - must match noc.sim (imported there and checked by the
# parity suite; duplicated as literals here to keep the kernel module free
# of a circular import on noc.sim).
_SIDE_META_SHIFT = 9
_SIDE_VC_SHIFT = 11
_DEST_MASK = (1 << 9) - 1
_META_MASK = 3
_META_PAYLOAD = 1


def _popcount(x: jax.Array) -> jax.Array:
    """SWAR popcount (the popcount.py body) - Mosaic-lowerable, pinned
    bit-identical to ``lax.population_count`` by the parity suite."""
    x = x.astype(jnp.uint32)
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def _make_kernel(mesh_key, count_headers: bool, lf: int, m: int):
    """Build the kernel body for one (mesh size, recorder, stream count).

    All routing geometry is baked in as compile-time constants exactly as
    in ``noc.sim._make_step`` (same derivation, same names).
    """
    rows, cols, num_vcs, vc_depth, lanes = mesh_key
    cfg = NocConfig(rows, cols, (), num_vcs=num_vcs, vc_depth=vc_depth,
                    lanes=lanes)
    nr, p, v, d, l = (cfg.num_routers, NUM_PORTS, cfg.num_vcs, cfg.vc_depth,
                      cfg.lanes)
    nslots = p * v

    phantom_row = nr * NUM_PORTS * num_vcs * vc_depth

    def _geometry():
        """Routing constants, derived in-kernel from iota arithmetic.

        Pallas kernels cannot capture array constants, so the tables
        ``noc.sim._make_step`` bakes in as numpy arrays are recomputed
        here elementwise - same values (checked by the parity suite),
        zero inputs. Directions 0..3 are N, E, S, W; ``OPPOSITE`` for
        them is ``(dir + 2) % 4``.
        """
        cid3 = jax.lax.broadcasted_iota(jnp.int32, (nr, 1, 1), 0)
        rrow = cid3 // cols
        rcol = cid3 % cols
        c2 = jax.lax.broadcasted_iota(jnp.int32, (nr, 4), 0)
        dirid = jax.lax.broadcasted_iota(jnp.int32, (nr, 4), 1)
        rr2, cc2 = c2 // cols, c2 % cols
        delta = jnp.where(
            dirid == PORT_N, -cols, jnp.where(
                dirid == PORT_E, 1, jnp.where(
                    dirid == PORT_S, cols, -1)))
        down2 = c2 + delta
        dir_ok = jnp.where(
            dirid == PORT_N, rr2 > 0, jnp.where(
                dirid == PORT_E, cc2 < cols - 1, jnp.where(
                    dirid == PORT_S, rr2 < rows - 1, cc2 > 0)))
        opp = (dirid + 2) % 4
        nb_blk = jnp.where(dir_ok, down2 * NUM_PORTS + opp,
                           nr * NUM_PORTS)
        src_po = jnp.where(dir_ok, down2, 0) * NUM_PORTS + opp
        rcv_base = c2 * NUM_PORTS + dirid
        return rrow, rcol, nb_blk, dir_ok, src_po, rcv_base

    def kernel(fifo_ref, head_ref, count_ref, rr_ref, link_last_ref,
               link_bt_ref, link_flits_ref, inj_ptr_ref, inj_last_ref,
               inj_bt_ref, ejected_ref, cycle_ref, drained_ref,
               iw_ref, active_ref, mc_ref, total_ref,
               fifo_o, head_o, count_o, rr_o, link_last_o,
               link_bt_o, link_flits_o, inj_ptr_o, inj_last_o,
               inj_bt_o, ejected_o, cycle_o, drained_o):
        rrow, rcol, nb_blk, src_ok, src_po, rcv_base = _geometry()
        fifo_rows = fifo_ref[...]                   # ((NR+1)*P*V*D, LF)
        head_full = head_ref[...]                   # (NR+1, P, V)
        count_full = count_ref[...]
        rr = rr_ref[...]                            # (NR, P)
        head_r = head_full[:nr]
        count_r = count_full[:nr]
        valid = count_r > 0

        # --- front sideband: one word per FIFO ---
        side_col = fifo_rows[:, l]
        front_row = (jnp.arange(nr * p * v, dtype=jnp.int32) * d
                     + head_r.reshape(-1))
        fside = jnp.take(side_col, front_row, mode="clip").astype(jnp.int32)
        fside = fside.reshape(nr, p, v)
        fd = fside & _DEST_MASK

        # --- route computation (X-Y, closed form) ---
        dr, dc = fd // cols, fd % cols
        out_port = jnp.where(
            dc > rcol, PORT_E, jnp.where(
                dc < rcol, PORT_W, jnp.where(
                    dr > rrow, PORT_S, jnp.where(
                        dr < rrow, PORT_N, PORT_LOCAL)))).astype(jnp.int32)

        # --- credit check ---
        is_eject = out_port == PORT_LOCAL
        count_blocks = count_full.reshape((nr + 1) * p, v)
        ok = (jnp.take(count_blocks, nb_blk.reshape(-1), axis=0,
                       mode="clip").reshape(nr, 4, v) < d)
        space = jnp.where(
            out_port == PORT_N, ok[:, None, PORT_N, :], jnp.where(
                out_port == PORT_E, ok[:, None, PORT_E, :], jnp.where(
                    out_port == PORT_S, ok[:, None, PORT_S, :],
                    ok[:, None, PORT_W, :])))
        request = valid & (is_eject | space)

        # --- switch allocation: masked-min round-robin ---
        slot_req = request.reshape(nr, nslots)
        slot_out = out_port.reshape(nr, nslots)
        outs = jnp.arange(NUM_PORTS)[None, :, None]
        req_po = slot_req[:, None, :] & (slot_out[:, None, :] == outs)
        slots = jnp.arange(nslots, dtype=jnp.int32)[None, None, :]
        rel = slots - rr[:, :, None]
        rel = jnp.where(rel < 0, rel + nslots, rel)
        min_rel = jnp.where(req_po, rel, nslots).min(axis=2)
        has = min_rel < nslots
        winner = rr + min_rel
        winner = jnp.where(winner >= nslots, winner - nslots, winner)
        rr_new = winner + 1
        rr_new = jnp.where(rr_new >= nslots, rr_new - nslots, rr_new)
        rr_new = jnp.where(has, rr_new, rr)

        # --- pops ---
        pop = ((slots == winner[:, :, None]) & has[:, :, None]).any(axis=1)
        pop = pop.reshape(nr, p, v)
        head_new = jnp.where(pop, (head_r + 1) % d, head_r)
        count_new = count_r - pop.astype(jnp.int32)
        head2 = head_full.at[:nr].set(head_new)
        count2 = count_full.at[:nr].set(count_new)

        # --- gather the winners' flits only ---
        win_p = winner // v
        win_v = winner % v
        r2 = jnp.arange(nr, dtype=jnp.int32)[:, None]
        win_pv = (r2 * p + win_p) * v + win_v
        win_head = jnp.take(head_full.reshape(-1), win_pv.reshape(-1),
                            mode="clip")
        win_row = win_pv.reshape(-1) * d + win_head
        mv = jnp.take(fifo_rows, win_row, axis=0,
                      mode="clip").reshape(nr, p, lf)
        mv_side = mv[..., l].astype(jnp.int32)
        mv_meta = (mv_side >> _SIDE_META_SHIFT) & _META_MASK

        # --- link BT recording ---
        tog = _popcount(link_last_ref[...] ^ mv[..., :l]).sum(-1)
        if count_headers:
            counted = has
        else:
            counted = has & ((mv_meta & _META_PAYLOAD) > 0)
        link_bt_o[...] = link_bt_ref[...] + jnp.where(counted, tog, 0)
        link_flits_o[...] = link_flits_ref[...] + has.astype(jnp.int32)
        link_last_o[...] = jnp.where(has[:, :, None], mv[..., :l],
                                     link_last_ref[...])

        # --- pushes, receiver-side ---
        o_ids = jnp.arange(NUM_PORTS)[None, :]
        inc_ok = (jnp.take(has.reshape(-1), src_po.reshape(-1), mode="clip")
                  .reshape(nr, 4) & src_ok)
        inc_vc = jnp.take(win_v.reshape(-1), src_po.reshape(-1),
                          mode="clip").reshape(nr, 4)
        inc_w = jnp.take(mv.reshape(nr * p, lf), src_po.reshape(-1),
                         axis=0, mode="clip")
        wc4 = (head2[:nr, :4, :] + count2[:nr, :4, :]) % d
        wslot = wc4[..., 0]
        for vi in range(1, v):
            wslot = jnp.where(inc_vc == vi, wc4[..., vi], wslot)
        ejected = (ejected_ref[...][0]
                   + jnp.sum(has & (o_ids == PORT_LOCAL)).astype(jnp.int32))

        # --- injection ---
        iw = iw_ref[...]                            # (M, LF) pre-gathered
        active = active_ref[...] > 0                # (M,)
        iside = iw[..., l].astype(jnp.int32)
        imeta = (iside >> _SIDE_META_SHIFT) & _META_MASK
        ivc = iside >> _SIDE_VC_SHIFT
        head2_flat = head2.reshape(-1)
        count2_flat = count2.reshape(-1)
        mc_pv = (mc_ref[...] * p + PORT_LOCAL) * v + ivc
        mc_cnt = jnp.take(count2_flat, mc_pv, mode="clip")
        can = active & (mc_cnt < d)
        inj_pv = jnp.where(can, mc_pv, (nr * p + PORT_LOCAL) * v + ivc)
        islot = (jnp.take(head2_flat, inj_pv, mode="clip")
                 + jnp.take(count2_flat, inj_pv, mode="clip")) % d

        # --- one combined push+inject scatter ---
        rcv_row = jnp.where(inc_ok, (rcv_base * v + inc_vc) * d + wslot,
                            phantom_row)
        cat_row = jnp.concatenate([rcv_row.reshape(-1), inj_pv * d + islot])
        cat_w = jnp.concatenate([inc_w, iw])
        fifo_o[...] = fifo_rows.at[cat_row].set(cat_w,
                                                mode="promise_in_bounds")
        vcs4 = jnp.arange(v, dtype=jnp.int32)[None, None, :]
        count_inc = ((vcs4 == inc_vc[..., None])
                     & inc_ok[..., None]).astype(jnp.int32)
        count_o[...] = count2.at[:nr, :4, :].add(count_inc).reshape(-1).at[
            inj_pv].add(can.astype(jnp.int32),
                        mode="promise_in_bounds").reshape(count2.shape)
        head_o[...] = head2
        rr_o[...] = rr_new
        inj_ptr_o[...] = inj_ptr_ref[...] + can.astype(jnp.int32)

        # --- NI-link BT ---
        itog = _popcount(inj_last_ref[...] ^ iw[..., :l]).sum(-1)
        if count_headers:
            icounted = can
        else:
            icounted = can & ((imeta & _META_PAYLOAD) > 0)
        inj_bt_o[...] = inj_bt_ref[...] + jnp.where(icounted, itog, 0)
        inj_last_o[...] = jnp.where(can[:, None], iw[..., :l],
                                    inj_last_ref[...])

        cycle = cycle_ref[...][0]
        drained = drained_ref[...][0]
        drained_new = jnp.where(
            (drained < 0) & (ejected >= total_ref[...][0]),
            cycle + 1, drained)
        ejected_o[...] = ejected[None]
        cycle_o[...] = (cycle + 1)[None]
        drained_o[...] = drained_new[None]

    return kernel


@functools.lru_cache(maxsize=None)
def make_router_step(mesh_key, count_headers: bool, lf: int, m: int,
                     interpret: bool):
    """Pallas-callable for one router cycle; cached per static signature.

    The callable takes the 13 state leaves (fifo flattened to rows), the
    pre-gathered injection rows ``iw`` (M, LF), the ``active`` int32 mask
    (M,), the per-stream injection nodes (M,) and the scalar flit total
    (1,), and returns the 13 next-cycle leaves in the same layout.
    ``interpret=False`` (a Mosaic compile for TPU) raises ValueError with
    :data:`TPU_UNSUPPORTED` instead of failing deep inside the compiler.
    """
    if not interpret:
        raise ValueError(TPU_UNSUPPORTED)
    rows, cols, num_vcs, vc_depth, lanes = mesh_key
    nr = rows * cols
    p, v, d, l = NUM_PORTS, num_vcs, vc_depth, lanes
    nrows = (nr + 1) * p * v * d
    kernel = _make_kernel(mesh_key, count_headers, lf, m)
    out_shape = [
        jax.ShapeDtypeStruct((nrows, lf), jnp.uint32),      # fifo rows
        jax.ShapeDtypeStruct((nr + 1, p, v), jnp.int32),    # head
        jax.ShapeDtypeStruct((nr + 1, p, v), jnp.int32),    # count
        jax.ShapeDtypeStruct((nr, p), jnp.int32),           # rr
        jax.ShapeDtypeStruct((nr, p, l), jnp.uint32),       # link_last
        jax.ShapeDtypeStruct((nr, p), jnp.int32),           # link_bt
        jax.ShapeDtypeStruct((nr, p), jnp.int32),           # link_flits
        jax.ShapeDtypeStruct((m,), jnp.int32),              # inj_ptr
        jax.ShapeDtypeStruct((m, l), jnp.uint32),           # inj_last
        jax.ShapeDtypeStruct((m,), jnp.int32),              # inj_bt
        jax.ShapeDtypeStruct((1,), jnp.int32),              # ejected
        jax.ShapeDtypeStruct((1,), jnp.int32),              # cycle
        jax.ShapeDtypeStruct((1,), jnp.int32),              # drained_at
    ]
    return pl.pallas_call(kernel, out_shape=out_shape, interpret=interpret)


def router_step_pallas(mesh_key, count_headers: bool, lf: int,
                       state_leaves, iw, active, mc_nodes, total,
                       *, interpret: bool = True):
    """One router cycle through the Pallas kernel (see module docstring).

    state_leaves: the 13 SimState leaves with fifo pre-flattened to
        ``((NR+1)*P*V*D, LF)`` rows and the three scalars shaped (1,).
    """
    m = int(iw.shape[0])
    call = make_router_step(mesh_key, count_headers, lf, m, interpret)
    return call(*state_leaves, iw, active, mc_nodes, total)
