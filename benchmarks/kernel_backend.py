"""Kernel-backend trajectory suite: the raw-speed layer's before/after.

Two measurements back the ``kernel_backend`` entry in BENCH_noc.json:

* ``fig12_packetize``: the fig12 ordering workload (full trained-LeNet
  operand layers, O0/O1/O2/O3 x float32/fixed8 at the fig12 packet budget)
  timed against the frozen pre-batching recording
  (:data:`PR6_FIG12_PACKETIZE_S` - ``suites.fig12.packetize_s`` before the
  batched O3 chain landed). Ordering caches are cleared first so the
  number is cold-equivalent even when fig12 ran earlier in the process.
* ``router_step``: cycles/sec of the pinned ``step_overhaul`` 8x8 chunk
  under the fused jnp step vs the Pallas router kernel. On CPU the Pallas
  path runs in interpret mode (a correctness artifact, expected slower -
  the recorded ``mode`` says which path was measured). The kernel does
  not compile for TPU, so there this half is skipped with a printed
  reason (recorded under ``skipped``).

Both halves pin bit-identity: the O3 payloads against the per-window
numpy oracle chain, and the Pallas drain against the fused drain on a
short pinned batch. ``--check-packetize-ceiling S`` runs only the
ordering measurement and exits nonzero above S seconds - the CI gate
against packetizer regressions (generous margin for CI jitter).
"""
from __future__ import annotations

import sys
import time

import numpy as np

from repro.core.wire import by_name
from repro.kernels.ops import on_tpu
from repro.kernels.router_step import TPU_UNSUPPORTED
from repro.noc import mesh_by_name
from repro.noc.sim import simulate_batch
from repro.noc.traffic import _packet_fn, build_traffic_batch, \
    ordered_payloads
from repro.quant import quantize_fixed8

from .fig12 import SMOKE, lenet_layers
from .step_overhaul import PIN, _fused_cps, _pinned_traffic

# suites.fig12.packetize_s recorded at PR 6 (per-window O3 chain dispatch,
# lax.top_k beam select) - the baseline the batched chain is gated against.
PR6_FIG12_PACKETIZE_S = 160.47


def _fig12_variants(transforms=("O0", "O1", "O2", "O3")):
    """The fig12 ordering workload's variant list (pattern tiebreak,
    float32 + fixed8) - what ``run_sweep`` orders once per model."""
    quant = {"float32": None, "fixed8": lambda t: quantize_fixed8(t).values}
    return [(by_name(tr, tiebreak="pattern"), quant[prec])
            for prec in ("float32", "fixed8") for tr in transforms]


def _clear_ordering_caches():
    """Cold-equivalent timing: drop the memoized packet transforms and
    every jitted executable (the batched chain scan included)."""
    import jax
    _packet_fn.cache_clear()
    jax.clear_caches()


def packetize_compare() -> dict:
    """Time the fig12 ordering workload cold and pin O3 == oracle."""
    from repro.kernels.min_hamming import (min_hamming_chain,
                                           min_hamming_chain_reference)

    layers = lenet_layers(trained=not SMOKE)
    lanes = mesh_by_name("4x4_mc2").lanes
    max_packets = 4 if SMOKE else 40
    _clear_ordering_caches()
    t0 = time.perf_counter()
    stacks = ordered_payloads(layers, lanes, _fig12_variants(),
                              max_packets_per_layer=max_packets)
    after_s = time.perf_counter() - t0
    total_words = int(sum(s.size for s in stacks))

    # Oracle pin: the batched beam-select chain must match the per-window
    # numpy reference on real operand windows (the full sweep-level parity
    # lives in tests/test_kernel_parity.py).
    u = np.abs(np.asarray(layers[0].inputs[:5, :12],
                          np.float32)).view(np.uint32)
    res = min_hamming_chain(u)
    want_perm, want_cost, _ = min_hamming_chain_reference(u)
    oracle_ok = (np.array_equal(np.asarray(res.perm), want_perm)
                 and np.array_equal(np.asarray(res.cost), want_cost))

    before_s = None if SMOKE else PR6_FIG12_PACKETIZE_S
    out = {
        "workload": {"model": "lenet", "transforms": ["O0", "O1", "O2", "O3"],
                     "precisions": ["float32", "fixed8"],
                     "tiebreak": "pattern", "max_packets": max_packets,
                     "lanes": lanes, "smoke": SMOKE},
        "before_s": before_s,
        "after_s": round(after_s, 3),
        "speedup": (round(before_s / after_s, 2) if before_s else None),
        "ordered_words": total_words,
        "oracle_identical": bool(oracle_ok),
    }
    if not oracle_ok:
        raise RuntimeError(f"batched O3 chain diverged from the numpy "
                           f"oracle: {out}")
    return out


def router_step_compare() -> dict:
    """Fused vs Pallas cycles/sec on the pinned 8x8 chunk, plus drain
    bit-identity of the two backends on a short pinned batch. Skipped
    with a printed reason on TPU, where the kernel does not compile."""
    if on_tpu():
        print(f"kernel_backend/router_step skipped: {TPU_UNSUPPORTED}")
        return {"pinned": dict(PIN, variants=list(PIN["variants"])),
                "skipped": TPU_UNSUPPORTED}
    cfg, batch = _pinned_traffic()
    fused_cps, _ = _fused_cps(cfg, batch)
    pallas_cps, _ = _fused_cps(cfg, batch, backend="pallas")

    small = mesh_by_name("4x4_mc2")
    sl = lenet_layers(trained=not SMOKE)
    variants = [(by_name(o, tiebreak="pattern"), None)
                for o in ("O0", "O1", "O2")]
    bt = build_traffic_batch(sl, small, variants, max_packets_per_layer=8)
    rf = simulate_batch(small, bt, chunk=512, backend="fused")
    rp = simulate_batch(small, bt, chunk=512, backend="pallas")
    bt_ok = all(
        a.total_bt == b.total_bt and a.drain_cycle == b.drain_cycle
        and np.array_equal(a.link_bt, b.link_bt)
        for a, b in zip(rf, rp))
    return {
        "pinned": dict(PIN, variants=list(PIN["variants"])),
        "fused_cps": round(fused_cps, 1),
        "pallas_cps": round(pallas_cps, 1),
        "mode": "interpret",
        "bt_identical": bool(bt_ok),
    }


def main(print_csv: bool = True) -> dict:
    pk = packetize_compare()
    rs = router_step_compare()
    rs_ok = rs.get("bt_identical", "skipped" in rs)
    if not rs_ok:
        raise RuntimeError(f"Pallas drain diverged from fused: {rs}")
    bench = {"fig12_packetize": pk, "router_step": rs,
             "bt_identical": bool(pk["oracle_identical"] and rs_ok)}
    if print_csv:
        spd = f" speedup={pk['speedup']}x" if pk["speedup"] else ""
        print(f"kernel_backend/fig12_packetize,"
              f"{pk['after_s'] * 1e6:.0f},"
              f"before={pk['before_s']} after={pk['after_s']}{spd}")
        if "skipped" not in rs:
            print(f"kernel_backend/router_step,0,"
                  f"fused={rs['fused_cps']} pallas={rs['pallas_cps']} "
                  f"mode={rs['mode']} bt_identical={rs['bt_identical']}")
    return {"results": bench, "bench": bench}


def check_packetize_ceiling(ceiling_s: float) -> None:
    pk = packetize_compare()
    print(f"kernel_backend packetize check: {pk['after_s']:.1f}s "
          f"(ceiling {ceiling_s}s, oracle_identical={pk['oracle_identical']})")
    if pk["after_s"] > ceiling_s:
        raise SystemExit(f"fig12 packetize regression: {pk['after_s']:.1f}s "
                         f"> ceiling {ceiling_s}s")


if __name__ == "__main__":
    if len(sys.argv) == 1:
        main()
    elif sys.argv[1] == "--check-packetize-ceiling" and len(sys.argv) == 3:
        check_packetize_ceiling(float(sys.argv[2]))
    else:
        raise SystemExit(
            f"usage: {sys.argv[0]} [--check-packetize-ceiling SECONDS]")
