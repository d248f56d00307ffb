"""WireTransform: the composable link-payload transform API.

A WireTransform is what an ordering unit at a memory controller (paper
Fig. 6) -- or, in the beyond-paper extension, at a TPU ICI boundary -- applies
to a value stream before it hits the wires. It captures the three paper
configurations:

    O0 (baseline)  -> IdentityTransform
    O1 (affiliated)-> AffiliatedTransform   (keyed on the weight stream)
    O2 (separated) -> SeparatedTransform

plus the interleaved optimal variant used in the beyond-paper study and the
beyond-paper min-Hamming orderings:

    O3  (separated min-Hamming)  -> MinHammingTransform
    O3a (affiliated min-Hamming) -> MinHammingAffiliatedTransform

All transforms are pure functions of the payload (jit-safe) and report their
recovery overhead so benchmarks can charge it honestly.
``overhead_bits_per_value(window, paired=...)`` distinguishes the paired
request phase (order-invariant contraction: only *re-pairing* needs bits,
so O1/O3a are free) from single-stream phases (results, lone weight
streams: any non-identity reorder needs a recovery index to restore
element order).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import ClassVar, Optional

import jax
import numpy as np

from . import msr, ordering
from .flits import FlitStream, pack, pack_paired
from . import bt as bt_mod

__all__ = [
    "WireTransform",
    "IdentityTransform",
    "DescendingTransform",
    "AffiliatedTransform",
    "SeparatedTransform",
    "MinHammingTransform",
    "MinHammingAffiliatedTransform",
    "TRANSFORMS",
    "by_name",
    "measure",
    "PROTECTION_BITS",
    "protection_overhead_bits",
    "protection_syndrome_masks",
    "crc8_reference",
    "COMPRESSIONS",
    "compression_overhead_bits",
]


@dataclasses.dataclass(frozen=True)
class WireTransform:
    """Base: pack a paired (inputs, weights) stream into flits untouched."""

    name: str = "O0"
    window: Optional[int] = None
    tiebreak: str = "stable"   # "pattern" clusters equal-count values
    # The packetizer runs this transform on the host CPU, not the default
    # device (see MinHammingTransform).
    host_only: ClassVar[bool] = False

    def overhead_bits_per_value(self, window: int, paired: bool = True) -> int:
        """Recovery bits the receiver needs per transmitted value.

        ``paired=True`` is the request phase: (input, weight) pairs feed an
        order-invariant contraction, so only *re-pairing* information is
        chargeable. ``paired=False`` is a single stream (result phase, lone
        weight stream) whose element order itself must be restored, so any
        non-identity reorder costs a window-index per value.
        """
        return 0

    def order(self, inputs: jax.Array, weights: jax.Array, lanes: int):
        """The transform's value reordering alone: ``(inputs, weights) ->
        (ordered_inputs, ordered_weights)``, before any flit packing.

        ``apply`` is always ``pack_paired(*order(...))``; the compression
        knob (``repro.noc.traffic``) swaps the packer for the MSR codec's
        while reusing the exact same ordering, so ordered values have one
        definition per transform regardless of the wire encoding."""
        return inputs, weights

    def order_single(self, values: jax.Array, lanes: int) -> jax.Array:
        """Single-stream ordering alone (``apply_single`` = ``pack`` of it)."""
        return values

    def apply(self, inputs: jax.Array, weights: jax.Array, lanes: int) -> FlitStream:
        oi, ow = self.order(inputs, weights, lanes)
        return pack_paired(oi, ow, lanes)

    def apply_single(self, values: jax.Array, lanes: int) -> FlitStream:
        return pack(self.order_single(values, lanes), lanes)


class IdentityTransform(WireTransform):
    pass


@dataclasses.dataclass(frozen=True)
class DescendingTransform(WireTransform):
    """Single-stream popcount-descending ordering (no pairing semantics).

    ``fill='interleave'`` gives the provably optimal per-lane interleave;
    ``fill='rowmajor'`` is the paper's Fig. 9 layout.
    """

    name: str = "desc"
    fill: str = "rowmajor"

    def overhead_bits_per_value(self, window: int, paired: bool = True) -> int:
        # Halves are sorted independently (paired) and element order is not
        # preserved (single): a recovery index is owed either way.
        return ordering.index_overhead_bits(window)

    def order_single(self, values: jax.Array, lanes: int) -> jax.Array:
        return ordering.descending_order(
            values, window=self.window, fill=self.fill,
            lanes=lanes if self.fill == "interleave" else None,
            tiebreak=self.tiebreak).values

    def order(self, inputs: jax.Array, weights: jax.Array, lanes: int):
        # Without pairing semantics, order each half independently.
        oi = ordering.descending_order(
            inputs, window=self.window, fill=self.fill,
            lanes=(lanes // 2) if self.fill == "interleave" else None,
            tiebreak=self.tiebreak)
        ow = ordering.descending_order(
            weights, window=self.window, fill=self.fill,
            lanes=(lanes // 2) if self.fill == "interleave" else None,
            tiebreak=self.tiebreak)
        return oi.values, ow.values


@dataclasses.dataclass(frozen=True)
class AffiliatedTransform(WireTransform):
    """O1: order pairs by weight popcount; pairing intact, zero recovery cost."""

    name: str = "O1"

    def overhead_bits_per_value(self, window: int, paired: bool = True) -> int:
        # Pairs travel together, so re-pairing is free -- but a *single*
        # popcount-sorted stream still owes the index that restores order.
        return 0 if paired else ordering.index_overhead_bits(window)

    def order(self, inputs: jax.Array, weights: jax.Array, lanes: int):
        po = ordering.affiliated_order(inputs, weights, window=self.window,
                                       tiebreak=self.tiebreak)
        return po.inputs, po.weights

    def order_single(self, values: jax.Array, lanes: int) -> jax.Array:
        # A lone weight stream under O1 is just descending ordering.
        return ordering.descending_order(values, window=self.window,
                                         tiebreak=self.tiebreak).values


@dataclasses.dataclass(frozen=True)
class SeparatedTransform(WireTransform):
    """O2: order each stream by its own popcount; index needed to re-pair."""

    name: str = "O2"

    def overhead_bits_per_value(self, window: int, paired: bool = True) -> int:
        return ordering.index_overhead_bits(window)

    def order(self, inputs: jax.Array, weights: jax.Array, lanes: int):
        po = ordering.separated_order(inputs, weights, window=self.window,
                                      tiebreak=self.tiebreak)
        return po.inputs, po.weights

    def order_single(self, values: jax.Array, lanes: int) -> jax.Array:
        return ordering.descending_order(values, window=self.window,
                                         tiebreak=self.tiebreak).values


@dataclasses.dataclass(frozen=True)
class MinHammingTransform(WireTransform):
    """O3: chain each stream by consecutive Hamming distance (separated).

    Popcount sorting (O1/O2) is a proxy for the wire objective; O3 minimizes
    consecutive-flit Hamming distance directly via multi-start greedy
    nearest-neighbor chaining with beam lookahead, then deals each chain
    column-major so chain neighbors occupy the same lane on consecutive
    flits. Streams are chained independently, so re-pairing needs an
    O2-style index -- and so does a single stream's order recovery.
    """

    name: str = "O3"
    beam: int = ordering.DEFAULT_BEAM
    starts: int = ordering.DEFAULT_STARTS
    # On a TPU v5e the chain scan, batched over many packets, returns orders
    # that are not permutations (values dropped and repeated), so O3 and
    # O3a are ordered on the host.
    host_only: ClassVar[bool] = True

    def overhead_bits_per_value(self, window: int, paired: bool = True) -> int:
        return ordering.index_overhead_bits(window)

    def order(self, inputs: jax.Array, weights: jax.Array, lanes: int):
        po = ordering.separated_min_hamming_order(
            inputs, weights, window=self.window, lanes=lanes // 2,
            beam=self.beam, starts=self.starts)
        return po.inputs, po.weights

    def order_single(self, values: jax.Array, lanes: int) -> jax.Array:
        return ordering.min_hamming_order(
            values, window=self.window, lanes=lanes,
            beam=self.beam, starts=self.starts).values


@dataclasses.dataclass(frozen=True)
class MinHammingAffiliatedTransform(MinHammingTransform):
    """O3a: one min-Hamming chain over the *combined* pair distance.

    The summed two-plane distance is exactly the paired flit's per-lane-pair
    toggle cost, and one shared permutation keeps pairs matched -- zero
    recovery cost on the request phase, like O1.
    """

    name: str = "O3a"

    def overhead_bits_per_value(self, window: int, paired: bool = True) -> int:
        return 0 if paired else ordering.index_overhead_bits(window)

    def order(self, inputs: jax.Array, weights: jax.Array, lanes: int):
        po = ordering.affiliated_min_hamming_order(
            inputs, weights, window=self.window, lanes=lanes // 2,
            beam=self.beam, starts=self.starts)
        return po.inputs, po.weights


TRANSFORMS = {
    "O0": IdentityTransform,
    "O1": AffiliatedTransform,
    "O2": SeparatedTransform,
    "O3": MinHammingTransform,
    "O3a": MinHammingAffiliatedTransform,
    "desc": DescendingTransform,
}


def by_name(name: str, window: Optional[int] = None, **kw) -> WireTransform:
    return TRANSFORMS[name](name=name, window=window, **kw)


# --------------------------------------------------------------------------
# Flit protection codes (the fault-injection wire axis; see DESIGN.md
# "Fault model & protection"). Charged per transmitted flit like the O2
# recovery index: these bits ride the sideband, not the payload lanes, so
# they never perturb the recorded payload BT - the cost is accounted
# analytically via protection_overhead_bits.

PROTECTION_BITS = {"none": 0, "parity": 1, "crc8": 8}


def protection_overhead_bits(protect: str, num_flits: int) -> int:
    """Total protection bits owed for ``num_flits`` transmitted flits.

    Retransmitted flits carry the code again, so callers charge the
    *transmitted* flit count (injections including retries), not the
    logical payload size.
    """
    return PROTECTION_BITS[protect] * int(num_flits)


# MSR payload compression (see repro.core.msr and DESIGN.md "MSR
# compression"). The 5-bit codes ride the payload lanes (real flit-count
# reduction); the per-window escape records - outlier count + (position,
# top bits) per outlier - ride the sideband like the recovery index and the
# protection codes, charged analytically at half a transition per bit.

COMPRESSIONS = ("none", "msr")


def compression_overhead_bits(compression: str, values, window: int) -> int:
    """Escape/metadata bits a compression scheme owes for transmitting
    ``values`` in ``window``-slot windows (a 2-D operand matrix charges one
    window per row; a flat stream is split into ``ceil(n/window)``).

    ``none`` owes nothing. ``msr`` owes the per-window escape records
    (:func:`repro.core.msr.escape_bits`); outlier status is per-value, so
    the charge is invariant under every WireTransform's within-window
    permutation - compression overhead is a (model, precision) property,
    never an ordering property.
    """
    if compression == "none":
        return 0
    if compression != "msr":
        raise KeyError(f"unknown compression scheme {compression!r}; "
                       f"supported: {COMPRESSIONS}")
    return msr.escape_bits(values, window)


def crc8_reference(data: bytes) -> int:
    """Bitwise CRC-8 (poly 0x07, init 0, MSB-first, no xor-out).

    With init 0 the map is linear over GF(2): ``crc(a ^ b) = crc(a) ^
    crc(b)``, which is what lets :func:`protection_syndrome_masks` reduce
    the per-flit code to a handful of masked popcount parities.
    """
    crc = 0
    for byte in data:
        crc ^= byte
        for _ in range(8):
            crc = ((crc << 1) ^ 0x07) & 0xFF if crc & 0x80 else (crc << 1) & 0xFF
    return crc


@functools.lru_cache(maxsize=None)
def protection_syndrome_masks(protect: str, lanes: int) -> np.ndarray:
    """``(code_bits, lanes)`` uint32 masks: code bit ``j`` of a flit payload
    equals ``popcount(payload & masks[j]) & 1`` summed over the lanes.

    The flit message is the payload words in lane order, little-endian
    bytes, LSB-first bits. Because both codes are linear with zero init,
    the code of any message is the XOR of the codes of its set bits -
    precomputing the per-bit syndromes folds CRC8 into 8 masked parities
    the simulator can evaluate with the ``popcount_hw`` it already has.
    Parity is the single all-ones mask (detects odd-weight corruption
    only; the benchmark reports what slips through).
    """
    pbits = PROTECTION_BITS[protect]
    masks = np.zeros((pbits, lanes), dtype=np.uint32)
    if protect == "parity":
        masks[0, :] = 0xFFFFFFFF
    elif protect == "crc8":
        nbytes = lanes * 4
        for pos in range(lanes * 32):
            msg = bytearray(nbytes)
            msg[pos // 8] = 1 << (pos % 8)
            syndrome = crc8_reference(bytes(msg))
            for j in range(8):
                if syndrome >> j & 1:
                    masks[j, pos // 32] |= np.uint32(1 << (pos % 32))
    elif protect != "none":
        raise KeyError(f"unknown protection scheme {protect!r}; "
                       f"supported: {sorted(PROTECTION_BITS)}")
    return masks


def measure(stream: FlitStream) -> dict:
    """BT metrics of one flit stream (the Fig. 8 recorder)."""
    return {
        "total_bt": float(bt_mod.bt_stream(stream)),
        "bt_per_flit": float(bt_mod.bt_per_flit(stream)),
        "expected_bt": float(bt_mod.expected_bt_stream(stream)),
        "num_flits": int(stream.words.shape[0]),
        "flit_bits": stream.flit_bits,
    }
