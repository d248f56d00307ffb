"""DNN layer traffic -> packetized flit streams for the NoC simulator.

Models the paper's NOC-DNA dataflow (Fig. 7): memory controllers fetch
(input, weight) operand streams from off-chip memory, run them through the
ordering unit (a WireTransform), packetize into flits - inputs in the left
half-flit, weights in the right (Fig. 2) - and inject toward the PE assigned
to each neuron computation.

A *packet* carries the operands for one neuron (one output position x output
channel for conv; one output unit for linear): K (input, weight) pairs plus
one header flit. The ordering window is the packet payload, matching the
paper's ordering-unit-per-MC placement (it sees one packet at a time).

Packetization is fully vectorized (the seed's per-neuron Python loop lives
on only as the equivalence oracle in ``repro.noc._reference``): the MC/PE/VC
assignments are closed-form functions of the global packet id (round-robin,
or a periodic packet->MC affinity table - see ``_McSchedule``), header
words and META bitfields are synthesized as arrays, the ordering transform
is applied via one ``vmap`` per layer, and per-MC streams are written with
one scatter per layer. ``build_traffic_batch`` additionally shares all of
that skeleton work across ordering/precision variants, which only differ
in payload words.

The return direction is modeled too: :func:`build_result_traffic`
packetizes the PE->MC *result phase* (one MAC value per request packet,
grouped into per-(PE, MC) result windows and ordered by the same
WireTransforms via ``apply_single``); see DESIGN.md "Result phase".
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import msr
from repro.core.wire import (COMPRESSIONS, WireTransform,
                             compression_overhead_bits)
from .topology import NocConfig
from .sim import Traffic, META_PAYLOAD, META_TAIL
from .spans import span

__all__ = ["LayerTraffic", "build_traffic", "build_traffic_batch",
           "build_traffic_streamed", "build_traffic_streamed_multi",
           "build_result_traffic", "layer_results",
           "result_values", "ordered_payloads", "ordered_payloads_streamed",
           "payload_shapes", "assemble_traffic", "TrafficAssembler",
           "stream_lengths", "pad_traffic_length", "stack_traffics",
           "concat_inferences", "filter_packets", "conv_layer_traffic",
           "linear_layer_traffic", "DEFAULT_RESULT_WINDOW",
           "COMPRESSIONS", "compression_overhead"]

# One sweep variant: an ordering transform plus an optional value->wire-dtype
# quantizer (None transmits raw float32 words).
Variant = Tuple[WireTransform, Optional[Callable[[jax.Array], jax.Array]]]


@dataclasses.dataclass
class LayerTraffic:
    """(input, weight) operand pairs for every neuron of one layer.

    inputs:  (num_neurons, k) - receptive-field values per neuron
    weights: (num_neurons, k) - the matching kernel values
    """

    inputs: jax.Array
    weights: jax.Array

    def __post_init__(self):
        if self.inputs.shape != self.weights.shape:
            raise ValueError("inputs/weights must be (num_neurons, k) alike")


def conv_layer_traffic(x: jax.Array, w: jax.Array) -> LayerTraffic:
    """im2col a conv layer: x (H, W, Cin), w (kh, kw, Cin, Cout), VALID conv.

    Neuron = (output position, output channel); k = kh*kw*Cin.
    """
    kh, kw, cin, cout = w.shape
    patches = jax.lax.conv_general_dilated_patches(
        x[None].astype(jnp.float32), (kh, kw), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[0]
    oh, ow, k = patches.shape
    patches = patches.reshape(oh * ow, k).astype(x.dtype)
    wcol = w.reshape(k, cout).T                      # (Cout, k)
    # neuron ordering: all positions of channel 0, then channel 1, ...
    inputs = jnp.tile(patches, (cout, 1))
    weights = jnp.repeat(wcol, oh * ow, axis=0)
    return LayerTraffic(inputs, weights)


def linear_layer_traffic(x: jax.Array, w: jax.Array) -> LayerTraffic:
    """x (k,), w (out, k): one packet per output unit."""
    out, k = w.shape
    inputs = jnp.broadcast_to(x[None, :], (out, k))
    return LayerTraffic(inputs, w)


def _subsample(layer: LayerTraffic,
               max_packets: Optional[int]) -> Tuple[jax.Array, jax.Array]:
    """Deterministic-stride neuron subsampling (BT rates are per-flit, so
    subsampling is unbiased); identical to the seed packetizer's."""
    inp, wgt = layer.inputs, layer.weights
    n = int(inp.shape[0])
    if max_packets is not None and n > max_packets:
        stride = n // max_packets
        idx = jnp.arange(0, stride * max_packets, stride)
        inp, wgt = inp[idx], wgt[idx]
    return inp, wgt


def _check_compression(compression: str) -> None:
    if compression not in COMPRESSIONS:
        raise ValueError(f"unknown compression {compression!r}; "
                         f"supported: {COMPRESSIONS}")


def _packet_words(transform: WireTransform, i: jax.Array, w: jax.Array,
                  lanes: int, compression: str) -> jax.Array:
    """One packet's payload words under (transform, compression).

    ``none`` is the transform's own packer (``apply``, bit-identical to the
    pre-compression path). ``msr`` reuses the exact same value ordering
    (``WireTransform.order``) but packs the 8b values as dense 5-bit MSR
    codes - fewer flits per packet, data-independent geometry (escape
    metadata is charged analytically, never materialized on the lanes)."""
    if compression == "none":
        return transform.apply(i, w, lanes).words
    oi, ow = transform.order(i, w, lanes)
    return msr.msr_pack_paired(oi, ow, lanes).words


@functools.lru_cache(maxsize=None)
def _packet_fn(transform: WireTransform, lanes: int,
               compression: str = "none"):
    """Vmapped packet transform, memoized per (transform, lanes,
    compression).

    WireTransforms are frozen dataclasses, so they key the cache. The vmap
    is deliberately left un-jitted: its primitives (argsort, gathers,
    bitcasts) hit JAX's per-primitive executable cache, which the rest of
    the stack shares, whereas a whole-program jit would recompile per
    (transform, layer shape) combination - measurably slower for the one
    pass per model a sweep performs."""
    return _placed(transform, _packet_vmap(transform, lanes, compression))


def _packet_vmap(transform: WireTransform, lanes: int, compression: str):
    def one_packet(i, w):
        return _packet_words(transform, i, w, lanes, compression)

    return jax.vmap(one_packet)


def _placed(transform: WireTransform, fn):
    """``fn`` as is, or run on the host CPU for a ``host_only`` transform
    (its operands are committed there, so jitted work follows them)."""
    if not transform.host_only:
        return fn

    def on_host(*args):
        return fn(*jax.device_put(args, jax.devices("cpu")[0]))

    return on_host


def _payload_words(inp: jax.Array, wgt: jax.Array, transform: WireTransform,
                   quantizer, lanes: int,
                   compression: str = "none") -> np.ndarray:
    """Ordered payload flits for every neuron of one layer: (n, F, L) u32.

    One vmap over neurons applies the WireTransform packet-by-packet (the
    ordering window is the packet payload)."""
    if quantizer is not None:
        inp, wgt = quantizer(inp), quantizer(wgt)
    words = _packet_fn(transform, lanes, compression)(inp, wgt)
    return np.asarray(words.astype(jnp.uint32))


def ordered_payloads(
    layers: Sequence[LayerTraffic],
    lanes: int,
    variants: Sequence[Variant],
    *,
    max_packets_per_layer: Optional[int] = None,
    compression: str = "none",
) -> List[np.ndarray]:
    """Ordered payload words per layer, stacked over variants: (B, n, F, L).

    This is the mesh-independent half of packetization (the transform sees
    only packet payloads and the flit width); the sweep engine computes it
    once per model and re-assembles it for every mesh / MC-count cell via
    :func:`assemble_traffic`. ``compression="msr"`` packs each ordered
    packet through the MSR codec instead of the raw 8b packer - fewer
    payload flits per packet, same data-independent geometry contract.
    """
    if not variants:
        raise ValueError("need at least one (transform, quantizer) variant")
    _check_compression(compression)
    out: List[np.ndarray] = []
    for layer in layers:
        inp, wgt = _subsample(layer, max_packets_per_layer)
        if inp.shape[0] == 0:
            # Probe the geometry instead of transforming nothing - a
            # quantizer's scale reduction has no identity on empty operands.
            (_, fpay), = payload_shapes([layer], lanes, variants,
                                        compression=compression)
            out.append(np.zeros((len(variants), 0, fpay, lanes), np.uint32))
            continue
        per_variant = [_payload_words(inp, wgt, tr, q, lanes, compression)
                       for tr, q in variants]
        shapes = {w.shape for w in per_variant}
        if len(shapes) != 1:
            raise ValueError(
                f"variants disagree on flit geometry: {sorted(shapes)}")
        out.append(np.stack(per_variant))
    return out


@functools.lru_cache(maxsize=None)
def _packet_chunk_fn(transform: WireTransform, lanes: int,
                     compression: str = "none"):
    """Jitted wrapper of :func:`_packet_fn` for the streamed path.

    One whole-program compile per (transform, lanes, compression, chunk
    shape) that every chunk of every layer with that operand width reuses -
    the streamed packetizer pads its ragged final chunk up to the fixed
    chunk size precisely so this executable is hit on every call. Wrapping
    the shared vmap keeps the one-shot and streamed paths on a single
    transform kernel.
    """
    fn = _packet_vmap(transform, lanes, compression)

    def noc_packet_chunk(i, w):     # the trace's ``jit_noc_packet_chunk``
        return fn(i, w).astype(jnp.uint32)

    return _placed(transform, jax.jit(noc_packet_chunk))


def payload_shapes(
    layers: Sequence[LayerTraffic],
    lanes: int,
    variants: Sequence[Variant],
    *,
    max_packets_per_layer: Optional[int] = None,
    compression: str = "none",
) -> List[Tuple[int, int]]:
    """Per-layer ``(n_packets, payload_flits)`` without materializing any
    payloads: the flit geometry is probed on a single packet per variant.

    Lets the streamed path (and the sweep engine's stream-length padding)
    size everything up front at O(1) cost per layer. The probe holds under
    compression because MSR flit geometry is a pure function of the operand
    width (fixed 5-bit codes; escapes ride the sideband).
    """
    if not variants:
        raise ValueError("need at least one (transform, quantizer) variant")
    _check_compression(compression)
    out: List[Tuple[int, int]] = []
    for layer in layers:
        inp, wgt = _subsample(layer, max_packets_per_layer)
        # Geometry depends only on the operand width k, so a zero-packet
        # layer is probed with a dummy packet (matching the one-shot path,
        # which emits a (B, 0, F, L) array for it).
        i1, w1 = (inp[:1], wgt[:1]) if inp.shape[0] else (
            jnp.zeros((1,) + inp.shape[1:], inp.dtype),
            jnp.zeros((1,) + wgt.shape[1:], wgt.dtype))
        shapes = set()
        for tr, q in variants:
            i0, w0 = (i1, w1) if q is None else (q(i1), q(w1))
            shapes.add(tuple(_packet_words(tr, i0[0], w0[0], lanes,
                                           compression).shape))
        if len(shapes) != 1:
            raise ValueError(
                f"variants disagree on flit geometry: {sorted(shapes)}")
        (fpay, _), = shapes
        out.append((int(inp.shape[0]), int(fpay)))
    return out


def ordered_payloads_streamed(
    layers: Sequence[LayerTraffic],
    lanes: int,
    variants: Sequence[Variant],
    *,
    chunk_packets: int = 4096,
    max_packets_per_layer: Optional[int] = None,
    compression: str = "none",
):
    """Generator form of :func:`ordered_payloads` with bounded working set.

    Yields ``(layer_index, start_packet, words)`` where ``words`` is the
    ``(B, c, F, L)`` uint32 payload block for packets
    ``[start, start + c)`` of that layer, ``c <= chunk_packets``.
    Concatenating a layer's chunks is bit-identical to the one-shot path:
    quantizers are applied to the *whole* layer first (a fixed-point scale
    must not depend on the chunking), and the transform - per-packet by
    construction - runs through one jit-cached vmap whose ragged final
    chunk is zero-padded to the fixed chunk shape and sliced back. Each
    variant's quantization and each chunk's ordering up to host words is
    a ``noc.packetize.order`` span with its ``transform``.
    """
    if not variants:
        raise ValueError("need at least one (transform, quantizer) variant")
    if chunk_packets < 1:
        raise ValueError(f"chunk_packets must be >= 1, got {chunk_packets}")
    _check_compression(compression)
    for li, layer in enumerate(layers):
        inp, wgt = _subsample(layer, max_packets_per_layer)
        n = int(inp.shape[0])
        if n == 0:          # nothing to order or scatter for an empty layer
            continue
        ops = []
        for tr, q in variants:
            with span("noc.packetize.order", transform=tr.name):
                ops.append((inp, wgt) if q is None else (q(inp), q(wgt)))
        for start in range(0, n, chunk_packets):
            c = min(chunk_packets, n - start)
            per_variant = []
            for (tr, _), (qi, qw) in zip(variants, ops):
                with span("noc.packetize.order", transform=tr.name):
                    ci, cw = qi[start:start + c], qw[start:start + c]
                    if c < chunk_packets:
                        pad = ((0, chunk_packets - c), (0, 0))
                        ci, cw = jnp.pad(ci, pad), jnp.pad(cw, pad)
                    words = _packet_chunk_fn(tr, lanes, compression)(ci, cw)
                    per_variant.append(np.asarray(words)[:c])
            shapes = {w.shape for w in per_variant}
            if len(shapes) != 1:
                raise ValueError(
                    f"variants disagree on flit geometry: {sorted(shapes)}")
            yield li, start, np.stack(per_variant)


class _McSchedule:
    """Closed-form packet->MC schedule, elementwise in the global packet id.

    ``mc_table=None`` is the seed round-robin (``mc(g) = g % M``); an
    explicit table is Q-periodic: ``mc(g) = table[g % Q]``. The affinity
    path uses ``Q = num_pes`` (``topology.affinity_mc_table``), so a
    packet's serving MC follows its destination PE. Every quantity the
    assembler needs - the serving MC, and the number of earlier packets at
    that MC (which fixes the VC and the stream offset) - stays an
    elementwise function of ``g``, preserving chunk-decomposability.
    """

    def __init__(self, m: int, mc_table=None):
        if mc_table is None:
            tbl = np.arange(m, dtype=np.int64)
        else:
            tbl = np.asarray(mc_table, np.int64)
            if tbl.ndim != 1 or not tbl.size:
                raise ValueError("mc_table must be a non-empty 1-D array")
            if tbl.min() < 0 or tbl.max() >= m:
                raise ValueError(
                    f"mc_table entries must be MC stream indices in [0, {m})")
        self.m, self.q, self.tbl = m, len(tbl), tbl
        self.cnt = np.bincount(tbl, minlength=m).astype(np.int64)
        onehot = np.zeros((len(tbl) + 1, m), np.int64)
        onehot[np.arange(1, len(tbl) + 1), tbl] = 1
        self.cum = np.cumsum(onehot, axis=0)                 # (Q+1, M)

    def mc(self, g):
        """Serving-MC stream index of packet(s) ``g``."""
        return self.tbl[g % self.q]

    def before(self, g):
        """``#{g' < g : mc(g') == mc(g)}`` - earlier packets at g's MC."""
        mc = self.tbl[g % self.q]
        return (g // self.q) * self.cnt[mc] + self.cum[g % self.q, mc]

    def counts_before(self, g: int) -> np.ndarray:
        """Per-MC packet counts over ``[0, g)`` - an ``(M,)`` vector."""
        return (g // self.q) * self.cnt + self.cum[g % self.q]


def stream_lengths(layer_shapes: Sequence[Tuple[int, int]],
                   m: int, mc_table=None) -> np.ndarray:
    """Per-MC flit counts for layers of ``(n_packets, payload_flits)``.

    Closed-form: packets are dealt over the ``m`` MCs - round-robin by
    default, or by a periodic affinity ``mc_table`` (see
    :func:`repro.noc.topology.affinity_mc_table`) - each contributing its
    payload plus one header flit. Lets the sweep engine size stream
    padding without materializing any traffic.
    """
    sched = _McSchedule(m, mc_table)
    lengths = np.zeros(m, np.int64)
    g0 = 0
    for n, fpay in layer_shapes:
        counts = sched.counts_before(g0 + n) - sched.counts_before(g0)
        lengths += counts * (fpay + 1)
        g0 += n
    return lengths


def pad_traffic_length(traffic: Traffic, t: int) -> Traffic:
    """Pad the per-MC stream axis T with empty flits.

    Padding beyond ``length`` is never injected, so this only changes array
    shapes - the sweep engine uses it (with MC-stream padding) to give every
    MC placement of one mesh size identical traffic shapes, and therefore
    one shared compiled simulator.
    """
    cur = int(traffic.words.shape[-2])
    if t <= cur:
        return traffic
    extra = t - cur

    def pad_last(a):
        widths = [(0, 0)] * (a.ndim - 1) + [(0, extra)]
        return jnp.asarray(np.pad(np.asarray(a), widths))

    words = np.pad(np.asarray(traffic.words),
                   [(0, 0)] * (traffic.words.ndim - 2) + [(0, extra), (0, 0)])
    return traffic._replace(
        words=jnp.asarray(words), dest=pad_last(traffic.dest),
        meta=pad_last(traffic.meta), vc=pad_last(traffic.vc),
        pkt=pad_last(traffic.pkt))


def concat_inferences(traffic: Traffic, n: int) -> Traffic:
    """Replicate a single-inference Traffic ``n`` times back-to-back.

    Inference k's flits immediately follow inference k-1's within every
    stream (the injector walks streams contiguously), and packet ids are
    offset by ``k * num_packets`` so the per-inference conservation and
    timestamp ledgers stay disjoint - the closed-loop serving model
    (``repro.noc.online``) gates each inference's slice with its own
    release cycle. Unbatched Traffic with known ``num_packets`` only; the
    word values are replicated verbatim, so per-stream NI sequences are the
    single-inference sequences repeated (seam transitions between
    consecutive inferences included).
    """
    if traffic.length.ndim != 1:
        raise ValueError("concat_inferences wants an unbatched Traffic "
                         "(use .variant(i) on a batched one)")
    npkt = int(traffic.num_packets)
    if npkt < 0:
        raise ValueError("concat_inferences needs num_packets metadata "
                         "(hand-built Traffic must set it)")
    if n < 1:
        raise ValueError(f"need n >= 1 inferences, got {n}")
    if n == 1:
        return traffic
    lengths = np.asarray(traffic.length, np.int64)
    m = lengths.shape[0]
    lanes = traffic.words.shape[-1]
    t2 = int(lengths.max()) * n if m else 0
    words = np.asarray(traffic.words)
    dest = np.asarray(traffic.dest)
    meta = np.asarray(traffic.meta)
    vc = np.asarray(traffic.vc)
    pkt = np.asarray(traffic.pkt)
    w2 = np.zeros((m, t2, lanes), np.uint32)
    d2 = np.zeros((m, t2), np.int32)
    me2 = np.zeros((m, t2), np.int32)
    v2 = np.zeros((m, t2), np.int32)
    p2 = np.zeros((m, t2), np.int32)
    for mi in range(m):
        ln = int(lengths[mi])
        if not ln:
            continue
        w2[mi, :n * ln] = np.tile(words[mi, :ln], (n, 1))
        d2[mi, :n * ln] = np.tile(dest[mi, :ln], n)
        me2[mi, :n * ln] = np.tile(meta[mi, :ln], n)
        v2[mi, :n * ln] = np.tile(vc[mi, :ln], n)
        p2[mi, :n * ln] = (np.tile(pkt[mi, :ln], n)
                           + np.repeat(np.arange(n, dtype=np.int64), ln)
                           * npkt)
    return Traffic(
        words=jnp.asarray(w2), dest=jnp.asarray(d2), meta=jnp.asarray(me2),
        vc=jnp.asarray(v2), pkt=jnp.asarray(p2),
        length=jnp.asarray((lengths * n).astype(np.int32)),
        num_packets=npkt * n)


def filter_packets(traffic: Traffic, keep_ids) -> Traffic:
    """Keep only the flits of the given packet ids, compacting each stream.

    ``keep_ids``: packet ids to retain (array-like of ints, or a boolean
    mask of length ``num_packets``). Flits of other packets are removed
    and each stream's survivors slide forward in original order (the NI
    walks streams contiguously); ``length`` shrinks accordingly, the tail
    is zeroed padding, and ``num_packets`` is preserved - surviving
    packets keep their ids/ledger slots. The fault-injection layer uses
    this twice: to drop packets to unreachable destinations before
    injection, and to build retransmission traffic from the original
    (clean) flits of detected-corrupt packets. Unbatched Traffic only.
    """
    if traffic.length.ndim != 1:
        raise ValueError("filter_packets wants an unbatched Traffic "
                         "(use .variant(i) on a batched one)")
    npkt = int(traffic.num_packets)
    if npkt < 0:
        raise ValueError("filter_packets needs num_packets metadata "
                         "(hand-built Traffic must set it)")
    keep_ids = np.asarray(keep_ids)
    if keep_ids.dtype == bool:
        keep_pkt = keep_ids
        if keep_pkt.shape != (npkt,):
            raise ValueError(f"boolean keep mask must have shape ({npkt},), "
                             f"got {keep_pkt.shape}")
    else:
        keep_pkt = np.zeros(npkt, bool)
        keep_pkt[keep_ids.astype(np.int64)] = True
    lengths = np.asarray(traffic.length, np.int64)
    m, t = np.asarray(traffic.meta).shape
    valid = np.arange(t)[None, :] < lengths[:, None]
    pkt = np.asarray(traffic.pkt)
    keep = valid & keep_pkt[np.clip(pkt, 0, npkt - 1)]
    # Stable compaction: kept flits first, original order preserved.
    order = np.argsort(~keep, axis=1, kind="stable")
    rows = np.arange(m)[:, None]
    new_len = keep.sum(axis=1).astype(np.int32)
    live = np.arange(t)[None, :] < new_len[:, None]

    def take(a, fill=0):
        out = np.asarray(a)[rows, order]
        return np.where(live, out, fill)

    words = np.asarray(traffic.words)[rows, order]      # (M, T, L) rows move
    words = np.where(live[..., None], words, 0).astype(np.uint32)
    return Traffic(
        words=jnp.asarray(words),
        dest=jnp.asarray(take(traffic.dest).astype(np.int32)),
        meta=jnp.asarray(take(traffic.meta).astype(np.int32)),
        vc=jnp.asarray(take(traffic.vc).astype(np.int32)),
        pkt=jnp.asarray(take(traffic.pkt).astype(np.int32)),
        length=jnp.asarray(new_len),
        num_packets=npkt)


def stack_traffics(traffics: Sequence[Traffic]) -> Traffic:
    """Stack single (unbatched) Traffics into one batched Traffic.

    The lanes may carry different real lengths (each keeps its ``length``
    row - this is how heterogeneous-drain batches for the retirement
    scheduler are built); their stream axes are padded to the longest T
    first. ``num_packets`` becomes the max, which is what the conservation
    ledger needs to cover every lane.
    """
    if not traffics:
        raise ValueError("need at least one Traffic to stack")
    t = max(int(tr.words.shape[-2]) for tr in traffics)
    traffics = [pad_traffic_length(tr, t) for tr in traffics]
    return Traffic(
        words=jnp.stack([tr.words for tr in traffics]),
        dest=jnp.stack([tr.dest for tr in traffics]),
        meta=jnp.stack([tr.meta for tr in traffics]),
        vc=jnp.stack([tr.vc for tr in traffics]),
        pkt=jnp.stack([tr.pkt for tr in traffics]),
        length=jnp.stack([tr.length for tr in traffics]),
        num_packets=max(int(tr.num_packets) for tr in traffics))


class TrafficAssembler:
    """Incremental per-MC stream writer - the scatter half of packetization,
    shared verbatim by the one-shot (:func:`assemble_traffic`) and streamed
    (:func:`build_traffic_streamed`) paths, so the two are bit-identical by
    construction.

    Closed-form skeleton. With global packet id g (consecutive across
    layers), the seed loop's bookkeeping collapses to
        mc(g)   = g % M                 (packet round-robin over MCs, or an
                                         affinity ``mc_table`` lookup)
        dest(g) = pes[g % num_pes]      (pe_rr increments once per packet)
        vc(g)   = before(g) % V         (vc_rr[mc] counts packets at mc;
                                         = (g // M) % V for round-robin)
    and a packet's flit offset inside its MC stream is the running flit
    count of earlier packets at that MC. Every quantity is elementwise in
    g, so a layer may arrive in any number of packet chunks: each chunk
    scatters into its final location independently.
    """

    def __init__(self, layer_shapes: Sequence[Tuple[int, int]],
                 cfg: NocConfig, num_streams: Optional[int] = None,
                 num_variants: int = 1, mc_table=None):
        m, lanes = cfg.num_mcs, cfg.lanes
        if num_streams is not None and num_streams < m:
            raise ValueError(
                f"cannot pad {m} MC streams down to {num_streams}")
        self.cfg = cfg
        self.nv = num_variants
        self.num_streams = num_streams
        self.shapes = [(int(n), int(f)) for n, f in layer_shapes]
        self.pes = np.asarray(cfg.pe_nodes, np.int64)
        self.sched = _McSchedule(m, mc_table)
        # Per-layer global packet offset, per-MC flit base and per-MC packet
        # count at layer start.
        ns = [n for n, _ in self.shapes]
        self.layer_g0 = np.concatenate(
            [[0], np.cumsum(ns)]).astype(np.int64)
        self.layer_cb = [self.sched.counts_before(int(g0))
                         for g0 in self.layer_g0]
        self.layer_base = [np.zeros(m, np.int64)]
        lengths = np.zeros(m, np.int64)
        for (n, fpay), cb0, cb1 in zip(self.shapes, self.layer_cb,
                                       self.layer_cb[1:]):
            lengths = lengths + (cb1 - cb0) * (fpay + 1)
            self.layer_base.append(lengths.copy())
        self.lengths = lengths
        t = int(lengths.max()) if m else 0
        self.words = np.zeros((self.nv, m, t, lanes), np.uint32)
        self.dest = np.zeros((m, t), np.int32)
        self.meta = np.zeros((m, t), np.int32)
        self.vc = np.zeros((m, t), np.int32)
        self.pkt = np.zeros((m, t), np.int32)

    def add_chunk(self, layer: int, start: int, words: np.ndarray) -> None:
        """Scatter payload ``words`` (B, c, F, L) for packets
        ``[start, start + c)`` of ``layer`` into the per-MC streams."""
        cfg, lanes = self.cfg, self.cfg.lanes
        n_l, fpay = self.shapes[layer]
        if words.shape[0] != self.nv:
            raise ValueError(f"payload chunk has {words.shape[0]} variants, "
                             f"assembler was sized for {self.nv}")
        if words.shape[2] != fpay or words.shape[3] != lanes:
            raise ValueError(
                f"payload chunk {words.shape[2:]} does not match layer "
                f"{layer} geometry ({fpay}, {lanes})")
        c = words.shape[1]
        if start < 0 or start + c > n_l:
            raise ValueError(f"chunk [{start}, {start + c}) out of range for "
                             f"layer {layer} with {n_l} packets")
        if c == 0:
            return
        f = fpay + 1                                    # + header flit
        g0 = self.layer_g0[layer]
        gids = g0 + start + np.arange(c, dtype=np.int64)
        mcs = self.sched.mc(gids)
        dest = self.pes[gids % len(self.pes)].astype(np.int32)
        before = self.sched.before(gids)
        vc = (before % cfg.num_vcs).astype(np.int32)
        # Rank of each packet among this layer's packets at its MC: earlier
        # packets at the MC minus the count at layer start.
        rank = before - self.layer_cb[layer][mcs]
        flit0 = self.layer_base[layer][mcs] + rank * f  # (c,) stream offset
        cols = (flit0[:, None] + np.arange(f)[None, :]).reshape(-1)
        rows = np.repeat(mcs, f)

        # Header synthesis: word 0 = dest, 1 = packet id, 2 = payload flits.
        hdr = np.zeros((c, lanes), np.uint32)
        hdr[:, 0] = dest.astype(np.uint32)
        hdr[:, 1] = (gids & 0xFFFFFFFF).astype(np.uint32)
        hdr[:, 2] = fpay
        full = np.empty((self.nv, c, f, lanes), np.uint32)
        full[:, :, 0, :] = hdr[None]
        full[:, :, 1:, :] = words

        # META bitfield: header 0, payload flits PAYLOAD, last flit |= TAIL.
        md = np.full((f,), META_PAYLOAD, np.int32)
        md[0] = 0
        md[-1] |= META_TAIL

        self.words[:, rows, cols] = full.reshape(self.nv, c * f, lanes)
        self.dest[rows, cols] = np.repeat(dest, f)
        self.meta[rows, cols] = np.broadcast_to(md, (c, f)).reshape(-1)
        self.vc[rows, cols] = np.repeat(vc, f)
        self.pkt[rows, cols] = np.repeat(gids.astype(np.int32), f)

    def finish(self) -> Traffic:
        """Batched Traffic over everything scattered so far (empty padding
        streams appended per ``num_streams``)."""
        m, lanes, t = self.cfg.num_mcs, self.cfg.lanes, self.words.shape[2]
        words_arr, lengths = self.words, self.lengths
        dest_arr, meta_arr = self.dest, self.meta
        vc_arr, pkt_arr = self.vc, self.pkt
        if self.num_streams is not None and self.num_streams > m:
            extra = self.num_streams - m
            words_arr = np.concatenate(
                [words_arr, np.zeros((self.nv, extra, t, lanes), np.uint32)],
                axis=1)
            pad2 = ((0, extra), (0, 0))
            dest_arr = np.pad(dest_arr, pad2)
            meta_arr = np.pad(meta_arr, pad2)
            vc_arr = np.pad(vc_arr, pad2)
            pkt_arr = np.pad(pkt_arr, pad2)
            lengths = np.pad(lengths, (0, extra))

        def tile(a):
            return jnp.asarray(np.broadcast_to(a, (self.nv,) + a.shape))

        return Traffic(
            words=jnp.asarray(words_arr), dest=tile(dest_arr),
            meta=tile(meta_arr), vc=tile(vc_arr), pkt=tile(pkt_arr),
            length=tile(lengths.astype(np.int32)),
            num_packets=int(self.layer_g0[-1]))


def assemble_traffic(layer_words: Sequence[np.ndarray],
                     cfg: NocConfig,
                     num_streams: Optional[int] = None,
                     num_variants: Optional[int] = None,
                     mc_table=None) -> Traffic:
    """Scatter per-layer (B, n, F, L) payloads into batched per-MC streams.

    All variants share the packetization skeleton (headers, META bitfields,
    MC/PE/VC round-robin, per-MC scatter layout): an ordering transform only
    permutes values within a packet and a quantizer only narrows them, so
    the flit geometry - and therefore dest/meta/vc/pkt/length - is variant-
    independent. Only the payload words differ per variant. The result
    feeds :func:`repro.noc.sim.simulate_batch` directly.

    num_streams: pad the MC-stream axis to this count with empty streams
        (packets still round-robin over the config's real MCs). The sweep
        engine pads every placement of one mesh size to a common count so
        they share a single compiled simulator.
    num_variants: the variants-axis size when ``layer_words`` is empty (it
        is otherwise read off the payload arrays).
    mc_table: optional periodic packet->MC assignment (the affinity knob;
        see :func:`repro.noc.topology.affinity_mc_table`). ``None`` keeps
        the seed round-robin deal.
    """
    nv = layer_words[0].shape[0] if layer_words else (num_variants or 1)
    for words_v in layer_words:
        if words_v.shape[3] != cfg.lanes:
            raise ValueError(f"payloads built for {words_v.shape[3]} lanes, "
                             f"config has {cfg.lanes}")
    asm = TrafficAssembler([(w.shape[1], w.shape[2]) for w in layer_words],
                           cfg, num_streams=num_streams, num_variants=nv,
                           mc_table=mc_table)
    for li, words_v in enumerate(layer_words):
        asm.add_chunk(li, 0, words_v)
    return asm.finish()


def build_traffic_streamed(
    layers: Sequence[LayerTraffic],
    cfg: NocConfig,
    variants: Sequence[Variant],
    *,
    chunk_packets: int = 4096,
    num_streams: Optional[int] = None,
    max_packets_per_layer: Optional[int] = None,
    shapes: Optional[Sequence[Tuple[int, int]]] = None,
    mc_table=None,
    compression: str = "none",
) -> Traffic:
    """Packetize full (DarkNet-scale) layers in fixed-size packet chunks.

    Bit-identical to ``build_traffic_batch`` (property-tested in
    tests/test_noc_stream.py) with a bounded packetization working set: the
    one-shot path materializes every layer's (B, n, F, L) payload tensor
    *plus* the transform's whole-layer intermediates before assembling,
    while this path holds one (B, chunk_packets, F, L) block at a time and
    scatters it straight into its final stream location. The dense output
    Traffic is the same either way - it is the input to the simulator.

    shapes: precomputed :func:`payload_shapes` result for these layers /
        variants (the sweep engine already has it for padding); probed here
        when omitted.
    mc_table: optional periodic packet->MC affinity assignment (every
        skeleton quantity stays elementwise in the global packet id, so
        the streamed path supports affinity unchanged).
    """
    return build_traffic_streamed_multi(
        layers, [cfg], variants, chunk_packets=chunk_packets,
        num_streams=num_streams, max_packets_per_layer=max_packets_per_layer,
        shapes=shapes, mc_tables=[mc_table], compression=compression)[0]


def build_traffic_streamed_multi(
    layers: Sequence[LayerTraffic],
    cfgs: Sequence[NocConfig],
    variants: Sequence[Variant],
    *,
    chunk_packets: int = 4096,
    num_streams: Optional[int] = None,
    max_packets_per_layer: Optional[int] = None,
    shapes: Optional[Sequence[Tuple[int, int]]] = None,
    mc_tables: Optional[Sequence] = None,
    compression: str = "none",
) -> List[Traffic]:
    """Streamed packetization for SEVERAL (config, mc_table) combos at once.

    Ordering dominates streamed packetization and is mesh-independent (the
    transform sees only packet payloads and the flit width), so one
    :func:`ordered_payloads_streamed` pass feeds every combo's assembler -
    each chunk is ordered once and scattered into all N stream layouts,
    instead of the N full re-ordering passes N separate
    :func:`build_traffic_streamed` calls would pay. All configs must share
    the flit lane width (they are placement/affinity variants of one mesh
    size in the sweep engine). Element i of the result is bit-identical to
    ``build_traffic_streamed(layers, cfgs[i], ..., mc_table=mc_tables[i])``.
    """
    if not cfgs:
        raise ValueError("need at least one config")
    if len({c.lanes for c in cfgs}) != 1:
        raise ValueError("streamed combos must share the flit lane width")
    if mc_tables is None:
        mc_tables = [None] * len(cfgs)
    if len(mc_tables) != len(cfgs):
        raise ValueError("mc_tables must match cfgs")
    if shapes is None:
        shapes = payload_shapes(layers, cfgs[0].lanes, variants,
                                max_packets_per_layer=max_packets_per_layer,
                                compression=compression)
    with span("noc.packetize.assemble"):
        asms = [TrafficAssembler(shapes, cfg, num_streams=num_streams,
                                 num_variants=len(variants), mc_table=tbl)
                for cfg, tbl in zip(cfgs, mc_tables)]
    for li, start, words in ordered_payloads_streamed(
            layers, cfgs[0].lanes, variants, chunk_packets=chunk_packets,
            max_packets_per_layer=max_packets_per_layer,
            compression=compression):
        with span("noc.packetize.assemble"):
            for asm in asms:
                asm.add_chunk(li, start, words)
    with span("noc.packetize.assemble"):
        return [asm.finish() for asm in asms]


def build_traffic_batch(
    layers: Sequence[LayerTraffic],
    cfg: NocConfig,
    variants: Sequence[Variant],
    *,
    max_packets_per_layer: Optional[int] = None,
    mc_table=None,
    compression: str = "none",
) -> Traffic:
    """Packetize ``layers`` once per (transform, quantizer) variant into a
    batched Traffic with a leading variants axis (see
    :func:`ordered_payloads` / :func:`assemble_traffic`)."""
    payloads = ordered_payloads(layers, cfg.lanes, variants,
                                max_packets_per_layer=max_packets_per_layer,
                                compression=compression)
    return assemble_traffic(payloads, cfg, num_variants=len(variants),
                            mc_table=mc_table)


def build_traffic(
    layers: Sequence[LayerTraffic],
    cfg: NocConfig,
    transform: WireTransform,
    *,
    quantizer=None,
    max_packets_per_layer: Optional[int] = None,
    compression: str = "none",
) -> Traffic:
    """Packetize layers under a WireTransform into per-MC injection streams.

    quantizer: optional value -> wire-dtype map (e.g. fixed-8 quantization);
        default transmits raw float32 words.
    max_packets_per_layer: subsample neurons (deterministic stride) to bound
        simulation time; BT rates are per-flit so subsampling is unbiased.
    compression: ``"none"`` (the default, bit-identical to the seed loop
        implementation, pinned against ``repro.noc._reference``) or
        ``"msr"`` (8b->5b MSR payload codes; needs an 8-bit quantizer).
    """
    batch = build_traffic_batch(layers, cfg, [(transform, quantizer)],
                                max_packets_per_layer=max_packets_per_layer,
                                compression=compression)
    return batch.variant(0)


def compression_overhead(layers: Sequence[LayerTraffic], quantizer,
                         lanes: int, compression: str, *,
                         max_packets_per_layer: Optional[int] = None) -> int:
    """Total escape/metadata bits the request phase owes under
    ``compression`` - 0 for ``"none"``.

    Each packet transmits two independent half-flit windows (inputs left,
    weights right), each padded to the lane-rounded slot count
    ``ceil(k / (lanes/2)) * (lanes/2)``; MSR charges a per-window outlier
    count plus a (position, top-bits) record per outlier
    (:func:`repro.core.wire.compression_overhead_bits`). Outlier status is
    per-value and every WireTransform only permutes values within the
    window, so the charge is identical across the whole transform axis -
    an honest adjusted-BT comparison adds it at half a transition per bit,
    exactly like the O2 recovery index.
    """
    _check_compression(compression)
    if compression == "none":
        return 0
    half = lanes // 2
    total = 0
    for layer in layers:
        inp, wgt = _subsample(layer, max_packets_per_layer)
        if inp.shape[0] == 0:
            continue
        if quantizer is not None:
            inp, wgt = quantizer(inp), quantizer(wgt)
        k = int(inp.shape[1])
        window = -(-k // half) * half
        total += compression_overhead_bits(compression, np.asarray(inp),
                                           window)
        total += compression_overhead_bits(compression, np.asarray(wgt),
                                           window)
    return total


# --- result phase: PE -> MC ejection traffic -------------------------------

# Result values per result packet (the result ordering window). Four payload
# flits at the paper's 16-lane links: long enough that ordering has material
# freedom, short enough that a PE never waits long to flush toward memory.
DEFAULT_RESULT_WINDOW = 64


def layer_results(layer: LayerTraffic,
                  max_packets: Optional[int] = None) -> jax.Array:
    """Per-neuron result values of one layer: the MAC of each packet's
    operand pairs, ``result(g) = sum_k inputs[g, k] * weights[g, k]``.

    This is the single value PE ``dest(g)`` ejects back toward memory for
    request packet ``g`` - the model-geometry-derived payload of the result
    phase. ``max_packets`` applies the same deterministic-stride neuron
    subsampling as the request packetizer so the two phases stay aligned
    on the same global packet ids.
    """
    inp, wgt = _subsample(layer, max_packets)
    return jnp.sum(inp.astype(jnp.float32) * wgt.astype(jnp.float32), axis=1)


def result_values(
    layers: Sequence[LayerTraffic],
    variants: Sequence[Variant],
    max_packets_per_layer: Optional[int] = None,
) -> List[List[jax.Array]]:
    """Per-layer, per-variant result value arrays - the ``values`` input of
    :func:`build_result_traffic`, computed once and reused across every
    mesh/placement/affinity cell of a sweep."""
    out: List[List[jax.Array]] = []
    for layer in layers:
        res = layer_results(layer, max_packets_per_layer)
        out.append([res if q is None else q(res) for _, q in variants])
    return out


@functools.lru_cache(maxsize=None)
def _result_packet_fn(transform: WireTransform, lanes: int,
                      compression: str = "none"):
    """Vmapped single-stream packet transform for result payloads,
    memoized per (transform, lanes, compression) exactly like
    :func:`_packet_fn`."""

    def one_packet(vals):
        if compression == "none":
            return transform.apply_single(vals, lanes).words
        return msr.msr_pack(transform.order_single(vals, lanes), lanes).words

    return _placed(transform, jax.vmap(one_packet))


def build_result_traffic(
    layers: Sequence[LayerTraffic],
    cfg: NocConfig,
    variants: Sequence[Variant],
    *,
    max_packets_per_layer: Optional[int] = None,
    mc_table=None,
    result_window: Optional[int] = None,
    num_streams: Optional[int] = None,
    values: Optional[Sequence[Sequence[jax.Array]]] = None,
    compression: str = "none",
) -> Traffic:
    """Packetize the result phase: per-PE injection streams of PE->MC
    result packets, as a batched Traffic (leading variants axis).

    values: optional precomputed per-layer result values, one per-variant
        list of ``(n,)`` arrays per layer (:func:`layer_results` plus each
        variant's quantizer). Result values depend only on the layers and
        variants - never on the mesh, placement, or affinity - so the
        sweep engine computes them once per model and reuses them across
        every (placement, affinity) combo.

    The request phase computes neuron ``g`` at PE ``pes[g % num_pes]`` with
    operands served by MC ``mc(g)`` (round-robin, or the affinity
    ``mc_table``). The result phase returns each neuron's single MAC value
    (:func:`layer_results`) along the opposite path: stream ``i`` injects
    at ``cfg.pe_nodes[i]`` and every packet's destination is the *serving
    MC* of the neurons it carries, so request and result traffic traverse
    the same MC<->PE pairs in opposite directions.

    A result packet groups up to ``result_window`` consecutive results of
    one (PE, MC) pair within one layer (layer boundaries flush partial
    windows - results of a layer return before the next layer's). The
    window is the ordering window: each variant's transform orders the
    packet's result values via ``WireTransform.apply_single`` (O0 keeps
    arrival order, O1/O2 sort by popcount) and its quantizer narrows them,
    mirroring the request-side contract. All variants share the skeleton
    (dest/meta/vc/pkt/length); only payload words differ.

    num_streams: pad the PE-stream axis to this count with empty streams
        (the sweep engine gives every placement of one mesh size a common
        stream count so result drains share one executable).

    Feeds :func:`repro.noc.sim.simulate_batch` with
    ``mc_nodes=cfg.pe_nodes`` (padded with zeros for padding streams) -
    the injection-node argument names the *sources*, which for this phase
    are the PEs. Packet conservation (``check_conservation=True``) works
    unchanged: result packets number ``0..num_packets-1`` and every one
    must eject exactly once at its MC.
    """
    if not variants:
        raise ValueError("need at least one (transform, quantizer) variant")
    _check_compression(compression)
    m, lanes, nv = cfg.num_mcs, cfg.lanes, len(variants)
    pes = np.asarray(cfg.pe_nodes, np.int64)
    p = len(pes)
    if num_streams is not None and num_streams < p:
        raise ValueError(f"cannot pad {p} PE streams down to {num_streams}")
    w = DEFAULT_RESULT_WINDOW if result_window is None else int(result_window)
    if w < 1:
        raise ValueError(f"result_window must be >= 1, got {w}")
    sched = _McSchedule(m, mc_table)
    mcs_nodes = np.asarray(cfg.mc_nodes, np.int64)
    # Payload flits per full window; under MSR compression the window's
    # 5-bit codes pack into ceil(5 * slots / 8) bytes of 8-bit lanes.
    fw = (-(-w // lanes) if compression == "none"
          else msr.compressed_payload_flits(w, lanes))

    # Like the request-phase TrafficAssembler, assembly is scatters, not a
    # per-packet loop: each layer contributes one flat (stream row, flit
    # col, value) scatter, with per-stream running flit/packet counters
    # carrying the state between layers.
    stream_len = np.zeros(p, np.int64)        # flits written per stream
    stream_pkts = np.zeros(p, np.int64)       # packets per stream (-> VC)
    scatters = []                             # per-layer scatter payloads
    pkt_id = 0
    g0 = 0
    for li, layer in enumerate(layers):
        n = int(_subsample(layer, max_packets_per_layer)[0].shape[0])
        if n == 0:
            continue
        if values is not None:
            vals = values[li]
        else:
            res = layer_results(layer, max_packets_per_layer)
            vals = [res if q is None else q(res) for _, q in variants]

        gids = g0 + np.arange(n, dtype=np.int64)
        g0 += n
        src = (gids % p).astype(np.int64)            # PE stream index
        mcidx = sched.mc(gids)
        key = src * m + mcidx
        order = np.argsort(key, kind="stable")       # group-major, g-order
        ksort = key[order]
        uniq, start, counts = np.unique(ksort, return_index=True,
                                        return_counts=True)
        grp = np.repeat(np.arange(len(uniq)), counts)
        rank = np.arange(n) - np.repeat(start, counts)
        pkts_per_grp = -(-counts // w)
        pkt_base = np.concatenate([[0], np.cumsum(pkts_per_grp)])
        row = pkt_base[grp] + rank // w              # packet row per neuron
        col = rank % w
        npkt = int(pkt_base[-1])

        # One uniform-window transform vmap per variant; padding zeros end
        # up in the tail flits under every transform (popcount 0 sorts last
        # for O1/O2; the O3 deal confines the chained non-zeros to the
        # first ceil(z / lanes) * lanes slots), so slicing each packet to
        # its real flit count is exact. Under MSR the kept flits cover the
        # lane-rounded slot count's code bytes, which by the same argument
        # hold every non-zero code; dropped bytes pack only zero codes.
        mats = []
        for v in vals:
            mat = np.zeros((npkt, w), np.asarray(v).dtype)
            mat[row, col] = np.asarray(v)[order]
            mats.append(mat)
        words_v = [np.asarray(_result_packet_fn(tr, lanes, compression)(
            jnp.asarray(mat)).astype(jnp.uint32))
            for (tr, _), mat in zip(variants, mats)]
        shapes = {wv.shape for wv in words_v}
        if shapes != {(npkt, fw, lanes)}:
            raise ValueError(
                f"variants disagree on result flit geometry: {sorted(shapes)}")
        words_v = np.stack(words_v)                  # (nv, npkt, fw, L)

        # Per-packet skeleton, in (pe, mc, window) order = stream order.
        pk_grp = np.repeat(np.arange(len(uniq)), pkts_per_grp)
        pk_src = uniq[pk_grp] // m
        pk_mc = uniq[pk_grp] % m
        pk_idx = np.arange(npkt) - pkt_base[pk_grp]  # window index in group
        pk_c = np.minimum(counts[pk_grp] - pk_idx * w, w)
        pk_fpay = (np.asarray((-(-pk_c // lanes)) if compression == "none"
                              else msr.compressed_payload_flits(pk_c, lanes))
                   ).astype(np.int64)
        f_tot = pk_fpay + 1                          # + header flit
        dest_pk = mcs_nodes[pk_mc].astype(np.int32)
        ids_pk = (pkt_id + np.arange(npkt)).astype(np.int64)

        # Packets sorted by src, so each stream's packets of this layer
        # are one contiguous run: within-stream rank gives the VC, the
        # exclusive flit cumsum (rebased per run) the stream offset.
        s_counts = np.bincount(pk_src, minlength=p)
        s_first = np.concatenate([[0], np.cumsum(s_counts)])[:-1]
        within = np.arange(npkt) - np.repeat(s_first, s_counts)
        vc_pk = ((stream_pkts[pk_src] + within) % cfg.num_vcs).astype(np.int32)
        fcum = np.cumsum(f_tot) - f_tot              # exclusive, pk order
        run0 = fcum[np.minimum(s_first, max(npkt - 1, 0))]
        flit0 = stream_len[pk_src] + fcum - np.repeat(run0, s_counts)

        # Flat flit axis: j = flit index within its packet (0 = header).
        total_f = int(f_tot.sum())
        fl_pk = np.repeat(np.arange(npkt), f_tot)
        pk_f0 = np.concatenate([[0], np.cumsum(f_tot)])[:-1]
        j = np.arange(total_f) - np.repeat(pk_f0, f_tot)
        hdr = j == 0
        md = np.where(hdr, 0, META_PAYLOAD).astype(np.int32)
        md[j == f_tot[fl_pk] - 1] |= META_TAIL
        flit_words = words_v[:, fl_pk, np.maximum(j - 1, 0)]  # (nv, F, L)
        hdr_words = np.zeros((npkt, lanes), np.uint32)
        hdr_words[:, 0] = dest_pk.astype(np.uint32)
        hdr_words[:, 1] = (ids_pk & 0xFFFFFFFF).astype(np.uint32)
        hdr_words[:, 2] = pk_fpay
        flit_words[:, hdr] = hdr_words

        scatters.append((pk_src[fl_pk], flit0[fl_pk] + j, flit_words,
                         dest_pk[fl_pk], md, vc_pk[fl_pk],
                         ids_pk[fl_pk].astype(np.int32)))
        stream_len += np.bincount(pk_src, weights=f_tot,
                                  minlength=p).astype(np.int64)
        stream_pkts += s_counts
        pkt_id += npkt

    t = int(stream_len.max()) if p and stream_len.size else 0
    ns = num_streams if num_streams is not None else p
    words_arr = np.zeros((nv, ns, t, lanes), np.uint32)
    dest_arr = np.zeros((ns, t), np.int32)
    meta_arr = np.zeros((ns, t), np.int32)
    vc_arr = np.zeros((ns, t), np.int32)
    pkt_arr = np.zeros((ns, t), np.int32)
    for rows, cols, flit_words, dest_f, md, vc_f, pkt_f in scatters:
        words_arr[:, rows, cols] = flit_words
        dest_arr[rows, cols] = dest_f
        meta_arr[rows, cols] = md
        vc_arr[rows, cols] = vc_f
        pkt_arr[rows, cols] = pkt_f

    def tile(a):
        return jnp.asarray(np.broadcast_to(a, (nv,) + a.shape))

    lengths = np.pad(stream_len, (0, ns - p))
    return Traffic(
        words=jnp.asarray(words_arr), dest=tile(dest_arr),
        meta=tile(meta_arr), vc=tile(vc_arr), pkt=tile(pkt_arr),
        length=tile(lengths.astype(np.int32)), num_packets=pkt_id)
