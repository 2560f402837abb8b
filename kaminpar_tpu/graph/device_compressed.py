"""Device-resident compressed graph view — the TeraPart *compute* tier.

Reference: ``kaminpar-shm/datastructures/compressed_graph.h:409`` — the
reference's kernels iterate neighborhoods straight off the compressed
stream (``adjacent_nodes`` decodes varint gaps in-loop), so the dense CSR
never exists at the finest levels.  Our fixed-bit-width gap encoding
(graph/compressed.py) was designed for exactly this on TPU: decoding one
edge is ONE word-gather (two words when the gap straddles a word boundary)
plus shifts/masks — no data-dependent control flow — so the decode fuses
into the vectorized LP kernels.

This module owns the device half:

- :class:`DeviceCompressedView` — the packed word stream plus per-node
  ``(word_start, width, degree, node_w)`` resident in HBM, node arrays
  padded on the PR 1 geometric shape ladder (``n_pad`` matches what the
  dense ``PaddedView`` of the same graph would use, so labels / LP states
  share kernel shapes with the dense path) and the word stream padded on
  its own bucket dimension.  Non-uniform edge weights stay an uncompressed
  (m-sized) side stream, exactly like the reference's weighted graphs —
  the structural arrays (col_idx + edge_u + the bucketed neighbor
  matrices, 2/3 of the dense bytes) are still never materialized.
- a *compressed bucketed layout* mirroring graph/bucketed.py: nodes are
  grouped into the identical degree buckets (same merge cascade, same
  ascending order, same ``R_pad``/``gather_idx``), but each bucket row
  stores only ``(word_start, width, degree, edge_start)`` — the (R, w)
  neighbor matrix is materialized *inside* the consuming kernel by
  :func:`decode_rows`.  Heavy rows (degree > MAX_WIDTH) stay dense (they
  are rare and already take the flat edge-parallel path, mirroring the
  reference's two-phase LP split).
- in-trace decode helpers shared by the XLA oracle twin (ops/lp.py), the
  fused Pallas rate kernel (ops/pallas_lp.py), and the contraction /
  re-materialization paths (:func:`decode_flat_padded`).

Envelope: the 32-bit build with LP clustering (v-cycle community
restriction needs per-edge masking the stream does not carry; HEM walks
matchings host-side).  ``GraphCompressionContext.device_decode`` gates the
routing with the dense path as fallback (see :func:`resolve_device_decode`).

Bit-identity contract (asserted in tests/test_device_compressed.py): the
decoded bucket matrices equal the dense bucketed view of the decompressed
graph bit for bit — same cols, weights, pad conventions, gather_idx — so
every downstream kernel (rating, auction, commit) is byte-compatible and
``device_decode=finest`` partitions are identical to the dense path.
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.intmath import next_pow2, next_shape_bucket
from .bucketed import HeavyPart
from .compressed import CompressedGraph


class CompressedStream(NamedTuple):
    """The device-resident byte streams: packed gap words plus the
    (uncompressed) edge-weight side stream.  ``edge_w`` is a (1,) zero
    dummy when the graph's weights are uniform all-1 — its *shape* is the
    trace-time weighted/unweighted switch, so no extra static argument
    threads through the kernel entry points."""

    words: jax.Array  # (W_pad,) uint32 packed zig-zag gaps
    edge_w: jax.Array  # (m_pad,) weights in decode order, or (1,) dummy

    @property
    def weighted(self) -> bool:
        return int(self.edge_w.shape[0]) > 1


class CompressedBucket(NamedTuple):
    """One degree bucket of the compressed layout: per-row decode metadata
    instead of the dense (R, w) neighbor matrix.  ``slot`` is a (w,) iota
    whose *shape* carries the bucket width into jitted consumers (its
    contents are never read)."""

    nodes: jax.Array  # (R_pad,) node id per row (pad rows -> anchor)
    wstart: jax.Array  # (R_pad,) first word of the row's gap stream
    width: jax.Array  # (R_pad,) bits per gap (pad rows -> 1)
    deg: jax.Array  # (R_pad,) degree (pad rows -> 0)
    estart: jax.Array  # (R_pad,) first edge slot (weight-stream gather base)
    slot: jax.Array  # (w,) static width carrier


# -- in-trace decode --------------------------------------------------------


def _funnel_unpack(words, w0, bit_in_word, wd):
    """Extract the ``wd``-bit zig-zag value starting at ``bit_in_word`` of
    word ``w0`` and return the signed gap — the per-edge shift/mask core.
    32-bit only (no uint64), so the math lowers identically with and
    without jax x64.  Shared with the dist tier's per-shard in-trace
    decode (dist/device_compressed.decode_shard_adjacency, round 15) —
    any change here must keep the signed shard-relative-gap case exact."""
    s0 = jnp.clip(w0, 0, words.shape[0] - 2)
    sh = bit_in_word.astype(jnp.uint32)
    lo = words[s0]
    hi = words[s0 + 1]
    lo_part = jnp.right_shift(lo, sh)
    hi_part = jnp.where(
        sh == jnp.uint32(0),
        jnp.uint32(0),
        jnp.left_shift(hi, (jnp.uint32(32) - sh) & jnp.uint32(31)),
    )
    mask = jnp.right_shift(
        jnp.uint32(0xFFFFFFFF), jnp.uint32(32) - wd.astype(jnp.uint32)
    )
    z = (lo_part | hi_part) & mask
    return jnp.right_shift(z, jnp.uint32(1)).astype(jnp.int32) ^ -(
        (z & jnp.uint32(1)).astype(jnp.int32)
    )


def decode_rows(stream: CompressedStream, nodes, wstart, width, deg, estart,
                w: int, wdtype):
    """Materialize the (R, w) neighbor matrix of one bucket from the packed
    word stream — pure jnp, traced inside the consuming jit / Pallas kernel.

    Per slot: one gather of two consecutive words + shift/mask (the gap
    straddles at most one word boundary because widths are <= 32), zig-zag
    decode, then a row cumsum turns gaps into absolute neighbor ids (the
    first gap is relative to the node id).  Weights come from the
    uncompressed side stream (one more gather) or are the constant 1.  Pad
    slots reproduce the dense bucket conventions exactly: ``col = the
    row's own node id`` with weight 0 (pad rows decode to all-anchor rows).
    """
    R = nodes.shape[0]
    slot = jax.lax.broadcasted_iota(jnp.int32, (R, w), 1)
    wd = width[:, None].astype(jnp.int32)
    bit = slot * wd
    w0 = wstart[:, None].astype(jnp.int32) + (bit >> 5)
    gap = _funnel_unpack(stream.words, w0, bit & 31, wd)
    valid = slot < deg[:, None]
    base = jnp.where(slot == 0, nodes.astype(jnp.int32)[:, None], 0)
    vals = jnp.where(valid, base + gap, 0)
    cols = jnp.cumsum(vals, axis=1)
    cols = jnp.where(valid, cols, nodes.astype(jnp.int32)[:, None]).astype(
        nodes.dtype
    )
    if stream.weighted:
        eidx = jnp.clip(
            estart[:, None].astype(jnp.int32) + slot,
            0, stream.edge_w.shape[0] - 1,
        )
        wgts = jnp.where(valid, stream.edge_w[eidx], 0).astype(wdtype)
    else:
        wgts = valid.astype(wdtype)
    return cols, wgts


def decode_bucket(stream: CompressedStream, cb: CompressedBucket, wdtype):
    """(cols, wgts) of one :class:`CompressedBucket` (see decode_rows)."""
    return decode_rows(
        stream, cb.nodes, cb.wstart, cb.width, cb.deg, cb.estart,
        int(cb.slot.shape[0]), wdtype,
    )


def decode_flat_padded(stream: CompressedStream, wstart, width, deg, *,
                       m_pad: int):
    """Flat in-trace decode to PaddedView-convention arrays.

    Returns ``(row_ptr, col_idx, edge_w, edge_u)`` padded exactly like the
    dense ``CSRGraph.padded()`` of the decompressed graph: pad edges are
    weight-0 anchor self-loops, pad rows are empty except the anchor (the
    last node), whose row_ptr entry closes at ``m_pad``.  Used by the
    compressed contraction wrapper (the finest level's coarse graph is
    built without ever holding a resident dense CSR) and by the finest
    re-materialization at final uncoarsening (a device decode kernel, no
    host round trip).
    """
    idt = deg.dtype
    n_pad = deg.shape[0]
    rp = jnp.concatenate(
        [jnp.zeros(1, dtype=idt), jnp.cumsum(deg).astype(idt)]
    )
    m = rp[-1]
    # edge_u via the scatter-of-row-starts cumsum trick: rows with start <=
    # slot accumulate, so each slot lands on its owning row; all pad slots
    # (>= m) accumulate every trailing empty row and land on the anchor —
    # exactly the dense pad convention.
    marks = jnp.zeros(m_pad, dtype=jnp.int32).at[rp[:-1]].add(1, mode="drop")
    eu = (jnp.cumsum(marks) - 1).astype(idt)
    pos = jnp.arange(m_pad, dtype=jnp.int32) - rp[eu].astype(jnp.int32)
    wd = width[eu].astype(jnp.int32)
    bit = pos * wd
    w0 = wstart[eu].astype(jnp.int32) + (bit >> 5)
    gap = _funnel_unpack(stream.words, w0, bit & 31, wd)
    valid = jnp.arange(m_pad, dtype=jnp.int32) < m.astype(jnp.int32)
    firsts = pos == 0
    vals = jnp.where(
        valid, jnp.where(firsts, eu.astype(jnp.int32) + gap, gap), 0
    )
    c = jnp.cumsum(vals)
    row_base = jnp.concatenate([jnp.zeros(1, c.dtype), c])[rp[:-1]]
    col = c - row_base[eu]
    anchor = jnp.asarray(n_pad - 1, dtype=idt)
    col = jnp.where(valid, col.astype(idt), anchor)
    if stream.weighted:
        eidx = jnp.clip(
            jnp.arange(m_pad, dtype=jnp.int32), 0, stream.edge_w.shape[0] - 1
        )
        ew = jnp.where(valid, stream.edge_w[eidx], 0).astype(idt)
    else:
        ew = valid.astype(idt)
    eu = jnp.where(valid, eu, anchor)
    rp = rp.at[-1].set(jnp.asarray(m_pad, dtype=idt))
    return rp, col, ew, eu


_decode_flat_padded_jit = jax.jit(
    decode_flat_padded, static_argnames=("m_pad",)
)


# -- host-side heavy-row decode (view construction only) --------------------


def _decode_neighbors_host(
    cg: CompressedGraph, nodes: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Decode the concatenated (sorted-ascending) neighbor lists of the
    given nodes on host, plus their flat edge-slot indices (for the weight
    side stream) — used once at view build for the rare heavy rows."""
    deg_all = cg.degree.astype(np.int64)
    rp_all = np.concatenate([[0], np.cumsum(deg_all)])
    deg = deg_all[nodes]
    total = int(deg.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.int64)
    u_arr = np.repeat(nodes.astype(np.int64), deg)
    starts = np.concatenate([[0], np.cumsum(deg)[:-1]])
    pos = np.arange(total) - np.repeat(starts, deg)
    slots = np.repeat(rp_all[nodes], deg) + pos
    w = cg.width[u_arr].astype(np.int64)
    bit = pos * w
    word0 = cg.word_start[u_arr].astype(np.int64) + (bit >> 5)
    shift = bit & 31
    lo = cg.words[word0].astype(np.uint64)
    hi = cg.words[np.minimum(word0 + 1, len(cg.words) - 1)].astype(np.uint64)
    both = lo | (hi << np.uint64(32))
    mask = (np.uint64(1) << w.astype(np.uint64)) - np.uint64(1)
    z = (both >> shift.astype(np.uint64)) & mask
    gaps = (z.astype(np.int64) >> 1) ^ -(z.astype(np.int64) & 1)
    firsts = pos == 0
    vals = np.where(firsts, u_arr + gaps, gaps)
    c = np.cumsum(vals)
    c_ext = np.concatenate([np.zeros(1, c.dtype), c])
    return c - np.repeat(c_ext[starts], deg), slots


# -- the view ---------------------------------------------------------------


class DeviceCompressedView:
    """Device-resident compressed graph + compressed bucketed layout.

    Resident arrays (everything the finest-level LP pass touches): the
    :class:`CompressedStream` (word-stream bucket + the edge-weight side
    stream when non-uniform), per-node ``word_start/width/degree/node_w``
    (node ladder ``n_pad`` — the same bucket the dense PaddedView would
    use, so LP states share kernel shapes), the per-bucket row metadata,
    the dense heavy part, and ``gather_idx``.  The structural m-sized
    arrays (col_idx, edge_u, the bucketed neighbor matrices) exist only as
    kernel transients.
    """

    def __init__(self, cg: CompressedGraph, *, layout_mode: Optional[str] = None):
        from .csr import _next_bucket

        self._cg = cg
        self.n = int(cg.n)
        self.m = int(cg.m)
        self.n_pad = _next_bucket(self.n)
        self.m_pad = _next_bucket(self.m)
        self.layout_mode = layout_mode
        idt = np.int32

        deg = cg.degree.astype(np.int64)
        erp = np.concatenate([[0], np.cumsum(deg)])  # decode-order row_ptr
        node_w = np.asarray(cg.node_w).astype(idt)
        wstart = cg.word_start[: self.n].astype(np.int64)
        width = cg.width.astype(np.int64)

        # Word stream: its own shape-bucket dimension (strictly > len so the
        # straddle read at +1 stays in bounds even at the last real word).
        w_bucket = next_shape_bucket(len(cg.words) + 1, 256)
        words_pad = np.zeros(w_bucket, dtype=np.uint32)
        words_pad[: len(cg.words)] = cg.words
        if cg.edge_w is None:
            ew_pad = np.zeros(1, dtype=idt)
        else:
            ew_pad = np.zeros(self.m_pad, dtype=idt)
            ew_pad[: self.m] = np.asarray(cg.edge_w, dtype=idt)
        self.stream = CompressedStream(jnp.asarray(words_pad), jnp.asarray(ew_pad))

        n_fill = self.n_pad - self.n
        self.node_w_pad = jnp.asarray(
            np.concatenate([node_w, np.zeros(n_fill, dtype=idt)])
        )
        self.degree_pad = jnp.asarray(
            np.concatenate([deg.astype(idt), np.zeros(n_fill, dtype=idt)])
        )
        self.wstart_pad = jnp.asarray(
            np.concatenate([wstart.astype(idt), np.zeros(n_fill, dtype=idt)])
        )
        self.width_pad = jnp.asarray(
            np.concatenate([width.astype(idt), np.ones(n_fill, dtype=idt)])
        )

        self.buckets, self.heavy, self.gather_idx = self._build_buckets(
            cg, deg, erp, wstart, width, idt
        )
        self._row_ptr = None
        self._total_node_weight = int(node_w.astype(np.int64).sum())
        self._max_node_weight = int(node_w.max(initial=0))
        self._total_edge_weight = (
            self.m if cg.edge_w is None
            else int(np.asarray(cg.edge_w).astype(np.int64).sum())
        )
        from ..utils import compile_stats

        compile_stats.record(
            "compressed_bucket", statics=(self.n_pad, int(w_bucket))
        )

    @property
    def anchor(self) -> int:
        return self.n_pad - 1

    @property
    def total_node_weight(self) -> int:
        return self._total_node_weight

    @property
    def max_node_weight(self) -> int:
        return self._max_node_weight

    def _build_buckets(self, cg, deg, erp, wstart, width, idt):
        """The dense host builder's exact bucket structure (same width
        classes, same merge cascade — literally the shared
        :func:`~kaminpar_tpu.graph.bucketed.node_width_plan` — same
        ascending node order, same ``R_pad`` and ``gather_idx``) with
        per-row decode metadata instead of materialized neighbor
        matrices."""
        from .bucketed import node_width_plan

        anchor = self.anchor
        bwidth, heavy_mask = node_width_plan(deg)

        buckets = []
        offsets = np.full(self.n_pad, -1, dtype=np.int64)
        offset = 0
        for w in sorted(int(x) for x in np.unique(bwidth[~heavy_mask])):
            nodes = np.nonzero((~heavy_mask) & (bwidth == w))[0]
            R = len(nodes)
            R_pad = next_pow2(R, 8)
            nodes_b = np.full(R_pad, anchor, dtype=idt)
            ws_b = np.zeros(R_pad, dtype=idt)
            wd_b = np.ones(R_pad, dtype=idt)
            dg_b = np.zeros(R_pad, dtype=idt)
            es_b = np.zeros(R_pad, dtype=idt)
            nodes_b[:R] = nodes
            ws_b[:R] = wstart[nodes]
            wd_b[:R] = width[nodes]
            dg_b[:R] = deg[nodes]
            es_b[:R] = erp[nodes]
            buckets.append(
                CompressedBucket(
                    jnp.asarray(nodes_b), jnp.asarray(ws_b),
                    jnp.asarray(wd_b), jnp.asarray(dg_b), jnp.asarray(es_b),
                    jnp.arange(w, dtype=jnp.int32),
                )
            )
            offsets[nodes] = offset + np.arange(R)
            offset += R_pad

        hn = np.nonzero(heavy_mask)[0]
        Hr = len(hn)
        if Hr:
            hdeg = deg[hn]
            Hs = int(hdeg.sum())
            Hr_pad = next_pow2(Hr + 1, 8)  # strictly > Hr: last row is a pad
            Hs_pad = next_pow2(Hs, 8)
            hcols = np.full(Hs_pad, anchor, dtype=idt)
            hw = np.zeros(Hs_pad, dtype=idt)
            hrow_full = np.full(Hs_pad, Hr_pad - 1, dtype=idt)
            cols, slots = _decode_neighbors_host(cg, hn)
            hcols[:Hs] = cols
            hw[:Hs] = 1 if cg.edge_w is None else cg.edge_w[slots]
            hrow_full[:Hs] = np.repeat(np.arange(Hr, dtype=idt), hdeg)
            hnodes = np.full(Hr_pad, anchor, dtype=idt)
            hnodes[:Hr] = hn
            heavy = HeavyPart(
                jnp.asarray(hnodes), jnp.asarray(hrow_full),
                jnp.asarray(hcols), jnp.asarray(hw),
            )
            offsets[hn] = offset + np.arange(Hr)
        else:
            z = jnp.zeros(0, dtype=idt)
            heavy = HeavyPart(z, z, z, z)
        return tuple(buckets), heavy, jnp.asarray(offsets.astype(idt))

    def row_ptr_like(self):
        """(n_pad + 1,) row-pointer twin of the dense PaddedView's (cached
        device array; feeds ``lp.cluster_isolated_nodes`` unchanged)."""
        if self._row_ptr is None:
            idt = self.degree_pad.dtype
            rp = jnp.concatenate(
                [jnp.zeros(1, dtype=idt), jnp.cumsum(self.degree_pad)]
            )
            self._row_ptr = rp.at[-1].set(
                jnp.asarray(self.m_pad, dtype=idt)
            )
        return self._row_ptr

    # -- memory accounting (bench compress_ab) -----------------------------

    def resident_bytes(self) -> int:
        """Device-resident bytes of the compressed adjacency tier (the
        steady-state finest-level footprint under device decode)."""
        b = self.stream.words.nbytes + self.stream.edge_w.nbytes
        for arr in (
            self.node_w_pad, self.degree_pad, self.wstart_pad,
            self.width_pad, self.gather_idx,
        ):
            b += arr.nbytes
        for cb in self.buckets:
            b += cb.nodes.nbytes + cb.wstart.nbytes + cb.width.nbytes
            b += cb.deg.nbytes + cb.estart.nbytes
        for arr in self.heavy:
            b += arr.nbytes
        return b

    def dense_resident_bytes(self) -> int:
        """Padded dense-CSR footprint of the same level (what the dense
        path keeps resident: row_ptr/col/edge_w/edge_u/node_w on the shape
        ladder, plus the dense bucketed layout's neighbor matrices)."""
        itemsize = 4
        csr = (self.n_pad + 1 + self.n_pad + 3 * self.m_pad) * itemsize
        slots = 0
        for cb in self.buckets:
            slots += int(cb.nodes.shape[0]) * int(cb.slot.shape[0])
        bucketed = (2 * slots + self.n_pad) * itemsize  # cols + wgts + gather
        bucketed += sum(int(a.shape[0]) for a in self.heavy) * itemsize
        return csr + bucketed

    # -- finest re-materialization (device decode, no host round trip) -----

    def materialize_csr(self):
        """Decode the full CSR into device arrays (ONE jit dispatch, zero
        blocking transfers — every scalar a later phase needs is seeded
        from host-side compressed metadata).  The returned graph carries
        ``_compressed_view = self`` so the finest-level LP refinement pass
        routes through the decode-fused kernels."""
        from .bucketed import host_deg_histogram
        from .csr import CSRGraph, PaddedView

        rp, col, ew, eu = _decode_flat_padded_jit(
            self.stream, self.wstart_pad, self.width_pad, self.degree_pad,
            m_pad=self.m_pad,
        )
        g = CSRGraph(
            rp[: self.n + 1], col[: self.m], self.node_w_pad[: self.n],
            ew[: self.m], edge_u=eu[: self.m],
        )
        g._padded = PaddedView(rp, col, self.node_w_pad, ew, eu, self.n, self.m)
        from ..utils import compile_stats

        compile_stats.record("padded_bucket", statics=(self.n_pad, self.m_pad))
        rp_host = np.zeros(self.n + 1, dtype=np.int64)
        np.cumsum(self._cg.degree.astype(np.int64), out=rp_host[1:])
        g._deg_hist = host_deg_histogram(rp_host, self.n)
        g._total_node_weight = self._total_node_weight
        g._max_node_weight = self._max_node_weight
        g._total_edge_weight = self._total_edge_weight
        g._layout_mode = self.layout_mode
        g._compressed_view = self
        return g


# -- routing ----------------------------------------------------------------


def resolve_device_decode(compression_ctx) -> str:
    """Map ``GraphCompressionContext.device_decode`` to a concrete mode
    ("off" | "finest"); ``KAMINPAR_TPU_DEVICE_DECODE`` overrides."""
    mode = os.environ.get("KAMINPAR_TPU_DEVICE_DECODE", "") or getattr(
        compression_ctx, "device_decode", "off"
    )
    if mode not in ("off", "finest", "auto"):
        raise ValueError(
            f"device_decode must be 'off', 'finest' or 'auto', got {mode!r}"
        )
    return "finest" if mode == "auto" else mode


def device_decode_eligible(ctx, cg: CompressedGraph, communities=None) -> Tuple[bool, str]:
    """(eligible, reason) for routing the finest level through the device
    view.  The envelope: 32-bit build, LP clustering, no v-cycle community
    restriction (community masking needs per-edge weight masking, which
    the compressed stream does not carry)."""
    from ..context import ClusteringAlgorithm

    if cg is None or cg.n == 0:
        return False, "empty graph"
    if ctx.use_64bit_ids:
        return False, "64-bit build"
    if ctx.coarsening.algorithm != ClusteringAlgorithm.LP:
        return False, f"clusterer {ctx.coarsening.algorithm.value}"
    if communities is not None:
        return False, "v-cycle community restriction"
    return True, ""


def build_device_view_if_eligible(ctx, cg: CompressedGraph, communities=None):
    """The deep partitioner's gate: a :class:`DeviceCompressedView` when the
    knob + envelope allow it, else None (dense fallback).  ``finest`` warns
    on fallback; ``auto`` falls back silently."""
    mode = resolve_device_decode(ctx.compression)
    if mode == "off":
        return None
    ok, reason = device_decode_eligible(ctx, cg, communities)
    if not ok:
        # Warn iff "finest" was what the caller *requested* — via the env
        # override or the ctx knob (an "auto" that resolved to finest falls
        # back silently; that is its contract).
        requested = os.environ.get(
            "KAMINPAR_TPU_DEVICE_DECODE", ""
        ) or getattr(ctx.compression, "device_decode", "off")
        if requested == "finest":
            from ..utils.logger import Logger

            Logger.warning(
                f"compression.device_decode=finest requested but {reason}; "
                "falling back to the dense decode path"
            )
        return None
    from ..resilience.breakers import global_registry

    reg = global_registry()
    breaker = reg.get("device_decode")
    if not breaker.allow():
        # Round 17: the decode-fused path failed its way past the breaker
        # threshold — run this level dense (bit-identical by the round-14
        # contract) instead of paying another doomed build; the half-open
        # probe after the cooldown re-admits the compressed path.
        reg.record_demotion("device_decode", "circuit breaker open")
        return None
    try:
        from ..resilience.faults import maybe_inject

        maybe_inject("execute", site="device_decode")
        view = DeviceCompressedView(
            cg, layout_mode=ctx.parallel.device_layout_build
        )
    except Exception as exc:  # noqa: BLE001 — the dense path is the
        # bit-identical fallback for every view-build failure class
        from ..resilience.errors import classify

        err = classify(exc, site="device_decode")
        breaker.record_failure()
        reg.record_demotion("device_decode", err.failure_class)
        return None
    if breaker.record_success():
        reg.record_restoration("device_decode")
    return view
