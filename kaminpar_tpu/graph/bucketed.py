"""Degree-bucketed adjacency view — the TPU-fast neighborhood layout.

The reference handles power-law degree distributions with degree buckets and a
two-phase LP (``kaminpar-shm/label_propagation.h:571-601,640-815``: low-degree
nodes node-parallel, huge-degree nodes edge-parallel).  The TPU analog
(SURVEY §7 hard part (a)): group nodes by degree into power-of-two width
buckets and lay each bucket out as a dense ``(rows, width)`` matrix.  Row-local
kernels (batched sort + cumulative ops along the width axis) then replace the
global edge sort — XLA maps them onto the VPU with full parallelism over rows,
which is ~20x faster than a flat ``m``-element sort per LP round.

Nodes with degree > ``MAX_WIDTH`` go to the *heavy* flat path (edge-parallel
sort-reduce over just their slots), mirroring the reference's second phase.

Layout invariants (all host-built once per graph, then device-resident):
- pad slots inside a row: ``col = the row's own node id`` with edge weight 0 —
  inert in ratings (a zero-weight run of the node's own label); in the heavy
  part, pad slots use ``col = anchor``;
- pad rows: ``node = anchor``; their results are never gathered;
- ``gather_idx`` has one entry per node of the padded node space
  (``n_pad``, on the shape ladder): ``gather_idx[u]`` = position of real
  node u's row in the concatenation of all bucket rows (buckets in order,
  then heavy rows), and -1 on pad nodes.  Per-node results are assembled
  with one gather and no scatter, and consumers give pad nodes their
  defaults where ``gather_idx < 0``.  No array of the layout has the real
  node count as a shape, so a new graph on the same rung and bucket plan
  reuses every compiled program that takes the layout.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MIN_WIDTH = 8
MAX_WIDTH = 4096  # batched row sorts stay cheap even at this width
# Buckets with fewer rows merge upward to bound the per-level kernel-shape
# count.  4096 was far too aggressive: on power-law graphs it cascaded every
# mid-degree class into one max-width bucket (rmat14: 73x slot inflation,
# 15x slower LP rounds on TPU).  Bucket *count* barely affects XLA compile
# time (row sorts are cheap to compile; measured 19 s for 2 buckets vs 19 s
# for 6); padding waste dominates runtime, so keep classes fine-grained.
MIN_ROWS = 256


class Bucket(NamedTuple):
    nodes: jax.Array  # (R,)   node id per row (pad rows -> anchor)
    cols: jax.Array  # (R, w) neighbor ids (pad slots -> anchor)
    wgts: jax.Array  # (R, w) edge weights (pad slots -> 0)


class HeavyPart(NamedTuple):
    nodes: jax.Array  # (Hr,)  heavy node id per dense row (pads -> anchor)
    row: jax.Array  # (Hs,)  dense row index per slot, ascending (pads -> Hr-1)
    cols: jax.Array  # (Hs,)  neighbor ids (pads -> anchor)
    wgts: jax.Array  # (Hs,)  edge weights (pads -> 0)


class BucketedView(NamedTuple):
    buckets: Tuple[Bucket, ...]
    heavy: HeavyPart  # zero-row part when no heavy nodes
    gather_idx: jax.Array  # (n_pad,) row of node u in concat(results); -1 on pads
    n: int

    @property
    def num_rows(self) -> int:
        """Total rows across buckets + heavy (the concat result length)."""
        r = sum(int(b.nodes.shape[0]) for b in self.buckets)
        return r + int(self.heavy.nodes.shape[0])


from ..ops.segment import cumsum, nonzero, take
from ..utils.intmath import next_pow2 as _next_pow2

# Width classes of the degree histogram that rides the contraction level's
# batched readback (ops/contraction.py stats layout): class i holds nodes of
# bucket width 2^(3+i); two trailing ints carry heavy row / slot counts.
WIDTH_CLASSES = tuple(1 << (3 + i) for i in range(10))


def degree_classes(deg, real):
    """Device (width-class index 0..9, heavy mask) per node via integer
    threshold counts — bit-identical to the host builder's float
    ``pow2ceil`` width computation for every degree (exact comparisons, no
    rounding)."""
    import jax.numpy as _jnp

    cls = _jnp.zeros_like(deg)
    for t in WIDTH_CLASSES[:-1]:
        cls = cls + (deg > t).astype(deg.dtype)
    heavy = real & (deg > WIDTH_CLASSES[-1])
    return cls, heavy


def device_deg_histogram(deg, real):
    """(12,) device ints: per-class real non-heavy node counts, heavy row
    count, heavy slot count.  Trace-safe (called inside the contraction
    kernel so the histogram ships in the level's single readback)."""
    cls, heavy = degree_classes(deg, real)
    ok = real & ~heavy
    seg = jnp.where(ok, cls, len(WIDTH_CLASSES)).astype(jnp.int32)
    hist = jax.ops.segment_sum(
        jnp.ones_like(seg), seg, num_segments=len(WIDTH_CLASSES) + 1
    )[:-1]
    hr = jnp.sum(heavy.astype(jnp.int32))
    hs = jnp.sum(jnp.where(heavy, deg, 0)).astype(jnp.int32)
    return jnp.concatenate(
        [hist.astype(deg.dtype), jnp.stack([hr, hs]).astype(deg.dtype)]
    )


def host_deg_histogram(row_ptr: np.ndarray, n: int) -> np.ndarray:
    """Host twin of :func:`device_deg_histogram` for graphs built from
    numpy (the finest level) — no device readback needed."""
    deg = np.diff(np.asarray(row_ptr)[: n + 1]).astype(np.int64)
    heavy = deg > WIDTH_CLASSES[-1]
    cls = np.zeros(n, dtype=np.int64)
    for t in WIDTH_CLASSES[:-1]:
        cls += deg > t
    counts = np.bincount(cls[~heavy], minlength=len(WIDTH_CLASSES))
    return np.concatenate(
        [counts[: len(WIDTH_CLASSES)],
         [int(heavy.sum()), int(deg[heavy].sum())]]
    ).astype(np.int64)


def node_width_plan(
    deg: np.ndarray,
    *,
    min_width: int = MIN_WIDTH,
    max_width: int = MAX_WIDTH,
    min_rows: int = MIN_ROWS,
):
    """(per-node bucket width, heavy mask) — the host-side bucket plan.

    Per-node width = next power of two >= degree, clamped; then sparse
    width classes merge upward so small graphs use few kernel shapes.  An
    undersized class merges into the next *naturally occupied* class, so
    the cascade ends at next_pow2(max degree) — never at max_width — and a
    coarse graph cannot be inflated past its own degree range.

    Shared by :func:`build_bucketed_view` and the compressed layout builder
    (graph/device_compressed.py), whose bit-identity contract requires the
    two plans to be the SAME function — do not fork this logic.
    """
    deg = np.asarray(deg, dtype=np.int64)
    width = np.maximum(
        min_width, 2 ** np.ceil(np.log2(np.maximum(deg, 1))).astype(np.int64)
    )
    heavy_mask = deg > max_width
    width = np.minimum(width, max_width)
    natural = set(int(x) for x in np.unique(width[~heavy_mask]))
    for w in sorted(natural)[:-1]:
        sel = (~heavy_mask) & (width == w)
        cnt = int(sel.sum())
        if 0 < cnt < min_rows:
            bigger = min(x for x in natural if x > w)
            width[sel] = bigger
    return width, heavy_mask


def build_bucketed_view(
    row_ptr: np.ndarray,
    col_idx: np.ndarray,
    edge_w: np.ndarray,
    n: int,
    anchor: int,
    *,
    min_width: int = MIN_WIDTH,
    max_width: int = MAX_WIDTH,
    min_rows: int = MIN_ROWS,
) -> BucketedView:
    rp = np.asarray(row_ptr)
    col = np.asarray(col_idx)
    ew = np.asarray(edge_w)
    idt = col.dtype
    m = col.shape[0]
    deg = np.diff(rp[: n + 1]).astype(np.int64)

    width, heavy_mask = node_width_plan(
        deg, min_width=min_width, max_width=max_width, min_rows=min_rows
    )

    buckets = []
    offsets = np.full(anchor + 1, -1, dtype=np.int64)
    offset = 0
    for w in sorted(int(x) for x in np.unique(width[~heavy_mask])):
        nodes = np.nonzero((~heavy_mask) & (width == w))[0]
        R = len(nodes)
        R_pad = _next_pow2(R, 8)
        slot = np.arange(w, dtype=np.int64)
        idx = rp[nodes][:, None] + slot[None, :]
        valid = slot[None, :] < deg[nodes][:, None]
        safe = np.minimum(idx, max(m - 1, 0))
        cols_b = np.where(valid, col[safe] if m else 0, nodes[:, None]).astype(idt)
        wgts_b = np.where(valid, ew[safe] if m else 0, 0).astype(idt)
        nodes_b = np.full(R_pad, anchor, dtype=idt)
        nodes_b[:R] = nodes
        cols_full = np.full((R_pad, w), anchor, dtype=idt)
        cols_full[:R] = cols_b
        wgts_full = np.zeros((R_pad, w), dtype=idt)
        wgts_full[:R] = wgts_b
        buckets.append(
            Bucket(jnp.asarray(nodes_b), jnp.asarray(cols_full), jnp.asarray(wgts_full))
        )
        offsets[nodes] = offset + np.arange(R)
        offset += R_pad

    # Heavy part: flat slots of all heavy rows, padded to a power of two.
    hn = np.nonzero(heavy_mask)[0]
    Hr = len(hn)
    if Hr:
        hdeg = deg[hn]
        Hs = int(hdeg.sum())
        Hr_pad = _next_pow2(Hr + 1, 8)  # strictly > Hr so the last row is a pad
        Hs_pad = _next_pow2(Hs, 8)
        hrow = np.repeat(np.arange(Hr, dtype=idt), hdeg)
        starts = rp[hn]
        base = np.repeat(starts - np.concatenate([[0], np.cumsum(hdeg)[:-1]]), hdeg)
        hslots = base + np.arange(Hs, dtype=np.int64)
        hcols = np.full(Hs_pad, anchor, dtype=idt)
        hw = np.zeros(Hs_pad, dtype=idt)
        hrow_full = np.full(Hs_pad, Hr_pad - 1, dtype=idt)
        hcols[:Hs] = col[hslots]
        hw[:Hs] = ew[hslots]
        hrow_full[:Hs] = hrow
        hnodes = np.full(Hr_pad, anchor, dtype=idt)
        hnodes[:Hr] = hn
        heavy = HeavyPart(
            jnp.asarray(hnodes), jnp.asarray(hrow_full), jnp.asarray(hcols), jnp.asarray(hw)
        )
        offsets[hn] = offset + np.arange(Hr)
    else:
        heavy = HeavyPart(
            jnp.zeros(0, dtype=idt),
            jnp.zeros(0, dtype=idt),
            jnp.zeros(0, dtype=idt),
            jnp.zeros(0, dtype=idt),
        )

    return BucketedView(
        buckets=tuple(buckets),
        heavy=heavy,
        gather_idx=jnp.asarray(offsets.astype(idt)),
        n=n,
    )


# ---------------------------------------------------------------------------
# Device-side builder: the layout is computed with jitted gathers on the
# padded (shape-ladder) arrays; the ONLY host-side input is the 12-int degree
# histogram, which for coarse graphs rides the contraction level's single
# batched readback — so a coarsening level performs zero bulk device->host
# transfers for layout construction.  Bit-identical to the host builder
# (same class structure, same ascending node order, same pad conventions;
# asserted in tests/test_bucketed.py).
# ---------------------------------------------------------------------------


def _merge_plan(hist, min_rows: int):
    """Histogram twin of the host builder's width-class merge cascade.

    Returns (plan, merged_to): ``plan`` is [(width, R, R_pad)] ascending for
    every final occupied class; ``merged_to[i]`` is the final width of
    original class i (0 for empty classes)."""
    counts = {
        w: int(hist[i]) for i, w in enumerate(WIDTH_CLASSES) if int(hist[i]) > 0
    }
    natural = sorted(counts)
    groups = {w: [w] for w in natural}
    for w in natural[:-1]:
        cnt = counts.get(w, 0)
        if 0 < cnt < min_rows:
            bigger = min(x for x in natural if x > w)
            counts[bigger] = counts.get(bigger, 0) + cnt
            counts[w] = 0
            groups.setdefault(bigger, [bigger]).extend(groups.pop(w))
    merged_to = np.zeros(len(WIDTH_CLASSES), dtype=np.int32)
    plan = []
    for w in sorted(counts):
        if counts[w] <= 0:
            continue
        for member in groups.get(w, [w]):
            merged_to[WIDTH_CLASSES.index(member)] = w
        plan.append((w, counts[w], _next_pow2(counts[w], 8)))
    return plan, merged_to


from functools import partial as _partial


@_partial(jax.jit, static_argnames=("w", "R_pad"), donate_argnums=(3,))
def _device_bucket(row_ptr, col, ew, gather_idx, n, merged_to, base, R, *,
                   w: int, R_pad: int):
    idt = col.dtype
    n_pad = row_ptr.shape[0] - 1
    m_pad = col.shape[0]
    anchor = n_pad - 1
    deg = row_ptr[1:] - row_ptr[:-1]
    real = jnp.arange(n_pad) < n
    cls, heavy = degree_classes(deg, real)
    mask = real & ~heavy & (merged_to[cls.astype(jnp.int32)] == w)
    nodes = nonzero(mask, size=R_pad, fill_value=anchor).astype(idt)
    rows_ok = jnp.arange(R_pad) < R
    degn = jnp.where(rows_ok, deg[nodes], 0)
    slot = jnp.arange(w)
    idx = row_ptr[nodes][:, None] + slot[None, :]
    valid = slot[None, :] < degn[:, None]
    safe = jnp.minimum(idx, m_pad - 1)
    cols_b = jnp.where(valid, take(col, safe), nodes[:, None])
    wgts_b = jnp.where(valid, take(ew, safe), 0)
    rank = (cumsum(mask) - 1).astype(idt)
    gi = jnp.where(mask, base.astype(idt) + rank, gather_idx)
    return nodes, cols_b, wgts_b, gi


@_partial(jax.jit, static_argnames=("Hr_pad", "Hs_pad"), donate_argnums=(4,))
def _device_heavy(row_ptr, col, ew, edge_u, gather_idx, n, base, Hs, *,
                  Hr_pad: int, Hs_pad: int):
    idt = col.dtype
    n_pad = row_ptr.shape[0] - 1
    m_pad = col.shape[0]
    anchor = n_pad - 1
    deg = row_ptr[1:] - row_ptr[:-1]
    real = jnp.arange(n_pad) < n
    _, heavy = degree_classes(deg, real)
    hnodes = nonzero(heavy, size=Hr_pad, fill_value=anchor).astype(idt)
    hrank = (cumsum(heavy) - 1).astype(idt)
    # Heavy CSR slots ascending == host's per-node slot enumeration (pad
    # edges belong to the anchor, which real-mask excludes from heavy).
    edge_sel = heavy[edge_u]
    hslots = nonzero(edge_sel, size=Hs_pad, fill_value=0)
    slot_ok = jnp.arange(Hs_pad) < Hs
    safe = jnp.minimum(hslots, m_pad - 1)
    hcols = jnp.where(slot_ok, col[safe], anchor).astype(idt)
    hw = jnp.where(slot_ok, ew[safe], 0).astype(idt)
    hrow = jnp.where(
        slot_ok, hrank[edge_u[safe]], Hr_pad - 1
    ).astype(idt)
    gi = jnp.where(heavy, base.astype(idt) + hrank, gather_idx)
    return hnodes, hrow, hcols, hw, gi


def build_bucketed_view_device(pv, n: int, hist) -> BucketedView:
    """Device-resident layout build over a :class:`PaddedView`.

    ``hist``: the 12-int degree histogram (see :func:`device_deg_histogram`)
    — the only host-side shape input.  Uses the default width configuration
    (the histogram classes are fixed); the host builder remains the
    configurable reference implementation."""
    plan, merged_to = _merge_plan(hist, MIN_ROWS)
    Hr, Hs = int(hist[len(WIDTH_CLASSES)]), int(hist[len(WIDTH_CLASSES) + 1])
    idt = pv.col_idx.dtype
    n_dev = jnp.asarray(n)
    m2 = jnp.asarray(merged_to)
    gather_idx = jnp.full(pv.n_pad, -1, dtype=idt)
    buckets = []
    base = 0
    for w, R, R_pad in plan:
        nodes, cols_b, wgts_b, gather_idx = _device_bucket(
            pv.row_ptr, pv.col_idx, pv.edge_w, gather_idx, n_dev, m2,
            jnp.asarray(base), jnp.asarray(R), w=w, R_pad=R_pad,
        )
        buckets.append(Bucket(nodes, cols_b, wgts_b))
        base += R_pad
    if Hr:
        Hr_pad = _next_pow2(Hr + 1, 8)  # strictly > Hr: last row is a pad
        Hs_pad = _next_pow2(Hs, 8)
        hnodes, hrow, hcols, hw, gather_idx = _device_heavy(
            pv.row_ptr, pv.col_idx, pv.edge_w, pv.edge_u, gather_idx, n_dev,
            jnp.asarray(base), jnp.asarray(Hs), Hr_pad=Hr_pad, Hs_pad=Hs_pad,
        )
        heavy = HeavyPart(hnodes, hrow, hcols, hw)
    else:
        z = jnp.zeros(0, dtype=idt)
        heavy = HeavyPart(z, z, z, z)
    return BucketedView(
        buckets=tuple(buckets), heavy=heavy, gather_idx=gather_idx, n=n
    )
