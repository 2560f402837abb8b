"""Best-move computation over the degree-bucketed layout — the fast path.

Semantics are identical to :func:`kaminpar_tpu.ops.gains.best_moves` (the flat
sort-reduce reference implementation, kept for cross-checking); the execution
shape is different: per degree bucket, a batched row-local sort
(``lax.sort`` along the width axis) + cumulative-sum run reduction replaces
the global ``m``-element sort.  Heavy rows (degree > MAX_WIDTH) run the flat
algorithm over just their slots — the TPU rendition of the reference's
two-phase LP (label_propagation.h:571-601,640-815).

All functions here are meant to be called *inside* an enclosing jit (they
trace into it); only shapes in the bucketed view determine specialization.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..graph.bucketed import Bucket, HeavyPart
from .segment import (
    cummax,
    cumsum,
    row_cummax,
    row_cumsum,
    run_starts2,
    take,
    take_rows,
)
from .sort import argsort

_I32MAX = 2**31 - 1


def lookup(table_or_scalar, idx):
    """Index a per-label table, or broadcast a scalar limit (saves a large
    random gather when the limit is uniform, as in clustering)."""
    t = jnp.asarray(table_or_scalar)
    if t.ndim == 0:
        return t
    return take(t, idx) if jnp.ndim(idx) == 2 else t[idx]


def _bucket_moves(
    key,
    labels,
    bucket: Bucket,
    node_w,
    label_weights,
    max_label_weights,
    *,
    external_only: bool,
    respect_caps: bool,
    tie_break: str = "uniform",
):
    """Per-row best move for one (R, w) bucket.  Returns (target, tconn,
    own_conn, has_cand), each (R,)."""
    nodes, cols, wgts = bucket
    R, w = cols.shape
    own = labels[nodes]  # (R,)
    nw = node_w[nodes]  # (R,)

    L = take(labels, cols)  # (R, w) neighbor labels
    W = wgts
    own_conn = jnp.sum(jnp.where(L == own[:, None], W, 0), axis=1)

    Ls, Ws = jax.lax.sort((L, W), dimension=1, num_keys=1)
    c = row_cumsum(Ws)
    change = Ls[:, 1:] != Ls[:, :-1]
    start = jnp.concatenate([jnp.ones((R, 1), bool), change], axis=1)
    end = jnp.concatenate([change, jnp.ones((R, 1), bool)], axis=1)
    # Rating of the run covering each slot, valid at run ends: cumsum minus the
    # cumsum value just before the run began (propagated by a row cummax, which
    # is monotone because weights are non-negative).
    base = jnp.where(start, c - Ws, 0)
    run_base = row_cummax(base)
    rating = c - run_base

    is_cur = Ls == own[:, None]
    # rating > 0 excludes all-pad runs (pad slots have weight 0; real edges
    # have weight >= 1), matching the flat path where pads don't exist.
    ok = end & (rating > 0)
    if external_only:
        ok = ok & ~is_cur
    if respect_caps:
        fits = take(label_weights, Ls) + nw[:, None] <= lookup(
            max_label_weights, Ls
        )
        ok = ok & fits if external_only else ok & (is_cur | fits)

    score = jnp.where(ok, rating, -1)
    best = jnp.max(score, axis=1)
    has = best >= 0
    eligible = ok & (rating == best[:, None]) & has[:, None]
    if tie_break == "lightest":
        # Among equally-rated clusters prefer the lightest one (then
        # random) — see TieBreakingStrategy.LIGHTEST.
        lw = lookup(label_weights, Ls)
        lw_m = jnp.where(eligible, lw, jnp.iinfo(lw.dtype).max)
        eligible = eligible & (lw_m == jnp.min(lw_m, axis=1)[:, None])
    tie = jax.random.randint(key, (R, w), 0, _I32MAX, dtype=jnp.int32)
    tie_m = jnp.where(eligible, tie, -1)
    slot = jnp.argmax(tie_m, axis=1)
    target = jnp.where(has, take_rows(Ls, slot), own)
    tconn = jnp.where(has, best, 0)
    return target, tconn, own_conn, has


def flat_best_moves(
    key,
    row,
    cand,
    w,
    own,
    node_w_row,
    label_weights,
    max_label_weights,
    *,
    num_rows: int,
    external_only: bool,
    respect_caps: bool,
    tie_break: str = "uniform",
    fast_compile: bool = False,
):
    """Flat run-reduce best-move kernel over (row, candidate-label, weight)
    slot triples: one variadic sort by (row, label), then run ratings via the
    cumsum/cummax trick (the global cumsum is monotone, so a single cummax
    propagates each run's base — no m-segment scatters).

    Shared by the heavy path of the bucketed layout and the per-shard
    distributed LP kernel (dist/lp.py).  ``own``/``node_w_row`` are
    (num_rows,); returns per-row (target, tconn, own_conn, has_cand).

    ``fast_compile`` sorts and scans with the forms of ops/sort.py and
    ops/segment.py, whose TPU compile time does not grow with ``S``; the
    result is the same.  The sharded path keeps XLA's own sort and scans
    until those forms have run on a real mesh."""
    S = cand.shape[0]
    if fast_compile:
        order = argsort((row, cand))
        sr, sc, sw = row[order], cand[order], w[order]
        c = cumsum(sw)
    else:
        sr, sc, sw = jax.lax.sort((row, cand, w), dimension=0, num_keys=2)
        c = jnp.cumsum(sw)
    first = run_starts2(sr, sc)
    base = jnp.where(first, c - sw, 0)
    run_base = cummax(base) if fast_compile else jax.lax.cummax(base)
    rating = c - run_base  # valid at run *ends*
    # mark run ends so per-row maxima only consider complete run totals
    end = jnp.concatenate([first[1:], jnp.ones(1, dtype=bool)]) if S else first
    rating = jnp.where(end, rating, 0)

    is_cur = sc == own[sr]
    own_conn = jnp.maximum(
        jax.ops.segment_max(
            jnp.where(end & is_cur, rating, 0), sr, num_segments=num_rows,
            indices_are_sorted=True,
        ),
        0,
    )

    ok = end & (rating > 0)  # excludes all-pad runs, see _bucket_moves
    if external_only:
        ok = ok & ~is_cur
    if respect_caps:
        fits = label_weights[sc] + node_w_row[sr] <= lookup(max_label_weights, sc)
        ok = ok & fits if external_only else ok & (is_cur | fits)

    score = jnp.where(ok, rating, -1)
    best = jax.ops.segment_max(score, sr, num_segments=num_rows, indices_are_sorted=True)
    eligible = ok & (rating == best[sr])
    if tie_break == "lightest":
        lw = lookup(label_weights, sc)
        lw_m = jnp.where(eligible, lw, jnp.iinfo(lw.dtype).max)
        best_lw = jax.ops.segment_min(
            lw_m, sr, num_segments=num_rows, indices_are_sorted=True
        )
        eligible = eligible & (lw_m == best_lw[sr])
    tie = jax.random.randint(key, (S,), 0, _I32MAX, dtype=jnp.int32)
    tie_m = jnp.where(eligible, tie, -1)
    best_tie = jax.ops.segment_max(
        tie_m, sr, num_segments=num_rows, indices_are_sorted=True
    )
    winner = eligible & (tie_m == best_tie[sr])
    slot = jnp.arange(S, dtype=jnp.int32)
    best_slot = jax.ops.segment_min(
        jnp.where(winner, slot, S), sr, num_segments=num_rows, indices_are_sorted=True
    )
    has = best >= 0
    safe = jnp.clip(best_slot, 0, max(S - 1, 0))
    target = jnp.where(has, sc[safe], own)
    tconn = jnp.where(has, best, 0)
    return target, tconn, own_conn, has


def _heavy_moves(
    key,
    labels,
    heavy: HeavyPart,
    node_w,
    label_weights,
    max_label_weights,
    *,
    external_only: bool,
    respect_caps: bool,
    tie_break: str = "uniform",
):
    """Heavy rows: the flat kernel with the dense heavy-row index as row key."""
    hnodes, hrow, hcols, hw = heavy
    return flat_best_moves(
        key, hrow, labels[hcols], hw, labels[hnodes], node_w[hnodes],
        label_weights, max_label_weights, num_rows=hnodes.shape[0],
        external_only=external_only, respect_caps=respect_caps,
        tie_break=tie_break, fast_compile=True,
    )


def bucketed_best_moves(
    key,
    labels,
    buckets,
    heavy: HeavyPart,
    gather_idx,
    node_w,
    label_weights,
    max_label_weights,
    *,
    external_only: bool = True,
    respect_caps: bool = True,
    tie_break: str = "uniform",
):
    """Drop-in equivalent of gains.best_moves over the bucketed layout.

    ``labels``/``node_w`` are (n_pad,) arrays of the graph's PaddedView;
    returns (target, tconn, own_conn, has_cand) each (n_pad,), with inert
    defaults (no candidate, no move) on pad nodes.
    """
    outs = []
    for i, b in enumerate(buckets):
        outs.append(
            _bucket_moves(
                jax.random.fold_in(key, i),
                labels,
                b,
                node_w,
                label_weights,
                max_label_weights,
                external_only=external_only,
                respect_caps=respect_caps,
                tie_break=tie_break,
            )
        )
    if heavy.nodes.shape[0] > 0:
        outs.append(
            _heavy_moves(
                jax.random.fold_in(key, len(buckets)),
                labels,
                heavy,
                node_w,
                label_weights,
                max_label_weights,
                external_only=external_only,
                respect_caps=respect_caps,
                tie_break=tie_break,
            )
        )

    return assemble_moves(outs, gather_idx, labels)


def assemble_moves(outs, gather_idx, labels):
    """Gather per-bucket row results into (n_pad,) node arrays with inert
    defaults on pad nodes (``gather_idx < 0``): own label, connection 0,
    own connection 0, no candidate.  Shared by the XLA path above and the
    fused Pallas path (ops/pallas_lp.py), which must assemble identically."""
    real = gather_idx >= 0
    row = jnp.maximum(gather_idx, 0)

    def gather(j, pad_value):
        return jnp.where(real, jnp.concatenate([o[j] for o in outs])[row], pad_value)

    return gather(0, labels), gather(1, 0), gather(2, 0), gather(3, False)


def bucketed_neighbor_reduce(fn, buckets, heavy: HeavyPart, gather_idx):
    """Generic per-node reduction over neighbors in the bucketed layout.

    ``fn(nodes, cols, wgts) -> (R, w) contributions`` is evaluated per bucket
    (and per heavy slot with shapes (Hs,)); contributions are summed per row
    and gathered into an (n_pad,) array (0 on pads).  Used by JET's
    pessimistic-gain filter, which the reference computes edge-parallel
    (jet_refiner.cc:135-170).
    """
    outs = []
    for b in buckets:
        contrib = fn(b.nodes[:, None], b.cols, b.wgts)
        outs.append(jnp.sum(contrib, axis=1))
    if heavy.nodes.shape[0] > 0:
        hnodes, hrow, hcols, hw = heavy
        contrib = fn(hnodes[hrow], hcols, hw)
        outs.append(
            jax.ops.segment_sum(
                contrib, hrow, num_segments=hnodes.shape[0], indices_are_sorted=True
            )
        )
    flat = jnp.concatenate(outs)[jnp.maximum(gather_idx, 0)]
    return jnp.where(gather_idx >= 0, flat, 0)
