"""Lane-axis (vmapped) variants of the multilevel device kernels (ISSUE 6).

The serve runtime micro-batches same-shape-cell requests; until round 11 a
batch still executed the multilevel pipeline once per graph, so occupancy
bought queueing efficiency but zero device parallelism.  These wrappers run
one pipeline *step* for a whole lane stack — the padded CSR buffers of all
batch graphs stacked along a leading lane axis — as ONE vmapped program.

Bit-identity contract (the serve discipline since PR 3, asserted in
tests/test_lanestack.py): a lane's result must equal its own sequential
``KaMinPar.compute_partition`` run exactly.  Two rules make that hold by
construction:

1. **Exact shape signatures.** jax's counter-based PRNG pairs threefry
   counters by the *total draw size*, so a random draw of shape (R, w) is
   NOT slot-stable under padding R — a lane may only ride a stack whose
   per-kernel shapes (padded buckets, width-class structure, per-class row
   pads, heavy pads) are exactly the shapes its sequential run compiles.
   The serve runner groups lanes by this signature (same-cell same-family
   batches almost always share it) and splits the stack when it diverges;
   ``jax.vmap`` then maps each lane through literally the sequential
   per-lane computation.
2. **One layout form.** The stacked layout's ``gather_idx`` is the
   sequential one: full (n_pad,)-length with -1 on each lane's pad nodes,
   so the shared assembly (``bucketed_gains.assemble_moves``) gives pads
   the sequential defaults and the lane programs call the sequential
   kernels unchanged.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from ..graph.bucketed import (
    MIN_ROWS,
    WIDTH_CLASSES,
    Bucket,
    HeavyPart,
    _device_bucket,
    _device_heavy,
    _merge_plan,
)
from ..utils.intmath import next_pow2
from . import lp
from .contraction import STATS_LEN, _contract_device, _extract_padded


def _unwrap(fn):
    """The traceable python function under a ``jax.jit`` wrapper — calling
    it inside an enclosing jit/vmap avoids donation warnings from the inner
    jit (donation only applies at top-level execution anyway)."""
    return getattr(fn, "__wrapped__", fn)


# ---------------------------------------------------------------------------
# Stacked LP clustering: init + fused sweep loop + isolated + two-hop, one
# dispatch for the whole lane stack (the lane twin of
# lp_clusterer._one_clustering's device work).
# ---------------------------------------------------------------------------


@partial(
    jax.jit,
    static_argnames=(
        "num_labels", "active_prob", "tie_break",
        "cluster_isolated", "cluster_two_hop",
    ),
)
def lane_cluster(
    row_ptr,      # (L, n_pad + 1)
    node_w,       # (L, n_pad)
    buckets,      # tuple[Bucket] with (L, R, w) leaves
    heavy,        # HeavyPart with (L, ...) leaves (0-row when absent)
    gather_idx,   # (L, n_pad)
    keys_iter,    # (L,) typed keys — the sequential iterate key per lane
    keys_twohop,  # (L,) typed keys (unused rows when two-hop is off)
    max_cw,       # (L,) per-lane max cluster weight
    min_moved,    # (L,) early-exit thresholds
    iters,        # (L,) per-lane sweep budgets (low-degree boost varies them)
    *,
    num_labels: int,
    active_prob: float,
    tie_break: str,
    cluster_isolated: bool,
    cluster_two_hop: bool,
):
    """(labels (L, n_pad), num_moved (L,)) of one LP clustering per lane."""
    from ..utils import compile_stats

    compile_stats.record(
        "lane_cluster",
        arrays=[node_w, *(b.cols for b in buckets), heavy.cols],
        statics=(num_labels, active_prob, tie_break,
                 cluster_isolated, cluster_two_hop),
    )
    idt = row_ptr.dtype
    anchor = num_labels - 1

    def one(rp, nw, bks, hv, gi, k_it, k_2h, mcw, mm, it):
        iota = jnp.arange(num_labels, dtype=idt)
        labels = jnp.where(gi >= 0, iota, jnp.asarray(anchor, dtype=idt))
        state = _unwrap(lp.init_state)(labels, nw, num_labels)
        max_w = mcw.astype(idt)  # scalar limit, as lp_clusterer builds it
        state = _unwrap(lp.lp_iterate_bucketed)(
            state, k_it, bks, hv, gi, nw, max_w,
            mm.astype(jnp.int32), it.astype(jnp.int32),
            num_labels=num_labels, active_prob=active_prob,
            allow_tie_moves=False, tie_break=tie_break,
        )
        if cluster_isolated:
            # Pads are weight-0 and excluded by the kernel itself.
            state = _unwrap(lp.cluster_isolated_nodes)(
                state, rp, nw, max_w, num_labels=num_labels
            )
        if cluster_two_hop:
            state = _unwrap(lp.cluster_two_hop_nodes_bucketed)(
                state, k_2h, bks, hv, gi, nw, max_w, num_labels=num_labels
            )
        return state.labels, state.num_moved

    return jax.vmap(one)(
        row_ptr, node_w, buckets, heavy, gather_idx,
        keys_iter, keys_twohop, max_cw, min_moved, iters,
    )


# ---------------------------------------------------------------------------
# Stacked contraction + padded extraction (ops/contraction.py lane twins).
# ---------------------------------------------------------------------------


@jax.jit
def lane_contract(labels, edge_u, col_idx, edge_w, node_w, lp_moved):
    """Vmapped ``_contract_device``; each lane's stats vector is widened by
    its LP moved-count so the whole stack's per-level scalars ride ONE
    stacked readback (the caller pulls the (L, STATS_LEN + 1) result)."""
    from ..utils import compile_stats

    compile_stats.record("lane_contract", arrays=[labels, col_idx])

    def one(lab, eu, ci, ew, nw, mv):
        coarse_of, stats, c_node_w, out_u, out_v, out_w, row_ptr = _unwrap(
            _contract_device
        )(lab, eu, ci, ew, nw)
        stats = jnp.concatenate([stats, mv[None].astype(stats.dtype)])
        return coarse_of, stats, c_node_w, out_u, out_v, out_w, row_ptr

    return jax.vmap(one)(labels, edge_u, col_idx, edge_w, node_w, lp_moved)


LANE_STATS_LEN = STATS_LEN + 1  # + the LP moved-count extra


@partial(jax.jit, static_argnames=("n_pad", "m_pad"))
def lane_extract_padded(row_ptr, c_node_w, out_u, out_v, out_w, n_c, m_c, *,
                        n_pad: int, m_pad: int):
    """Vmapped ``_extract_padded`` into the group's shared next-level
    buckets (equal to every lane's own buckets — the runner groups lanes
    by coarse bucket before extraction)."""
    from ..utils import compile_stats

    compile_stats.record(
        "lane_extract", arrays=[c_node_w], statics=(n_pad, m_pad)
    )

    def one(rp, cw, ou, ov, ow, nc, mc):
        return _unwrap(_extract_padded)(
            rp, cw, ou, ov, ow, nc, mc, n_pad=n_pad, m_pad=m_pad
        )

    return jax.vmap(one)(row_ptr, c_node_w, out_u, out_v, out_w, n_c, m_c)


# ---------------------------------------------------------------------------
# Stacked degree-bucketed layout build (graph/bucketed.py lane twin).
# ---------------------------------------------------------------------------


def lane_layout_signature(hist) -> tuple:
    """The full stacked-layout shape signature of one lane's degree
    histogram: ordered (width, R_pad) pairs after the merge cascade plus
    the heavy pads.  Lanes may share a stack ONLY when their signatures are
    equal — the per-bucket tie draws are shaped (R_pad, w) and the
    per-bucket ``fold_in`` indices follow the class order, so any
    difference would change a lane's random stream vs its sequential run."""
    plan, _ = _merge_plan(hist, MIN_ROWS)
    hr = int(hist[len(WIDTH_CLASSES)])
    hs = int(hist[len(WIDTH_CLASSES) + 1])
    if hr:
        heavy_sig: tuple = (next_pow2(hr + 1, 8), next_pow2(hs, 8))
    else:
        heavy_sig = (0, 0)
    return tuple((w, r_pad) for w, _, r_pad in plan) + (heavy_sig,)


def lane_layout_plan(hists):
    """Shared stacked-layout structure for lanes with EQUAL signatures.

    Returns ``(plan, merged_to (L, 10) np, Rs (L, C) np, Hs (L,) np,
    Hr_pad, Hs_pad)``: ``plan`` is the shared ((width, R_pad), ...) tuple,
    ``merged_to`` the per-lane class-merge maps (each lane reaches the
    shared width list through its own cascade), ``Rs`` the per-lane real
    row counts per class."""
    import numpy as np

    per_lane = [_merge_plan(h, MIN_ROWS) for h in hists]
    plan0 = per_lane[0][0]
    plan = tuple((w, r_pad) for w, _, r_pad in plan0)
    merged_to = np.stack([m for _, m in per_lane])
    counts = np.zeros((len(hists), len(plan)), dtype=np.int64)
    for li, (pl, _) in enumerate(per_lane):
        for ci, (_, r, _) in enumerate(pl):
            counts[li, ci] = r
    hr = [int(h[len(WIDTH_CLASSES)]) for h in hists]
    hs = [int(h[len(WIDTH_CLASSES) + 1]) for h in hists]
    if any(hr):
        Hr_pad = next_pow2(max(hr) + 1, 8)
        Hs_pad = next_pow2(max(hs), 8)
    else:
        Hr_pad = Hs_pad = 0
    return plan, merged_to, counts, np.asarray(hs, dtype=np.int64), Hr_pad, Hs_pad


@partial(jax.jit, static_argnames=("plan", "Hr_pad", "Hs_pad"))
def lane_bucketed(row_ptr, col, ew, edge_u, n, merged_to, Rs, Hs, *,
                  plan: tuple, Hr_pad: int, Hs_pad: int):
    """Vmapped device bucketed-view build under the shared ``plan``.

    Returns (buckets, heavy, gather_idx) with (L, ...) leaves, each lane's
    exactly the sequential :func:`build_bucketed_view_device` layout."""
    from ..utils import compile_stats

    compile_stats.record(
        "lane_bucketed", arrays=[col], statics=(plan, Hr_pad, Hs_pad)
    )
    idt = col.dtype

    def one(rp, c, w_, eu, n_i, m2, r_row, hs_i):
        gi = jnp.full(rp.shape[0] - 1, -1, dtype=idt)
        bks = []
        base = 0
        for ci, (wd, r_pad) in enumerate(plan):
            nodes, cols_b, wgts_b, gi = _unwrap(_device_bucket)(
                rp, c, w_, gi, n_i, m2, jnp.asarray(base), r_row[ci],
                w=wd, R_pad=r_pad,
            )
            bks.append(Bucket(nodes, cols_b, wgts_b))
            base += r_pad
        if Hr_pad:
            hnodes, hrow, hcols, hw, gi = _unwrap(_device_heavy)(
                rp, c, w_, eu, gi, n_i, jnp.asarray(base), hs_i,
                Hr_pad=Hr_pad, Hs_pad=Hs_pad,
            )
            hv = HeavyPart(hnodes, hrow, hcols, hw)
        else:
            z = jnp.zeros(0, dtype=idt)
            hv = HeavyPart(z, z, z, z)
        return tuple(bks), hv, gi

    return jax.vmap(one)(row_ptr, col, ew, edge_u, n, merged_to, Rs, Hs)


# ---------------------------------------------------------------------------
# Stacked refinement kernels (balancer round, LP refine, quality metrics,
# projection, keep-best selection).
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("k",))
def lane_balance_round(keys, labels, buckets, heavy, gather_idx, node_w,
                       max_bw, active, *, k: int):
    """Vmapped overload-balancer round; (labels (L, n_pad), flags (L, 2)).

    Each lane runs the sequential round unchanged (its pads gather no row
    and keep the sequential defaults).  ``active``
    ((L,) bool) freezes lanes whose sequential round loop already exited —
    their labels pass through unchanged and their (discarded) flags rows
    are computed from the frozen labels."""
    from ..refinement.balancer import _balance_round
    from ..utils import compile_stats

    compile_stats.record("lane_balance", arrays=[node_w], statics=(k,))

    def one(ky, lb, bks, hv, gi, nw, mb, act):
        new_lb, flags = _unwrap(_balance_round)(ky, lb, bks, hv, gi, nw, mb, k=k)
        return jnp.where(act, new_lb, lb), flags

    return jax.vmap(one)(
        keys, labels, buckets, heavy, gather_idx, node_w, max_bw, active
    )


@partial(
    jax.jit,
    static_argnames=("num_labels", "active_prob", "allow_tie_moves"),
)
def lane_lp_refine(labels, keys, buckets, heavy, gather_idx, node_w, max_w,
                   min_moved, iters, *,
                   num_labels: int, active_prob: float,
                   allow_tie_moves: bool):
    """Vmapped LP-refiner pass (init_state + fused sweep loop);
    returns the refined (L, n_pad) labels.  ``max_w`` is (L, num_labels) —
    per-lane block budgets padded to the shared label bucket."""
    from ..utils import compile_stats

    compile_stats.record(
        "lane_lp_refine",
        arrays=[node_w, *(b.cols for b in buckets), heavy.cols],
        statics=(num_labels, active_prob, allow_tie_moves),
    )

    def one(lb, ky, bks, hv, gi, nw, mw, mm, it):
        state = _unwrap(lp.init_state)(lb, nw, num_labels)
        state = _unwrap(lp.lp_iterate_bucketed)(
            state, ky, bks, hv, gi, nw, mw,
            mm.astype(jnp.int32), it.astype(jnp.int32),
            num_labels=num_labels, active_prob=active_prob,
            allow_tie_moves=allow_tie_moves, tie_break="uniform",
        )
        return state.labels

    return jax.vmap(one)(
        labels, keys, buckets, heavy, gather_idx, node_w, max_w,
        min_moved, iters,
    )


@partial(jax.jit, static_argnames=("k",))
def lane_quality(labels, node_w, edge_u, col_idx, edge_w, *, k: int):
    """(L, 1 + k) stacked [edge_cut, block_weights...] — the keep-best rank
    inputs of a whole refinement step in ONE dispatch + one readback."""
    from ..utils import compile_stats

    compile_stats.record("lane_quality", arrays=[labels], statics=(k,))

    def one(lb, nw, eu, ci, ew):
        cut = jnp.sum(jnp.where(lb[eu] != lb[ci], ew, 0)) // 2
        bw = jax.ops.segment_sum(nw, lb, num_segments=k)
        return jnp.concatenate([cut[None].astype(nw.dtype), bw])

    return jax.vmap(one)(labels, node_w, edge_u, col_idx, edge_w)


@jax.jit
def lane_project(coarse_of, coarse_labels):
    """Vmapped uncoarsening projection: fine[l, u] = coarse[l, coarse_of[l, u]].
    Fine pad nodes map through the anchor cluster to the coarse pad slots,
    which carry label 0 — the sequential pad convention."""

    def one(co, cl):
        return cl[co]

    return jax.vmap(one)(coarse_of, coarse_labels)


@jax.jit
def lane_select_best(snapshots, best_idx):
    """Per-lane keep-best selection over stacked label snapshots:
    ``snapshots`` (S, L, n_pad), ``best_idx`` (L,) — returns (L, n_pad)."""
    return jnp.take_along_axis(
        snapshots, best_idx[None, :, None], axis=0
    )[0]
