"""Cluster contraction as a sort-reduce kernel.

TPU-native counterpart of the reference's contraction algorithms
(``kaminpar-shm/coarsening/contraction/`` — buffered / unbuffered two-pass
with per-thread edge buffers, unbuffered_cluster_contraction.cc:35-70).  On
TPU the whole thing is the classic sort-reduce (SURVEY §7 stage 4):

1. relabel-compact cluster ids via presence scatter + prefix sum,
2. map both edge endpoints to coarse ids, drop intra-cluster edges,
3. sort edges by (coarse_u, coarse_v) and sum weights per run,
4. compact runs to the front and build the coarse CSR.

Device-residency contract (ISSUE 2): all device work uses static
(fine-bucket) shapes, the coarse graph is extracted into *padded device
buffers* on the geometric shape ladder (one fused slice+pad kernel, fine
buffers donated so the ladder does not accumulate HBM copies), and the host
learns everything it needs about a level — ``n_c``, ``m_c``, the coarse max
node weight, the coarse total edge weight, the degree histogram that seeds
the bucketed layout, plus any caller scalars (LP moved-count) — from ONE
batched scalar readback per level (``utils/sync_stats.pull``).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from ..graph.bucketed import WIDTH_CLASSES, device_deg_histogram
from ..graph.csr import CSRGraph, PaddedView, _next_bucket
from ..utils import sync_stats
from .segment import compact, cumsum, run_ids, run_starts2
from .sort import argsort

# stats layout: [n_c_full, m_c, max_node_w, total_edge_w, hist*10, Hr, Hs]
STATS_LEN = 4 + len(WIDTH_CLASSES) + 2


def _edge_sort_perm(ku, kv, sentinel: int):
    """Permutation sorting edges by (ku, kv) with original order on ties.

    One fused key when the composite key fits the widest enabled integer
    dtype (a scalar comparator), else the two keys (a lexicographic
    comparator — the measurably slower shape on TPU).  Both are stable, so
    the permutations are identical element-for-element (asserted in
    tests/test_contraction.py).
    """
    kdt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    if (sentinel + 1) * (sentinel + 2) <= jnp.iinfo(kdt).max:
        return argsort((ku.astype(kdt) * (sentinel + 1) + kv.astype(kdt),))
    return argsort((ku, kv))


def _contract_core(labels, edge_u, col_idx, edge_w, node_w):
    from ..utils import compile_stats

    compile_stats.record("contraction", arrays=[labels, col_idx])
    n = labels.shape[0]
    m = col_idx.shape[0]
    idt = labels.dtype

    # 1. relabel-compact: cluster label -> dense coarse id
    present = jnp.zeros(n, dtype=jnp.int32).at[labels].set(1)
    cmap = (cumsum(present) - 1).astype(idt)
    coarse_of = cmap[labels]
    n_c = jnp.sum(present)

    # coarse node weights (slots >= n_c are zero)
    c_node_w = jax.ops.segment_sum(
        node_w, coarse_of, num_segments=n
    )

    # 2./3. coarse edge aggregation
    cu = coarse_of[edge_u]
    cv = coarse_of[col_idx]
    keep = cu != cv
    ku = jnp.where(keep, cu, n)  # sentinel key sorts dropped edges last
    kv = jnp.where(keep, cv, 0)
    order = _edge_sort_perm(ku, kv, n)
    su, sv = ku[order], kv[order]
    sw = jnp.where(keep[order], edge_w[order], 0)
    first = run_starts2(su, sv)
    rid = run_ids(first)
    run_w = jax.ops.segment_sum(sw, rid, num_segments=m, indices_are_sorted=True)

    # 4. compact valid runs to the front
    valid = first & (su < n)
    out_u = compact(su, valid, m)
    out_v = compact(sv, valid, m)
    out_w = compact(run_w[rid], valid, m)
    m_c = jnp.sum(valid)

    # coarse row_ptr over the full n-slot buffer (sliced to n_c+1 later)
    deg_c = jax.ops.segment_sum(
        valid.astype(jnp.int32), jnp.where(valid, su, 0).astype(jnp.int32), num_segments=n
    )
    # nodes with no kept edges still need zero-degree rows; segment over su
    # only counts runs, and `where(valid, su, 0)` routes dropped runs to node 0
    # with value 0, which is harmless.
    row_ptr = jnp.concatenate(
        [jnp.zeros(1, dtype=idt), cumsum(deg_c).astype(idt)]
    )

    # Per-level host scalars, batched: everything the orchestration loop
    # needs to know about this level in ONE small array (pulled once by
    # contract_clustering).  The degree histogram covers the real coarse
    # nodes (the pure-padding anchor cluster, always last, has degree 0 and
    # is excluded along with the n_c slice).
    real = jnp.arange(n, dtype=jnp.int32) < (n_c - 1)
    # Weight totals accumulate in the widest enabled integer dtype; in the
    # default 32-bit build that is int32, which is exact under the repo-wide
    # invariant that total node/edge weight stays below 2^31 (ops/lp.py
    # module contract — every weight reduction in the system shares it; the
    # 64-bit build carries int64 end to end).
    wsum_dt = jnp.int64 if jax.config.jax_enable_x64 else idt
    stats = jnp.concatenate(
        [
            jnp.stack(
                [
                    n_c.astype(idt),
                    m_c.astype(idt),
                    jnp.max(c_node_w).astype(idt),
                    jnp.sum(out_w.astype(wsum_dt)).astype(idt),
                ]
            ),
            device_deg_histogram(deg_c.astype(idt), real),
        ]
    )
    return coarse_of, stats, c_node_w, out_u, out_v, out_w, row_ptr


_contract_device = partial(jax.jit, donate_argnums=(0,))(_contract_core)


@partial(jax.jit, donate_argnums=(0,), static_argnames=("m_pad",))
def _contract_compressed_device(labels, stream, wstart, width, deg, node_w, *,
                                m_pad: int):
    """Contraction straight off the compressed stream: the flat decode
    (graph/device_compressed.decode_flat_padded) feeds the contraction
    sort-reduce *inside one fused program*, so the decoded edge arrays are
    XLA transients of this dispatch — no resident dense CSR exists at the
    finest level.  ``m_pad`` is the same geometric bucket the dense
    PaddedView would use, so the contraction kernel shape (and the coarse
    graph, bit for bit) matches the dense path."""
    from ..graph.device_compressed import decode_flat_padded

    _, col, ew, eu = decode_flat_padded(stream, wstart, width, deg, m_pad=m_pad)
    return _contract_core(labels, eu, col, ew, node_w)


@partial(jax.jit, static_argnames=("n_pad", "m_pad"))
def _extract_padded(row_ptr, c_node_w, out_u, out_v, out_w, n_c, m_c, *,
                    n_pad: int, m_pad: int):
    """Slice+pad the fine-bucket contraction buffers straight into the coarse
    graph's PaddedView arrays (geometric shape ladder): pad nodes weight-0 /
    degree-0, pad edges weight-0 anchor self-loops.  The fine-sized inputs
    die with this call (their handles are dropped by contract_clustering),
    so the only survivors of a level are bucket-sized — donation is useless
    here because XLA cannot alias across the shape change."""
    idt = row_ptr.dtype
    anchor = jnp.asarray(n_pad - 1, dtype=idt)
    n1 = row_ptr.shape[0] - 1

    i_n1 = jnp.arange(n_pad + 1)
    rp = jnp.where(
        i_n1 <= n_c,
        row_ptr[jnp.minimum(i_n1, n1)],
        m_c.astype(idt),
    ).at[-1].set(jnp.asarray(m_pad, dtype=idt))

    i_n = jnp.arange(n_pad)
    node_ok = i_n < n_c
    safe_n = jnp.minimum(i_n, n1 - 1)
    nw = jnp.where(node_ok, c_node_w[safe_n], 0).astype(idt)

    i_m = jnp.arange(m_pad)
    edge_ok = i_m < m_c
    safe_m = jnp.minimum(i_m, out_v.shape[0] - 1)
    col = jnp.where(edge_ok, out_v[safe_m], anchor).astype(idt)
    eu = jnp.where(edge_ok, out_u[safe_m], anchor).astype(idt)
    ew = jnp.where(edge_ok, out_w[safe_m], 0).astype(idt)
    return rp, col, nw, ew, eu


def contract_clustering(
    graph: CSRGraph, labels_padded, *, extra_scalars=()
) -> Tuple[CSRGraph, jax.Array]:
    """Contract a clustering of graph's nodes into a coarse graph.

    ``labels_padded`` covers the graph's :class:`PaddedView` (pad nodes carry
    the anchor label, forming one pure-padding cluster that is sliced off —
    it is always the *last* coarse id since the anchor is the largest label).
    The labels buffer is donated to the kernel.

    Returns ``(coarse_graph, coarse_of)`` where ``coarse_of[u]`` is the coarse
    node id of fine node ``u`` — the projection map used by uncoarsening
    (reference: ``CoarseGraph::project_up``,
    coarsening/abstract_cluster_coarsener.cc:148-170).

    ``extra_scalars``: device scalars the caller wants in the level's single
    batched readback (the coarsener packs the LP moved-count here); their
    host values are returned as a third element when given.

    One-readback contract: this function performs exactly ONE blocking
    device->host transfer (the packed stats + extras vector).  The coarse
    CSRGraph comes back with its PaddedView, degree histogram,
    ``total_node_weight`` / ``max_node_weight`` / ``total_edge_weight``, and
    ``edge_u`` pre-seeded, so no later property access re-syncs the level.
    """
    pv = graph.padded()
    outs = _contract_device(
        jnp.asarray(labels_padded), pv.edge_u, pv.col_idx, pv.edge_w, pv.node_w
    )
    return _finish_contraction(
        outs, n_fine=graph.n, m_fine=graph.m, layout_mode=graph._layout_mode,
        total_node_weight=graph._total_node_weight, extra_scalars=extra_scalars,
    )


def contract_compressed(cview, labels_padded, *, extra_scalars=()):
    """contract_clustering off a DeviceCompressedView: identical result,
    identical one-readback contract, but the fine adjacency is decoded
    in-trace (see _contract_compressed_device) instead of read from a
    resident PaddedView."""
    outs = _contract_compressed_device(
        jnp.asarray(labels_padded), cview.stream, cview.wstart_pad,
        cview.width_pad, cview.degree_pad, cview.node_w_pad,
        m_pad=cview.m_pad,
    )
    return _finish_contraction(
        outs, n_fine=cview.n, m_fine=cview.m, layout_mode=cview.layout_mode,
        total_node_weight=cview.total_node_weight, extra_scalars=extra_scalars,
    )


def _finish_contraction(outs, *, n_fine: int, m_fine: int, layout_mode,
                        total_node_weight, extra_scalars=()):
    coarse_of, stats, c_node_w, out_u, out_v, out_w, row_ptr = outs
    if extra_scalars:
        idt = stats.dtype
        stats = jnp.concatenate(
            [stats, jnp.stack([jnp.asarray(x).astype(idt) for x in extra_scalars])]
        )
    stats_np = sync_stats.pull(stats)  # THE one blocking transfer of the level
    n_c = int(stats_np[0]) - 1  # drop the pure-padding anchor cluster (always last)
    m_c = int(stats_np[1])
    n_pad = _next_bucket(n_c)
    m_pad = _next_bucket(m_c)
    rp_p, col_p, nw_p, ew_p, eu_p = _extract_padded(
        row_ptr, c_node_w, out_u, out_v, out_w,
        jnp.asarray(n_c), jnp.asarray(m_c), n_pad=n_pad, m_pad=m_pad,
    )

    coarse = CSRGraph(
        rp_p[: n_c + 1],
        col_p[:m_c],
        nw_p[:n_c],
        ew_p[:m_c],
        edge_u=eu_p[:m_c],
    )
    # Seed everything a later phase would otherwise sync for.
    coarse._padded = PaddedView(rp_p, col_p, nw_p, ew_p, eu_p, n_c, m_c)
    from ..utils import compile_stats

    compile_stats.record("padded_bucket", statics=(n_pad, m_pad))
    coarse._layout_mode = layout_mode
    if total_node_weight is not None:
        # Contraction conserves total node weight (pads are weight-0).
        coarse._total_node_weight = total_node_weight
    coarse._max_node_weight = int(stats_np[2])
    coarse._total_edge_weight = int(stats_np[3])
    coarse._deg_hist = stats_np[4:STATS_LEN].astype(int)
    # Telemetry counter sample from the values THIS pull already produced —
    # the per-level quality probes ride the level's one readback (ISSUE 5);
    # no-op when no trace recorder is active.
    from ..telemetry import probes

    probes.contraction_level(
        n=n_fine, m=m_fine, n_c=n_c, m_c=m_c,
        max_node_weight=coarse._max_node_weight,
        total_edge_weight=coarse._total_edge_weight,
    )
    out = (coarse, coarse_of[:n_fine])
    if extra_scalars:
        return out + (tuple(int(x) for x in stats_np[STATS_LEN:]),)
    return out


@jax.jit
def project_partition(coarse_of, coarse_partition):
    """fine_partition[u] = coarse_partition[coarse_of[u]] — a single gather
    (reference: uncoarsening projection, abstract_cluster_coarsener.cc:162)."""
    return coarse_partition[coarse_of]
