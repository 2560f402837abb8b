"""LP clusterer: the LP engine instantiated for coarsening.

Reference: ``kaminpar-shm/coarsening/clustering/lp_clusterer.cc`` — clustering
labels are node ids (ClusterID = NodeID), up to ``num_iterations`` sweeps with
early break on (near-)zero moves (lp_clusterer.cc:94-105), followed by
isolated-node and two-hop handling (:107-162).

Runs on the graph's shape-bucketed :class:`PaddedView`: pad nodes start in the
anchor's cluster and never move (they have no edges), so one compile per
power-of-2 bucket serves every hierarchy level of that size.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..context import LabelPropagationContext
from ..graph.csr import CSRGraph
from ..ops import lp
from ..utils import next_key
from ..utils.timer import scoped_timer


@jax.jit
def _intersect_clusterings(la, lb):
    """Overlay intersection: u, v share a cluster iff they share one in BOTH
    inputs (reference: overlay_cluster_coarsener.cc).  Labels stay node ids
    (the LP convention): each (la, lb) run is relabeled to its smallest
    member."""
    n = la.shape[0]
    from ..ops.segment import cumsum, run_starts2
    from ..ops.sort import argsort

    order = argsort((la, lb))
    first = run_starts2(la[order], lb[order])
    rid = cumsum(first.astype(jnp.int32)) - 1
    rep = jax.ops.segment_min(order.astype(la.dtype), rid, num_segments=n)
    return jnp.zeros_like(la).at[order].set(rep[rid])


_warned_geometric = False


class LPClustering:
    def __init__(
        self,
        ctx: LabelPropagationContext,
        overlay_levels: int = 1,
        *,
        weighted_graph: bool = False,
    ):
        self.ctx = ctx
        self.overlay_levels = max(int(overlay_levels), 1)
        # Device scalar of the last clustering's final-round moved count;
        # batched into the coarsening level's single readback.
        self.last_num_moved = None
        # Set by the coarsener from the *input* graph's edge weights (the
        # gate must not flip mid-hierarchy as contraction accumulates
        # weights); see the weighted-graph mode note in _one_clustering.
        self.weighted_graph = weighted_graph
        global _warned_geometric
        if ctx.tie_breaking.value == "geometric" and not _warned_geometric:
            # Kernels implement 'uniform' and 'lightest' only; surface the
            # degradation instead of silently ignoring the configured
            # strategy.  Once per process: __init__ re-runs per hierarchy
            # level and per dist replica worker.
            _warned_geometric = True
            from ..utils.logger import Logger

            Logger.warning(
                "lp: tie_breaking=geometric is not implemented by the TPU "
                "kernels; falling back to uniform tie-breaking"
            )

    def _run_iterate(self, iterate, xla_iterate, *args, **kwargs):
        """Dispatch one LP sweep loop.  A failing fused-Pallas dispatch is
        raised as its classified error (resilience/errors.py): there is no
        silent rerun on the XLA twin, so a run that returns used the kernel
        it was configured with."""
        if iterate is xla_iterate:
            return xla_iterate(*args, **kwargs)
        from ..resilience.errors import classify
        from ..resilience.faults import maybe_inject

        try:
            maybe_inject("execute", site="lp_pallas")
            return iterate(*args, **kwargs)
        except Exception as exc:
            raise classify(exc, site="lp_pallas") from exc

    def compute_clustering(self, graph, max_cluster_weight: int):
        """Returns padded labels (over graph.padded(), or the equal-shape
        label space of a :class:`~kaminpar_tpu.graph.device_compressed.
        DeviceCompressedView` — the two share ``n_pad``); pad nodes carry
        the anchor label.  Fully device-resident: no blocking readback
        happens here — the per-clustering moved count stays on device as
        ``self.last_num_moved`` so the coarsener can batch it into the
        level's single readback."""
        with scoped_timer("lp_clustering", sync=True) as ts:
            labels = self._one_clustering(graph, max_cluster_weight)
            # Overlay: intersect independent clusterings (rounder clusters;
            # randomized-run variance cancels).  Intersection only splits
            # clusters, so the weight cap stays respected.
            for _ in range(self.overlay_levels - 1):
                other = self._one_clustering(graph, max_cluster_weight)
                labels = _intersect_clusterings(labels, other)
            ts.note(labels)
        return labels

    def _one_clustering(self, graph, max_cluster_weight: int):
        from ..graph.device_compressed import DeviceCompressedView

        if isinstance(graph, DeviceCompressedView):
            return self._one_clustering_compressed(graph, max_cluster_weight)
        pv = graph.padded()
        bv = graph.bucketed()
        n_pad = pv.n_pad
        idt = pv.row_ptr.dtype
        labels = jnp.concatenate(
            [
                jnp.arange(pv.n, dtype=idt),
                jnp.full(n_pad - pv.n, pv.anchor, dtype=idt),
            ]
        )
        state = lp.init_state(labels, pv.node_w, n_pad)
        # scalar, not a per-cluster table: the clustering weight limit is
        # uniform and a scalar saves one m-sized gather per round
        max_w = jnp.asarray(int(max_cluster_weight), dtype=idt)

        iters = self.ctx.num_iterations
        active_prob = self.ctx.active_prob
        if self.weighted_graph:
            # Weighted-graph mode (round-4 road-class levers, VERDICT r3
            # next #1): on graphs with non-uniform edge weights the
            # synchronous bulk adoption merges across light-edge valleys —
            # the exact cuts a good partition routes through — because a
            # whole neighborhood adopts one attractor label in a single
            # round.  Emulating the reference's *asynchronous* incremental
            # growth (label_propagation.h processes nodes in-place) with a
            # small random active fraction and proportionally more sweeps
            # preserves the valley structure: road512 k=2 coarse-space
            # optimum improved from ~2.0x fine-optimum to ~1.07x (measured
            # ladder: active 1.0 -> 1434, 0.25 -> 1218, 0.1 -> 1180,
            # 0.05 -> 1373 vs reference 1103).  Replaces the low-degree
            # sweep boost on this class (same remedy, weaker form).
            active_prob = min(active_prob, self.ctx.weighted_active_prob)
            iters *= max(self.ctx.weighted_sweep_factor, 1)
        elif (
            graph.n > 0
            and graph.m / graph.n < self.ctx.low_degree_boost_threshold
        ):
            # see LabelPropagationContext.low_degree_boost_threshold
            iters *= max(self.ctx.low_degree_boost_factor, 1)
        from ..ops.pallas_lp import select_lp_ops

        iterate = select_lp_ops(self.ctx.lp_kernel)[0]
        state = self._run_iterate(
            iterate,
            lp.lp_iterate_bucketed,
            state,
            next_key(),
            bv.buckets,
            bv.heavy,
            bv.gather_idx,
            pv.node_w,
            max_w,
            jnp.int32(int(self.ctx.min_moved_fraction * pv.n)),
            jnp.int32(iters),
            num_labels=n_pad,
            active_prob=active_prob,
            tie_break=self.ctx.tie_breaking.value,
        )
        lp.note_rounds(state)

        if self.ctx.cluster_isolated_nodes:
            state = lp.cluster_isolated_nodes(
                state, pv.row_ptr, pv.node_w, max_w, num_labels=n_pad
            )
        if self.ctx.cluster_two_hop_nodes:
            state = lp.cluster_two_hop_nodes_bucketed(
                state,
                next_key(),
                bv.buckets,
                bv.heavy,
                bv.gather_idx,
                pv.node_w,
                max_w,
                num_labels=n_pad,
            )
        # Device scalar — NOT pulled here; the coarsener packs it into the
        # level's single batched readback (contract_clustering).
        self.last_num_moved = state.num_moved
        return state.labels

    def _one_clustering_compressed(self, cv, max_cluster_weight: int):
        """The clustering sweep off the device-resident compressed stream
        (ISSUE 10 tentpole): the same label space (``n_pad`` matches the
        dense PaddedView), the same key-draw order (one iterate key, one
        two-hop key), and the decode-fused round kernels — bit-identical
        labels to the dense sweep on the decompressed graph (asserted in
        tests/test_device_compressed.py)."""
        n_pad = cv.n_pad
        idt = cv.node_w_pad.dtype
        labels = jnp.concatenate(
            [
                jnp.arange(cv.n, dtype=idt),
                jnp.full(n_pad - cv.n, cv.anchor, dtype=idt),
            ]
        )
        state = lp.init_state(labels, cv.node_w_pad, n_pad)
        max_w = jnp.asarray(int(max_cluster_weight), dtype=idt)

        iters = self.ctx.num_iterations
        active_prob = self.ctx.active_prob
        if self.weighted_graph:
            # Same weighted-graph emulation as the dense branch (see
            # _one_clustering) — the mode is pinned from the input graph,
            # so both paths take the same parameters.
            active_prob = min(active_prob, self.ctx.weighted_active_prob)
            iters *= max(self.ctx.weighted_sweep_factor, 1)
        elif (
            cv.n > 0 and cv.m / cv.n < self.ctx.low_degree_boost_threshold
        ):
            iters *= max(self.ctx.low_degree_boost_factor, 1)
        from ..ops.pallas_lp import select_compressed_iterate

        iterate = select_compressed_iterate(self.ctx.lp_kernel)
        state = self._run_iterate(
            iterate,
            lp.lp_iterate_compressed,
            state,
            next_key(),
            cv.buckets,
            cv.stream,
            cv.heavy,
            cv.gather_idx,
            cv.node_w_pad,
            max_w,
            jnp.int32(int(self.ctx.min_moved_fraction * cv.n)),
            jnp.int32(iters),
            num_labels=n_pad,
            active_prob=active_prob,
            tie_break=self.ctx.tie_breaking.value,
        )
        lp.note_rounds(state)

        if self.ctx.cluster_isolated_nodes:
            state = lp.cluster_isolated_nodes(
                state, cv.row_ptr_like(), cv.node_w_pad, max_w,
                num_labels=n_pad,
            )
        if self.ctx.cluster_two_hop_nodes:
            state = lp.cluster_two_hop_nodes_compressed(
                state,
                next_key(),
                cv.buckets,
                cv.stream,
                cv.heavy,
                cv.gather_idx,
                cv.node_w_pad,
                max_w,
                num_labels=n_pad,
            )
        self.last_num_moved = state.num_moved
        return state.labels
