"""LP refiner: the LP engine with blocks as clusters.

Reference: ``kaminpar-shm/refinement/lp/lp_refiner.cc`` — instantiates the
shared LP engine with ClusterID = BlockID, so nodes greedily move to the
adjacent block with maximal connection weight subject to the block weight
limits.  Here this is literally the same jitted round as coarsening LP with
``num_labels = k`` (SURVEY §7 stage 6).
"""

from __future__ import annotations

import jax.numpy as jnp

from ..context import LabelPropagationContext
from ..graph.partitioned import PartitionedGraph
from ..ops import lp
from ..utils import next_key
from ..utils.timer import scoped_timer
from .refiner import Refiner


class LPRefiner(Refiner):
    def __init__(self, ctx: LabelPropagationContext):
        self.ctx = ctx

    def refine(self, p_graph: PartitionedGraph) -> PartitionedGraph:
        pv = p_graph.graph.padded()
        # Finest level under device_decode (ISSUE 10): the graph was
        # materialized from a DeviceCompressedView and still carries it —
        # this pass rates blocks straight off the compressed stream
        # (decode-fused kernels) instead of the dense bucketed layout.
        # Bit-identical to the dense pass (same key draw, same round math).
        cview = getattr(p_graph.graph, "_compressed_view", None)
        bv = None if cview is not None else p_graph.graph.bucketed()
        k = p_graph.k
        # Label-space shape bucket: all intermediate k of the extension
        # ladder share one compiled kernel per graph (pad labels are inert;
        # see lp.num_labels_bucket).
        k_pad = lp.num_labels_bucket(k)
        part = pv.pad_node_array(p_graph.partition, 0)  # pads are inert (w=0)
        state = lp.init_state(part, pv.node_w, k_pad)
        max_w = jnp.asarray(p_graph.max_block_weights, dtype=pv.node_w.dtype)
        if k_pad > k:
            max_w = jnp.concatenate(
                [max_w, jnp.zeros(k_pad - k, dtype=max_w.dtype)]
            )

        from ..ops.pallas_lp import select_compressed_iterate, select_lp_ops

        with scoped_timer("lp_refinement", sync=True) as ts:
            # One dispatch, zero readbacks: the sweep loop and its
            # convergence test run on device (lp.lp_iterate_bucketed), and
            # the state carry is donated into the kernel.
            if cview is not None:
                iterate = select_compressed_iterate(self.ctx.lp_kernel)
                state = iterate(
                    state,
                    next_key(),
                    cview.buckets,
                    cview.stream,
                    cview.heavy,
                    cview.gather_idx,
                    pv.node_w,
                    max_w,
                    jnp.int32(int(self.ctx.min_moved_fraction * pv.n)),
                    jnp.int32(self.ctx.num_iterations),
                    num_labels=k_pad,
                    active_prob=self.ctx.active_prob,
                    allow_tie_moves=self.ctx.allow_tie_moves,
                )
            else:
                iterate = select_lp_ops(self.ctx.lp_kernel)[0]
                state = iterate(
                    state,
                    next_key(),
                    bv.buckets,
                    bv.heavy,
                    bv.gather_idx,
                    pv.node_w,
                    max_w,
                    jnp.int32(int(self.ctx.min_moved_fraction * pv.n)),
                    jnp.int32(self.ctx.num_iterations),
                    num_labels=k_pad,
                    active_prob=self.ctx.active_prob,
                    allow_tie_moves=self.ctx.allow_tie_moves,
                )
            lp.note_rounds(state)
            ts.note(state.labels)
            # Zero-transfer pass marker: moved count and cut deliberately
            # stay on device here (this refiner's contract is zero
            # readbacks); the sizes are the host-known record, and the
            # spine's next existing pull carries the level's cut
            # (telemetry/probes.pull_partition_with_quality).
            from ..telemetry import probes

            probes.refinement_pass(
                "lp_refinement", n=pv.n, k=k, rounds_budget=self.ctx.num_iterations
            )
        return p_graph.with_partition(state.labels[: pv.n])
