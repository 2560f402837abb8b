"""Device-side partition extension — batched over all blocks (round 5).

Reference: ``extend_partition`` (kaminpar-shm/partitioning/helper.cc:349)
extracts every block-induced subgraph (subgraph_extractor.h:176) and
recursively bipartitions each on its own.  The TPU redesign avoids per-block
extraction entirely; extension is ONE restricted nested multilevel over the
whole graph:

1. **Restricted coarsening (device)**: coarsen with communities = the
   current blocks, so clusters never span blocks — the same masked-rating
   machinery v-cycle coarsening already uses (cluster_coarsener.coarsen_once).
2. **Extension of the coarsest level only**: the nested coarsest graph
   (~``device_extension_cpb`` coarse nodes per new block) goes through the
   existing pool machinery per block — host BFS/GGG/random + 2-way FM, or
   the lane-vmapped device pool when ``ip_backend`` resolves to device
   (round 9: each bisection then costs one dispatch + one readback instead
   of a Python repetition loop).  This is the only non-device-resident
   step, O(n_coarsest) instead of O(n) per level.
3. **Restricted uncoarsening (device)**: project up; at each level zero the
   cross-block edge weights and run the grouped overload balancer + the LP
   refiner with the intermediate new-k budgets.  Ratings of masked edges are
   0 and the LP engine only adopts labels with rating > 0, so candidate
   labels never leave the parent block; the balancer's lightest-block
   fallback is group-restricted explicitly (refinement/balancer.py).

All blocks' splits thus run batched inside the same dense kernels — the
TPU-native answer to "bipartition many blocks in parallel" — and the
per-level host extraction that dominated large-k extension (~43% of wall in
the round-3 largek proof) disappears.
"""

from __future__ import annotations

import numpy as np

from ..utils import sync_stats
from ..utils.logger import Logger, OutputLevel
from .partition_utils import intermediate_block_weights, split_offsets


def extend_partition_device(graph, part, cur_k: int, new_k: int, ctx) -> np.ndarray:
    import jax.numpy as jnp

    from ..coarsening.cluster_coarsener import ClusterCoarsener

    final_bw = np.asarray(ctx.partition.max_block_weights, dtype=np.int64)
    k = len(final_bw)
    off_new = split_offsets(k, new_k)
    off_cur = split_offsets(k, cur_k)
    lo_of = np.searchsorted(off_new, off_cur)
    assert np.array_equal(off_new[lo_of], off_cur), "split refinement violated"
    # parent (current block) of each new block
    parent_of_new = (
        np.searchsorted(lo_of, np.arange(new_k), side="right") - 1
    ).astype(np.int32)

    ipc = ctx.initial_partitioning
    coarsener = ClusterCoarsener(ctx, graph)
    coarsener.set_communities(jnp.asarray(part, dtype=jnp.int32))
    target_n = max(
        new_k * ipc.device_extension_cpb, 2 * ctx.coarsening.contraction_limit
    )
    coarsener.coarsen(new_k, ctx.partition.epsilon, target_n)
    coarsest = coarsener.current_graph
    coarse_comm = sync_stats.pull(
        coarsener.current_communities, phase="extend_partition"
    ).astype(np.int32)
    Logger.log(
        f"  device-ext: n={graph.n} coarsened to {coarsest.n} "
        f"({coarsener.num_levels} nested levels) for k {cur_k}->{new_k}",
        OutputLevel.DEBUG,
    )

    # Host pool machinery on the tiny coarsest level only.
    from .deep import _extend_partition_host

    cpart = _extend_partition_host(coarsest, coarse_comm, cur_k, new_k, ctx)

    inter_bw = intermediate_block_weights(final_bw, new_k)
    part_dev = jnp.asarray(cpart, dtype=jnp.int32)
    while True:
        level_graph = coarsener.current_graph
        comm = coarsener.current_communities
        part_dev = _restricted_refine(
            level_graph, part_dev, comm, new_k, parent_of_new, inter_bw, ctx
        )
        if coarsener.num_levels == 0:
            break
        part_dev = coarsener.uncoarsen(part_dev)
    return sync_stats.pull(part_dev, phase="extend_partition").astype(np.int32)


def _restricted_refine(graph, part, comm, new_k, parent_of_new, inter_bw, ctx):
    """Grouped balancing + community-restricted LP on one nested level."""
    import jax.numpy as jnp

    from ..graph.csr import CSRGraph
    from ..ops import lp as lp_ops
    from ..refinement.balancer import _balance_round
    from ..utils import next_key, sync_stats

    masked_ew = jnp.where(
        comm[graph.edge_u] == comm[graph.col_idx], graph.edge_w, 0
    )
    mg = CSRGraph(
        graph.row_ptr, graph.col_idx, graph.node_w, masked_ew,
        sorted_by_degree=graph.sorted_by_degree, edge_u=graph.edge_u,
    )
    mg._deg_hist = graph._deg_hist
    mg._layout_mode = graph._layout_mode
    mg._host_row_ptr = graph._host_row_ptr
    pv = mg.padded()
    bv = mg.bucketed()
    # Relax caps by the level's max node weight (deep._refine's coarse
    # branch): coarse nodes are chunky relative to the new-block budgets.
    eps = ctx.partition.epsilon
    relaxed = np.ceil(inter_bw / (1.0 + eps)).astype(np.int64) + int(
        graph.max_node_weight
    )
    max_bw = jnp.asarray(
        np.maximum(inter_bw, relaxed), dtype=pv.node_w.dtype
    )
    labels = pv.pad_node_array(part, 0)
    group_of = jnp.asarray(parent_of_new)

    for _ in range(ctx.refinement.balancer.max_num_rounds):
        labels, flags = _balance_round(
            next_key(), labels, bv.buckets, bv.heavy, bv.gather_idx,
            pv.node_w, max_bw, k=new_k, group_of=group_of,
        )
        num_moved, still = sync_stats.pull(flags)
        if not still or num_moved == 0:
            break

    lctx = ctx.refinement.lp
    state = lp_ops.init_state(labels, pv.node_w, new_k)
    state = lp_ops.lp_iterate_bucketed(
        state, next_key(), bv.buckets, bv.heavy, bv.gather_idx, pv.node_w,
        max_bw, jnp.int32(int(lctx.min_moved_fraction * pv.n)),
        jnp.int32(lctx.num_iterations), num_labels=new_k,
        active_prob=lctx.active_prob, allow_tie_moves=lctx.allow_tie_moves,
    )
    lp_ops.note_rounds(state)
    return state.labels[: pv.n]
