"""JET refiner: filtered bulk moves with best-snapshot rollback.

Reference: ``kaminpar-shm/refinement/jet/jet_refiner.cc`` (Gilbert et al.'s
GPU algorithm — already bulk-synchronous, hence the designated TPU-native
quality refiner per SURVEY §7 stage 7).  Per iteration:

1. **Find** (jet_refiner.cc:104-132): every unlocked border node picks its
   best external block by gain, kept as a candidate if
   ``gain > -floor(temp * conn(u, from))`` — the temperature admits negative
   moves to escape local minima.
2. **Filter** (:135-170): candidate u re-evaluates its gain under the
   assumption that every candidate neighbor v with higher priority
   (``gain_v > gain_u`` or equal and ``v < u``) executes its move; u stays a
   candidate only if this pessimistic gain is positive.  On TPU this is one
   edge-parallel masked segment-sum — no sort needed.
3. **Execute** moves unconditionally (may violate balance), **rebalance**
   with the overload balancer, and snapshot the best feasible partition
   (:173-199).  Locked (= just moved) nodes sit out the next find phase.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..context import BalancerContext, JetContext
from ..graph.partitioned import PartitionedGraph
from ..ops.bucketed_gains import bucketed_best_moves, bucketed_neighbor_reduce
from ..utils import next_key
from ..utils.timer import scoped_timer
from .balancer import OverloadBalancer
from .refiner import Refiner


@partial(jax.jit, static_argnames=("k",))
def _jet_move_round(key, labels, locked, buckets, heavy, gather_idx, node_w, max_bw, temp, *, k: int):
    block_weights = jax.ops.segment_sum(node_w, labels, num_segments=k)

    # --- find -------------------------------------------------------------
    target, tconn, oconn, has = bucketed_best_moves(
        key, labels, buckets, heavy, gather_idx, node_w, block_weights, max_bw,
        external_only=True, respect_caps=False,
    )
    gain = tconn - oconn
    threshold = -jnp.floor(temp * oconn.astype(jnp.float32)).astype(gain.dtype)
    cand = has & ~locked & (gain > threshold)

    # --- filter (pessimistic gain over neighbors) -------------------------
    def contrib_fn(urow, cols, w):
        gu = gain[urow]
        gv = gain[cols]
        v_before = cand[cols] & ((gv > gu) | ((gv == gu) & (cols < urow)))
        eff_v = jnp.where(v_before, target[cols], labels[cols])
        return jnp.where(eff_v == target[urow], w, 0) - jnp.where(
            eff_v == labels[urow], w, 0
        )

    gain2 = bucketed_neighbor_reduce(contrib_fn, buckets, heavy, gather_idx)
    move = cand & (gain2 > 0)

    new_labels = jnp.where(move, target, labels)
    return new_labels, move


class JetRefiner(Refiner):
    def __init__(self, ctx: JetContext, balancer_ctx: BalancerContext, *, coarse_level: bool = False):
        self.ctx = ctx
        self.balancer = OverloadBalancer(balancer_ctx)
        self.coarse_level = coarse_level

    def refine(self, p_graph: PartitionedGraph) -> PartitionedGraph:
        # "4xjet"-style chaining (reference: create_jet_context(num_rounds)).
        for _ in range(max(self.ctx.num_rounds, 1)):
            p_graph = self._refine_once(p_graph)
        return p_graph

    def _refine_once(self, p_graph: PartitionedGraph) -> PartitionedGraph:
        pv = p_graph.graph.padded()
        bv = p_graph.graph.bucketed()
        k = p_graph.k
        ctx = self.ctx
        max_bw = jnp.asarray(p_graph.max_block_weights, dtype=pv.node_w.dtype)
        if self.coarse_level:
            t0 = ctx.initial_gain_temp_on_coarse_level
            t1 = ctx.final_gain_temp_on_coarse_level
        else:
            t0 = ctx.initial_gain_temp_on_fine_level
            t1 = ctx.final_gain_temp_on_fine_level

        p_graph = self.balancer.refine(p_graph)
        best = p_graph
        best_cut = p_graph.edge_cut()
        labels = pv.pad_node_array(p_graph.partition, 0)
        locked = jnp.zeros(pv.n_pad, dtype=bool)
        fruitless = 0

        with scoped_timer("jet_refinement"):
            for it in range(ctx.num_iterations):
                # Linear temperature anneal initial -> final across the
                # iteration budget (reference: jet_refiner.cc schedules).
                frac = it / max(ctx.num_iterations - 1, 1)
                temp = t0 + (t1 - t0) * frac
                labels, moved = _jet_move_round(
                    next_key(), labels, locked, bv.buckets, bv.heavy, bv.gather_idx,
                    pv.node_w, max_bw, jnp.float32(temp), k=k,
                )
                locked = moved
                cur = self.balancer.refine(p_graph.with_partition(labels[: pv.n]))
                labels = pv.pad_node_array(cur.partition, 0)
                cut = cur.edge_cut()
                if cut <= best_cut and cur.is_feasible():
                    if best_cut - cut > (1.0 - ctx.fruitless_threshold) * best_cut:
                        fruitless = 0
                    else:
                        fruitless += 1
                    best, best_cut = cur, cut
                else:
                    fruitless += 1
                if fruitless >= self.ctx.num_fruitless_iterations:
                    break
        return best
