"""Lane-stacked serve execution: one vmapped multilevel run per micro-batch.

PR 3's engine micro-batches same-shape-cell requests but executes the
pipeline once per graph; PR 4 built the per-lane RNG substrate.  This module
closes the loop (ISSUE 6): the padded CSR buffers of a whole shape-cell
batch are stacked along a leading lane axis and coarsening → initial
bipartitioning → uncoarsen/refine runs in *lockstep* — every device step is
ONE vmapped program over the stack (ops/lanestack.py) and every per-level
scalar readback is ONE stacked pull for all lanes (lane-accounted in
utils/sync_stats).

**Bit-identity** with sequential ``KaMinPar.compute_partition`` is the hard
contract (tests/test_lanestack.py asserts it across families, buckets, k and
lane counts).  It is engineered, not hoped for:

- every lane owns a :class:`LaneChain` — the exact key chain
  ``utils.rng.RandomState`` would thread through the lane's own sequential
  run (same seed, same split order) — and lockstep steps draw each lane's
  keys from its own chain exactly when the sequential code would (lanes
  whose balancer/coarsening exited early stop drawing, so chains never
  skew);
- host-orchestrated phases that the reference also runs sequentially
  (initial bipartitioning, extension) run *per lane* through the very same
  code paths, with the lane's chain swapped into the thread-local
  ``RandomState`` (:func:`lane_rng`);
- lanes share a stacked dispatch ONLY while their exact kernel shape
  signatures match (padded buckets, bucketed width classes + row pads,
  heavy pads, cur_k): jax's counter-based PRNG is positionally stable only
  at equal draw shapes, so the runner groups lanes by signature and splits
  cohorts when hierarchies diverge (``split`` events are counted in the
  runner's stats) — within a group, ``vmap`` runs literally the sequential
  per-lane computation;
- lanes whose coarsening converges at a different level peel off into
  their own cohort and the remaining lanes continue — the per-lane
  early-exit masking of the ISSUE.

Eligibility is an explicit envelope (:func:`check_eligibility`): the deep
mode with LP coarsening and the (overload-balancer, LP[, underload]) refiner
chain on int32 uniform-edge-weight graphs — the serve preset's
configuration.  Ineligible batches raise :class:`LaneStackUnsupported` and
the engine falls back to the per-graph loop, loudly and counted.
"""

from __future__ import annotations

import copy
import dataclasses
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..context import Context, PartitioningMode, RefinementAlgorithm
from ..graph.bucketed import host_deg_histogram
from ..graph.csr import CSRGraph, PaddedView, _next_bucket
from ..graph.isolated import assign_isolated_nodes, strip_isolated_csr
from ..initial.bipartitioner import HostCSR, recursive_bipartition
from ..ops import lanestack as lops
from ..ops.lp import num_labels_bucket
from ..partitioning.partition_utils import (
    compute_k_for_n,
    intermediate_block_weights,
)
from ..telemetry import probes
from ..utils import RandomState, sync_stats
from ..utils import rng
from ..utils.platform import host_pool_workers
from ..utils.timer import scoped_timer


class LaneStackUnsupported(Exception):
    """Batch/config outside the lane-stack envelope; the engine falls back
    to the per-graph loop (counted + warned)."""


# ---------------------------------------------------------------------------
# Per-lane RNG chains — RandomState's exact key arithmetic, one per lane.
# ---------------------------------------------------------------------------


class LaneChain:
    """The key chain ``RandomState`` threads through one sequential run:
    ``reseed(seed)`` then repeated ``split``.  Draw-for-draw identical to
    the facade's chain because it performs the same jax.random ops in the
    same order."""

    def __init__(self, seed: int):
        self.seed = int(seed)
        self.key = rng.seed_key(seed)

    def next_key(self):
        self.key, sub = jax.random.split(self.key)
        return sub


@contextmanager
def lane_rng(chain: LaneChain):
    """Swap a lane's chain into the thread-local ``RandomState`` so
    unmodified sequential code (recursive_bipartition, extend_partition and
    everything below them) draws from the lane's stream; the advanced chain
    is read back on exit and the caller's stream is restored untouched."""
    tls = RandomState._tls
    prev_key = getattr(tls, "key", None)
    prev_seed = getattr(tls, "seed", None)
    tls.key = chain.key
    tls.seed = chain.seed
    try:
        yield
    finally:
        chain.key = tls.key
        tls.key = prev_key
        tls.seed = prev_seed


# ---------------------------------------------------------------------------
# Eligibility
# ---------------------------------------------------------------------------

_REFINER_CHAINS = (
    (RefinementAlgorithm.OVERLOAD_BALANCER, RefinementAlgorithm.LP),
    (
        RefinementAlgorithm.OVERLOAD_BALANCER,
        RefinementAlgorithm.LP,
        RefinementAlgorithm.UNDERLOAD_BALANCER,
    ),
)


def check_eligibility(ctx: Context, graphs: Sequence, k: int) -> None:
    """Raise :class:`LaneStackUnsupported` unless the batch fits the
    lockstep envelope (the serve preset's pipeline shape)."""

    def bail(reason: str):
        raise LaneStackUnsupported(reason)

    from ..context import ClusteringAlgorithm
    from ..ops.pallas_lp import resolve_lp_kernel

    if ctx.mode != PartitioningMode.DEEP:
        bail(f"mode {ctx.mode.value!r} (deep only)")
    if ctx.vcycles or ctx.restrict_vcycle_refinement:
        bail("v-cycle configuration")
    if ctx.compression.enabled:
        bail("compressed inputs")
    if ctx.use_64bit_ids:
        bail("64-bit id build")
    if ctx.coarsening.algorithm != ClusteringAlgorithm.LP:
        bail(f"coarsening algorithm {ctx.coarsening.algorithm.value!r}")
    if ctx.coarsening.overlay_levels > 1:
        bail("overlay clustering")
    if ctx.coarsening.sparsification.enabled:
        bail("sparsification")
    if resolve_lp_kernel(ctx.coarsening.lp.lp_kernel) != "xla":
        bail("pallas coarsening LP kernel")
    if resolve_lp_kernel(ctx.refinement.lp.lp_kernel) != "xla":
        bail("pallas refinement LP kernel")
    if ctx.coarsening.lp.weighted_mode is not None:
        bail("explicit weighted-mode pin (auto-detection only)")
    if tuple(ctx.refinement.algorithms) not in _REFINER_CHAINS:
        bail(f"refiner chain {tuple(a.value for a in ctx.refinement.algorithms)}")
    if ctx.initial_partitioning.device_extension:
        bail("device extension")
    if ctx.parallel.mesh_shape:
        bail("distributed mesh")
    if k < 2:
        bail("k < 2")
    for g in graphs:
        if g is None or getattr(g, "n", 0) <= 0:
            bail("empty graph")
        # .dtype reads without materializing a device array on the host.
        if g.row_ptr.dtype != np.int32:
            bail("non-int32 graph")
        if k > g.n:
            bail("k exceeds n")


# ---------------------------------------------------------------------------
# Per-lane facade state + stacked level state
# ---------------------------------------------------------------------------


@dataclass
class _Lane:
    slot: int                    # position in the request batch
    graph: object                # original CSRGraph
    chain: LaneChain
    ctx: Context                 # shallow per-lane ctx (own partition tree)
    caps: np.ndarray             # final (k,) max block weights, int64
    # isolated-node strip state (kaminpar.py facade replica)
    keep: Optional[np.ndarray]
    isolated: Optional[np.ndarray]
    work_host: Dict[str, np.ndarray]  # row_ptr/col_idx/node_w/edge_w of work graph
    work_n: int
    work_m: int
    tnw: int                     # work-graph total node weight
    # The facade's auto-detected weighted clustering mode (non-uniform edge
    # weights on the *input* graph); a per-lane STATIC of the clustering
    # kernel, so cohorts group by it.
    weighted: bool = False
    part: Optional[np.ndarray] = None  # final full-graph partition


@dataclass
class _Level:
    """One stacked hierarchy level of a cohort (lane axis leads)."""

    row_ptr: object              # (L, n_pad + 1)
    col_idx: object              # (L, m_pad)
    node_w: object               # (L, n_pad)
    edge_w: object               # (L, m_pad)
    edge_u: object               # (L, m_pad)
    n: np.ndarray                # (L,) real node counts
    m: np.ndarray                # (L,) real edge counts
    hist: List[np.ndarray]       # per-lane (12,) degree histograms
    max_nw: np.ndarray           # (L,) max node weights (refine relax)
    coarse_of: object = None     # (L, n_pad_fine) projection map (None at finest)
    layout: object = None        # cached (buckets, heavy, gather_idx)

    @property
    def n_pad(self) -> int:
        return int(self.row_ptr.shape[1]) - 1

    @property
    def m_pad(self) -> int:
        return int(self.col_idx.shape[1])

    def select(self, idx: List[int]) -> "_Level":
        take = jnp.asarray(idx)
        return _Level(
            row_ptr=jnp.take(self.row_ptr, take, axis=0),
            col_idx=jnp.take(self.col_idx, take, axis=0),
            node_w=jnp.take(self.node_w, take, axis=0),
            edge_w=jnp.take(self.edge_w, take, axis=0),
            edge_u=jnp.take(self.edge_u, take, axis=0),
            n=self.n[idx],
            m=self.m[idx],
            hist=[self.hist[i] for i in idx],
            max_nw=self.max_nw[idx],
            coarse_of=(
                None if self.coarse_of is None
                else jnp.take(self.coarse_of, take, axis=0)
            ),
            layout=None,  # rebuilt (cheap) for the subset
        )


@dataclass
class _Cohort:
    """Lanes advancing in lockstep over a shared stacked hierarchy."""

    lanes: List[_Lane]
    levels: List[_Level] = field(default_factory=list)

    @property
    def L(self) -> int:
        return len(self.lanes)

    def select(self, idx: List[int]) -> "_Cohort":
        return _Cohort(
            lanes=[self.lanes[i] for i in idx],
            levels=[lvl.select(idx) for lvl in self.levels],
        )


def _map_lanes(fn, L: int, pool=None, disable_timers: bool = False) -> list:
    """Run ``fn(i)`` for each lane on a host thread pool — the analog of
    the reference's per-subproblem TBB tasks (DIVERGENCES #16).  Identity
    is scheduling-proof because each lane's chain swaps into ITS WORKER's
    thread-local ``RandomState`` (``lane_rng`` operates per-thread), so
    every lane performs exactly the draws its sequential run performs no
    matter how the pool interleaves — or which thread runs a lane when
    the map degrades to the caller.  ``pool`` reuses the runner's shared
    executor (one per batch, not one per stage — the host IP/extension
    stages are the pipeline's serial tail).  ``disable_timers`` guards the
    global timer tree exactly as the reference disables timers inside its
    tbb task arena (and as deep._extend_partition_host does)."""
    from concurrent.futures import ThreadPoolExecutor

    from ..context import propagate_runtime

    # Workers re-activate the dispatcher thread's EngineRuntime: the
    # per-lane IP/extension stages resolve layout/sync settings, and
    # thread-local activation does not cross pool threads (PR 6 class).
    wfn = propagate_runtime(fn)

    def _run() -> list:
        if pool is not None:
            return list(pool.map(wfn, range(L)))
        workers = host_pool_workers(L)
        if workers <= 1:
            return [fn(i) for i in range(L)]
        with ThreadPoolExecutor(max_workers=workers) as tpool:
            return list(tpool.map(wfn, range(L)))

    if disable_timers:
        from ..utils.timer import Timer

        timer = Timer.global_()
        timer.disable()
        try:
            return _run()
        finally:
            timer.enable()
    return _run()


def _group_indices(keys) -> List[List[int]]:
    """Stable grouping: lanes with equal keys, first-occurrence order."""
    groups: Dict[object, List[int]] = {}
    order: List[object] = []
    for i, key in enumerate(keys):
        if key not in groups:
            groups[key] = []
            order.append(key)
        groups[key].append(i)
    return [groups[key] for key in order]


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


@dataclass
class LaneStackReport:
    """What one lane-stacked batch execution did (engine stats surface)."""

    lanes: int = 0
    cohorts: int = 0
    splits: int = 0
    levels: int = 0
    stacked_pulls: int = 0
    # Per-lane cohort ordinal in request order (round 20): which cohort of
    # this batch execution each request's lane rode — the isolated-node
    # strip can move work graphs across stack buckets, so requests from
    # one shape cell may split across cohorts.  The engine's request-trace
    # lanestack event records it per request.
    lane_cohorts: tuple = ()
    # The stacked kernel shapes this run actually dispatched: level-0
    # stack buckets plus every coarsening level's (layout signature, lane
    # count).  Together with (k, epsilon) this names the executable set,
    # so the engine's warm accounting can key on what really compiled —
    # the request cell alone can't (the isolated-node strip moves work
    # graphs across buckets, and cohort splits change lane counts).
    layout_key: tuple = ()
    # Per-request final (k,) max block weights in request order — what the
    # sequential facade would leave in ctx.partition.max_block_weights
    # (the engine's feasibility check consumes them).
    caps: Optional[List[np.ndarray]] = None


class LaneStackRunner:
    """One batch execution.  ``run`` returns per-request partitions in
    request order, bit-identical to sequential facade runs."""

    def __init__(self, ctx: Context, graphs: Sequence, k: int, epsilon: float):
        self.base_ctx = ctx
        self.graphs = list(graphs)
        self.k = int(k)
        self.epsilon = float(epsilon)
        self.report = LaneStackReport(lanes=len(self.graphs))
        self._layout_shapes: set = set()
        self._pool = None  # shared host thread pool, owned by run()

    # -- facade replica (kaminpar.py per-request prep) ---------------------

    def _prep_lane(self, slot: int, graph) -> _Lane:
        ctx = self.base_ctx
        k = self.k
        chain = LaneChain(ctx.seed)  # the facade's per-call reseed
        # ONE counted pull materializes the request graph host-side
        # (kway.graph_to_host packs all four CSR arrays into a single
        # transfer); raw np.asarray reads would bypass the sync census on
        # the serve hot path.  scoped(): prep may run on a pool worker
        # thread whose phase stack is empty.
        from ..partitioning.kway import graph_to_host

        with sync_stats.scoped("serve_lanestack"):
            host = graph_to_host(graph)
        rp, ci, nw, ew = host.row_ptr, host.col_idx, host.node_w, host.edge_w
        # The facade's weighted-mode auto-pin, from the ORIGINAL graph.
        weighted = bool(ew.size and ew.min() != ew.max())
        # Per-lane ctx: own partition tree + the weighted-mode pin the
        # facade would set; shared read-only subtrees stay shared.
        lane_ctx = copy.copy(ctx)
        lane_ctx.partition = dataclasses.replace(ctx.partition)
        lane_ctx.coarsening = dataclasses.replace(
            ctx.coarsening,
            lp=dataclasses.replace(ctx.coarsening.lp, weighted_mode=weighted),
        )
        total_node_weight = int(graph.total_node_weight)
        max_node_weight = int(graph.max_node_weight)
        lane_ctx.partition.setup(total_node_weight, k, self.epsilon, 0.0)
        perfect = (total_node_weight + k - 1) // k
        lane_ctx.partition.max_block_weights = np.maximum(
            lane_ctx.partition.max_block_weights, perfect + max_node_weight
        )
        caps = np.asarray(lane_ctx.partition.max_block_weights, dtype=np.int64)

        # Isolated-node strip (the facade's exact helper, graph/isolated.py)
        # on the already-materialized host arrays.
        stripped = strip_isolated_csr(rp, ci, nw, graph.n, k)
        ew32 = ew.astype(np.int32, copy=False)
        if stripped is not None:
            keep, isolated, new_rp, new_col, new_nw = stripped
            work = {
                "row_ptr": new_rp.astype(np.int32),
                "col_idx": new_col.astype(np.int32),
                "node_w": new_nw.astype(np.int32),
                "edge_w": ew32,
            }
        else:
            keep = isolated = None
            work = {
                "row_ptr": rp.astype(np.int32, copy=False),
                "col_idx": ci.astype(np.int32, copy=False),
                "node_w": nw.astype(np.int32, copy=False),
                "edge_w": ew32,
            }
        work_n = len(work["row_ptr"]) - 1
        work_m = len(work["col_idx"])
        return _Lane(
            slot=slot, graph=graph, chain=chain, ctx=lane_ctx, caps=caps,
            keep=keep, isolated=isolated, work_host=work,
            work_n=work_n, work_m=work_m,
            tnw=int(work["node_w"].astype(np.int64).sum()),
            weighted=weighted,
        )

    # -- stacked level construction ----------------------------------------

    def _stack_level0(self, lanes: List[_Lane]) -> _Level:
        n_pad = _next_bucket(max(l.work_n for l in lanes))
        m_pad = _next_bucket(max(l.work_m for l in lanes))
        self._layout_shapes.add(("l0", n_pad, m_pad, len(lanes)))
        # All lanes share the cell by grouping, so per-lane buckets equal
        # the shared ones (asserted by the caller's grouping key).
        L = len(lanes)
        rp = np.zeros((L, n_pad + 1), dtype=np.int32)
        col = np.zeros((L, m_pad), dtype=np.int32)
        nw = np.zeros((L, n_pad), dtype=np.int32)
        ew = np.zeros((L, m_pad), dtype=np.int32)
        eu = np.zeros((L, m_pad), dtype=np.int32)
        hist = []
        max_nw = np.zeros(L, dtype=np.int64)
        anchor = n_pad - 1
        for i, lane in enumerate(lanes):
            w = lane.work_host
            n, m = lane.work_n, lane.work_m
            rp[i, : n + 1] = w["row_ptr"]
            rp[i, n + 1 : n_pad] = m
            rp[i, n_pad] = m_pad
            col[i, :m] = w["col_idx"]
            col[i, m:] = anchor
            nw[i, :n] = w["node_w"]
            ew[i, :m] = w["edge_w"]
            deg = np.diff(w["row_ptr"])
            eu[i, :m] = np.repeat(np.arange(n, dtype=np.int32), deg)
            eu[i, m:] = anchor
            hist.append(host_deg_histogram(w["row_ptr"], n))
            max_nw[i] = int(w["node_w"].max()) if n else 0
        return _Level(
            row_ptr=jnp.asarray(rp), col_idx=jnp.asarray(col),
            node_w=jnp.asarray(nw), edge_w=jnp.asarray(ew),
            edge_u=jnp.asarray(eu),
            n=np.asarray([l.work_n for l in lanes], dtype=np.int64),
            m=np.asarray([l.work_m for l in lanes], dtype=np.int64),
            hist=hist, max_nw=max_nw,
        )

    def _layout(self, level: _Level):
        """Stacked bucketed views under the shared width signature (the
        caller guarantees signature equality across the level's lanes)."""
        if level.layout is None:
            plan, merged_to, counts, hs, Hr_pad, Hs_pad = lops.lane_layout_plan(
                level.hist
            )
            buckets, heavy, gather_idx = lops.lane_bucketed(
                level.row_ptr, level.col_idx, level.edge_w, level.edge_u,
                jnp.asarray(level.n), jnp.asarray(merged_to),
                jnp.asarray(counts), jnp.asarray(hs),
                plan=plan, Hr_pad=Hr_pad, Hs_pad=Hs_pad,
            )
            level.layout = (buckets, heavy, gather_idx)
        return level.layout

    # -- lockstep coarsening ----------------------------------------------

    def _coarsen(self, cohort: _Cohort) -> List[_Cohort]:
        """Coarsen lanes in lockstep; returns cohorts that finished (their
        ``levels[-1]`` is the coarsest graph).  Mirrors
        ClusterCoarsener.coarsen + coarsen_once per lane."""
        ctx = self.base_ctx
        target_n = 2 * ctx.coarsening.contraction_limit
        finished: List[_Cohort] = []
        queue = [cohort]
        while queue:
            c = queue.pop()
            cur = c.levels[-1]
            # Signature grouping comes FIRST — before the stop/go split —
            # because a cohort that stops here hands this level straight to
            # the stacked *refinement* path, whose draw shapes (bucketed
            # layout row pads, heavy pads) must equal every lane's own
            # sequential layout just like the clustering kernel's.
            sigs = [lops.lane_layout_signature(h) for h in cur.hist]
            groups = _group_indices(sigs)
            if len(groups) > 1:
                self.report.splits += len(groups) - 1
                queue.extend(c.select(g) for g in groups)
                continue
            stop = [i for i in range(c.L) if cur.n[i] <= target_n]
            go = [i for i in range(c.L) if cur.n[i] > target_n]
            if stop and go:
                self.report.splits += 1
                finished.append(c.select(stop))
                c = c.select(go)
                cur = c.levels[-1]
            elif stop:
                finished.append(c)
                continue
            queue.extend(self._coarsen_level(c, finished))
        return finished

    def _coarsen_level(self, c: _Cohort, finished: List[_Cohort]) -> List[_Cohort]:
        """One lockstep coarsening level over a signature-uniform cohort.
        Converged lanes are appended to ``finished``; continuing lanes come
        back (possibly split by coarse bucket)."""
        ctx = self.base_ctx
        cc = ctx.coarsening
        cur = c.levels[-1]
        L = c.L
        self.report.levels += 1
        # Cohort is signature-uniform here (grouped in _coarsen), so one
        # lane's signature names this level's stacked dispatch shapes.
        self._layout_shapes.add(
            ("lvl", lops.lane_layout_signature(cur.hist[0]), L)
        )
        buckets, heavy, gather_idx = self._layout(cur)

        # Per-lane host parameters (lp_clusterer._one_clustering replica).
        weighted = c.lanes[0].weighted  # uniform within a cohort
        active_prob = cc.lp.active_prob
        if weighted:
            # lp_clusterer's weighted-graph mode (per-lane static; cohorts
            # group by the flag).
            active_prob = min(active_prob, cc.lp.weighted_active_prob)
        max_cw = np.zeros(L, dtype=np.int64)
        iters = np.zeros(L, dtype=np.int64)
        min_moved = np.zeros(L, dtype=np.int64)
        for i, lane in enumerate(c.lanes):
            from ..coarsening.max_cluster_weights import compute_max_cluster_weight

            n_i, m_i = int(cur.n[i]), int(cur.m[i])
            mcw = compute_max_cluster_weight(
                cc, n_i, lane.tnw, self.k, self.epsilon
            )
            if cc.max_shrink_factor > 0:
                avg_w = lane.tnw / max(n_i, 1)
                mcw = min(mcw, max(int(cc.max_shrink_factor * avg_w), 1))
            max_cw[i] = mcw
            it = cc.lp.num_iterations
            if weighted:
                it *= max(cc.lp.weighted_sweep_factor, 1)
            elif n_i > 0 and m_i / n_i < cc.lp.low_degree_boost_threshold:
                it *= max(cc.lp.low_degree_boost_factor, 1)
            iters[i] = it
            min_moved[i] = int(cc.lp.min_moved_fraction * n_i)

        keys_iter = jnp.stack([lane.chain.next_key() for lane in c.lanes])
        if cc.lp.cluster_two_hop_nodes:
            keys_2h = jnp.stack([lane.chain.next_key() for lane in c.lanes])
        else:
            keys_2h = keys_iter  # unread
        labels, moved = lops.lane_cluster(
            cur.row_ptr, cur.node_w, buckets, heavy, gather_idx,
            keys_iter, keys_2h, jnp.asarray(max_cw),
            jnp.asarray(min_moved), jnp.asarray(iters),
            num_labels=cur.n_pad, active_prob=active_prob,
            tie_break=cc.lp.tie_breaking.value,
            cluster_isolated=cc.lp.cluster_isolated_nodes,
            cluster_two_hop=cc.lp.cluster_two_hop_nodes,
        )
        coarse_of, stats, c_node_w, out_u, out_v, out_w, row_ptr = (
            lops.lane_contract(
                labels, cur.edge_u, cur.col_idx, cur.edge_w, cur.node_w, moved
            )
        )
        # THE one stacked blocking readback of the level (lane-accounted).
        stats_np = sync_stats.pull(
            stats, phase="lanestack_coarsening", lanes=L
        )
        self.report.stacked_pulls += 1

        from ..ops.contraction import STATS_LEN

        n_c = stats_np[:, 0].astype(np.int64) - 1  # drop the anchor cluster
        m_c = stats_np[:, 1].astype(np.int64)
        # Per-lane quality probes from values THIS stacked pull already
        # produced (cluster_coarsener's probe, lane-tagged; no-op without
        # an active trace recorder, never an extra transfer).
        for i in range(L):
            probes.coarsening_level(
                level=len(c.levels) - 1, n=int(cur.n[i]), m=int(cur.m[i]),
                n_c=int(n_c[i]), m_c=int(m_c[i]),
                max_cluster_weight=int(max_cw[i]),
                max_node_weight=int(stats_np[i, 2]),
                total_edge_weight=int(stats_np[i, 3]),
                lp_moved=int(stats_np[i, STATS_LEN]),
                lp_rounds_budget=cc.lp.num_iterations, lane=i,
            )
        conv, cont = [], []
        for i in range(L):
            shrink = 1.0 - n_c[i] / max(int(cur.n[i]), 1)
            (conv if shrink < cc.convergence_threshold else cont).append(i)
        if conv:
            # Whole-cohort convergence (the common same-family case) keeps
            # the cohort as-is — select() would copy every stacked level
            # for an identity subset.
            finished.append(c.select(conv) if cont else c)
            if cont:
                self.report.splits += 1
        if not cont:
            return []
        # Group continuing lanes by their coarse shape buckets (draw shapes
        # at the next level must equal each lane's own sequential buckets).
        out: List[_Cohort] = []
        bucket_groups = _group_indices(
            [(_next_bucket(int(n_c[i])), _next_bucket(int(m_c[i]))) for i in cont]
        )
        if len(bucket_groups) > 1:
            self.report.splits += len(bucket_groups) - 1
        take_all = lambda arr, idx: jnp.take(arr, jnp.asarray(idx), axis=0)
        for grp in bucket_groups:
            idx = [cont[j] for j in grp]
            n_pad = _next_bucket(int(n_c[idx[0]]))
            m_pad = _next_bucket(int(m_c[idx[0]]))
            rp_p, col_p, nw_p, ew_p, eu_p = lops.lane_extract_padded(
                take_all(row_ptr, idx), take_all(c_node_w, idx),
                take_all(out_u, idx), take_all(out_v, idx),
                take_all(out_w, idx),
                jnp.asarray(n_c[idx]), jnp.asarray(m_c[idx]),
                n_pad=n_pad, m_pad=m_pad,
            )
            sub = c.select(idx)
            sub.levels.append(_Level(
                row_ptr=rp_p, col_idx=col_p, node_w=nw_p, edge_w=ew_p,
                edge_u=eu_p, n=n_c[idx], m=m_c[idx],
                hist=[stats_np[i, 4:STATS_LEN].astype(int) for i in idx],
                max_nw=stats_np[idx, 2].astype(np.int64),
                coarse_of=take_all(coarse_of, idx),
            ))
            out.append(sub)
        return out

    # -- initial partitioning (per lane, host orchestration) ---------------

    def _initial_partition(self, c: _Cohort, cur_k: int):
        """Per-lane recursive bipartition on the coarsest graphs, fed from
        ONE stacked bulk pull (the graph_to_host twin).  The lanes run on
        the :func:`_map_lanes` thread pool — host IP is the serial tail of
        the lockstep pipeline, and the lanes are independent subproblems."""
        cur = c.levels[-1]
        packed = sync_stats.pull(
            jnp.concatenate(
                [cur.row_ptr, cur.col_idx, cur.node_w, cur.edge_w], axis=1
            ),
            phase="lanestack_ip", lanes=c.L,
        )
        self.report.stacked_pulls += 1
        n_pad, m_pad = cur.n_pad, cur.m_pad

        def one(i: int):
            lane = c.lanes[i]
            n_i, m_i = int(cur.n[i]), int(cur.m[i])
            row = packed[i]
            host = HostCSR(
                row[: n_i + 1].astype(np.int64),
                row[n_pad + 1 : n_pad + 1 + m_i].astype(np.int64),
                row[n_pad + 1 + m_pad : n_pad + 1 + m_pad + n_i].astype(np.int64),
                row[n_pad + 1 + m_pad + n_pad :][:m_i].astype(np.int64),
            )
            budgets = intermediate_block_weights(lane.caps, cur_k)
            with lane_rng(lane.chain):
                rng = RandomState.numpy_rng()  # deep.py's pre-IP draw
                return recursive_bipartition(
                    host, cur_k, budgets, rng,
                    lane.ctx.initial_partitioning,
                )

        parts = _map_lanes(one, c.L, pool=self._pool, disable_timers=True)
        return self._stack_labels(parts, n_pad)

    @staticmethod
    def _stack_labels(parts: List[np.ndarray], n_pad: int):
        L = len(parts)
        out = np.zeros((L, n_pad), dtype=np.int32)
        for i, p in enumerate(parts):
            out[i, : len(p)] = p
        return jnp.asarray(out)

    # -- lockstep refinement ------------------------------------------------

    def _block_caps(self, c: _Cohort, level: _Level, cur_k: int,
                    coarse: bool) -> np.ndarray:
        """(L, cur_k) per-lane intermediate budgets (deep._refine replica)."""
        eps = self.epsilon
        out = np.zeros((c.L, cur_k), dtype=np.int64)
        for i, lane in enumerate(c.lanes):
            mb = intermediate_block_weights(lane.caps, cur_k)
            if coarse:
                relaxed = np.ceil(mb / (1.0 + eps)).astype(np.int64) + int(
                    level.max_nw[i]
                )
                mb = np.maximum(mb, relaxed)
            out[i] = mb
        return out

    def _quality(self, level: _Level, labels, cur_k: int) -> np.ndarray:
        """(L, 1 + cur_k) [cut, block_weights...] via one stacked pull."""
        q = lops.lane_quality(
            labels, level.node_w, level.edge_u, level.col_idx, level.edge_w,
            k=cur_k,
        )
        out = sync_stats.pull(
            q, phase="lanestack_refinement", lanes=level.row_ptr.shape[0]
        )
        self.report.stacked_pulls += 1
        return out.astype(np.int64)

    def _refine(self, c: _Cohort, level: _Level, labels, cur_k: int,
                coarse: bool):
        """MultiRefiner keep-best over the stacked (balancer, LP) chain —
        refiner.py's rank/chain semantics per lane."""
        ctx = self.base_ctx
        caps = self._block_caps(c, level, cur_k, coarse)
        caps_dev = jnp.asarray(caps.astype(np.int32))
        buckets, heavy, gather_idx = self._layout(level)

        def ranks(q):
            # (infeasible, cut) per lane; min-feasibility is trivially true
            # in the envelope (no minimum block weights).
            return [
                (bool(np.any(q[i, 1:] > caps[i])), int(q[i, 0]))
                for i in range(c.L)
            ]

        snapshots = [labels]
        best_idx = [0] * c.L
        best_rank = ranks(self._quality(level, labels, cur_k))

        # --- overload balancer (balancer.py round-loop replica) -----------
        active = [True] * c.L
        lab = labels
        dummy = rng.seed_key(0)
        for _ in range(ctx.refinement.balancer.max_num_rounds):
            keys = jnp.stack([
                lane.chain.next_key() if active[i] else dummy
                for i, lane in enumerate(c.lanes)
            ])
            lab, flags = lops.lane_balance_round(
                keys, lab, buckets, heavy, gather_idx, level.node_w,
                caps_dev, jnp.asarray(active), k=cur_k,
            )
            flags_np = sync_stats.pull(
                flags, phase="lanestack_refinement", lanes=c.L
            )
            self.report.stacked_pulls += 1
            for i in range(c.L):
                if active[i] and (
                    not flags_np[i, 1] or flags_np[i, 0] == 0
                ):
                    active[i] = False
            if not any(active):
                break
        snapshots.append(lab)
        rank_b = ranks(self._quality(level, lab, cur_k))
        for i in range(c.L):
            if rank_b[i] <= best_rank[i]:
                best_rank[i], best_idx[i] = rank_b[i], 1

        # --- LP refiner (lp_refiner.py replica) ----------------------------
        rl = ctx.refinement.lp
        k_pad = num_labels_bucket(cur_k)
        max_w = np.zeros((c.L, k_pad), dtype=np.int32)
        max_w[:, :cur_k] = caps.astype(np.int32)
        keys = jnp.stack([lane.chain.next_key() for lane in c.lanes])
        min_moved = np.asarray(
            [int(rl.min_moved_fraction * int(level.n[i])) for i in range(c.L)],
            dtype=np.int64,
        )
        iters = np.full(c.L, rl.num_iterations, dtype=np.int64)
        lab_lp = lops.lane_lp_refine(
            lab, keys, buckets, heavy, gather_idx, level.node_w,
            jnp.asarray(max_w), jnp.asarray(min_moved), jnp.asarray(iters),
            num_labels=k_pad, active_prob=rl.active_prob,
            allow_tie_moves=rl.allow_tie_moves,
        )
        snapshots.append(lab_lp)
        rank_lp = ranks(self._quality(level, lab_lp, cur_k))
        for i in range(c.L):
            if rank_lp[i] <= best_rank[i]:
                best_rank[i], best_idx[i] = rank_lp[i], 2
        # (A trailing underload balancer is a no-op without minimum block
        # weights and cannot change the keep-best outcome.)

        return lops.lane_select_best(
            jnp.stack(snapshots), jnp.asarray(best_idx, dtype=np.int32)
        )

    # -- extension (per lane, host orchestration) ---------------------------

    def _lane_graph_view(self, level: _Level, i: int, lane: _Lane) -> CSRGraph:
        """Lane ``i``'s graph at ``level`` as a real CSRGraph (device slices
        + pre-seeded padded view) for the unmodified host extension path."""
        n_i, m_i = int(level.n[i]), int(level.m[i])
        rp = level.row_ptr[i]
        col = level.col_idx[i]
        nw = level.node_w[i]
        ew = level.edge_w[i]
        eu = level.edge_u[i]
        g = CSRGraph(
            rp[: n_i + 1], col[:m_i], nw[:n_i], ew[:m_i], edge_u=eu[:m_i]
        )
        g._padded = PaddedView(rp, col, nw, ew, eu, n_i, m_i)
        g._deg_hist = level.hist[i]  # host (12,) histogram (see _Level)
        g._layout_mode = lane.ctx.parallel.device_layout_build
        g._total_node_weight = lane.tnw
        g._max_node_weight = int(level.max_nw[i])
        return g

    def _extend(self, c: _Cohort, level: _Level, labels, cur_k: int,
                target_k: int):
        """Per-lane host extension through the real ``extend_partition``
        (identical draws via the lane chain), fed from ONE stacked pull.
        Lanes run on the :func:`_map_lanes` pool — extension derives every
        block's stream from a reseed that already lands in ITS OWN inner
        worker (deep._extend_partition_host), so outer-lane scheduling
        cannot reorder any draw."""
        from ..partitioning.deep import extend_partition

        lab_np = sync_stats.pull(
            labels, phase="lanestack_extend", lanes=c.L
        )
        self.report.stacked_pulls += 1

        def one(i: int):
            lane = c.lanes[i]
            g = self._lane_graph_view(level, i, lane)
            with lane_rng(lane.chain):
                return extend_partition(
                    g, lab_np[i, : int(level.n[i])].astype(np.int32),
                    cur_k, target_k, lane.ctx,
                )

        parts = _map_lanes(one, c.L, pool=self._pool)
        return self._stack_labels(parts, level.n_pad)

    # -- the deep uncoarsening loop (deep.py partition() replica) -----------

    def _uncoarsen_phase(self, c: _Cohort) -> List[Tuple[_Lane, np.ndarray]]:
        """IP + extend/refine/uncoarsen lockstep for one finished cohort;
        returns (lane, work-graph partition) pairs."""
        ctx = self.base_ctx
        C = ctx.coarsening.contraction_limit
        out: List[Tuple[_Lane, np.ndarray]] = []

        # cur_k may differ across lanes (it depends on the coarsest n).
        cur_ks = [
            min(self.k, compute_k_for_n(int(c.levels[-1].n[i]), C, self.k))
            for i in range(c.L)
        ]
        groups = _group_indices(cur_ks)
        if len(groups) > 1:
            self.report.splits += len(groups) - 1
        for grp in groups:
            sub = c.select(grp) if len(groups) > 1 else c
            cur_k = cur_ks[grp[0]]
            labels = self._initial_partition(sub, cur_k)
            depth = len(sub.levels) - 1
            labels = self._refine(
                sub, sub.levels[-1], labels, cur_k, coarse=depth > 0
            )
            out.extend(self._finish_from(sub, labels, cur_k, depth))
        return out

    def _finish_from(self, sub: _Cohort, labels, cur_k: int,
                     level_idx: int) -> List[Tuple[_Lane, np.ndarray]]:
        """Continue the uncoarsening loop for a split-off sub-cohort from
        ``level_idx`` with the given stacked labels."""
        ctx = self.base_ctx
        C = ctx.coarsening.contraction_limit
        out: List[Tuple[_Lane, np.ndarray]] = []
        while True:
            cur = sub.levels[level_idx]
            tks = [
                (compute_k_for_n(int(cur.n[i]), C, self.k)
                 if level_idx > 0 else self.k)
                for i in range(sub.L)
            ]
            tk_groups = _group_indices(tks)
            if len(tk_groups) > 1:
                self.report.splits += len(tk_groups) - 1
                for tg in tk_groups:
                    out.extend(self._finish_from(
                        sub.select(tg),
                        jnp.take(labels, jnp.asarray(tg), axis=0),
                        cur_k, level_idx,
                    ))
                return out
            target_k = min(self.k, tks[0]) if level_idx > 0 else self.k
            if cur_k < target_k:
                labels = self._extend(sub, cur, labels, cur_k, target_k)
                cur_k = target_k
                labels = self._refine(
                    sub, cur, labels, cur_k, coarse=level_idx > 0
                )
            if level_idx == 0:
                lab_np = sync_stats.pull(
                    labels, phase="lanestack_refinement", lanes=sub.L
                )
                self.report.stacked_pulls += 1
                for i, lane in enumerate(sub.lanes):
                    out.append((
                        lane, lab_np[i, : int(cur.n[i])].astype(np.int32)
                    ))
                return out
            labels = lops.lane_project(cur.coarse_of, labels)
            level_idx -= 1
            labels = self._refine(
                sub, sub.levels[level_idx], labels, cur_k,
                coarse=level_idx > 0,
            )

    # -- finalize (facade replica: isolated re-integration) -----------------

    def _finalize(self, lane: _Lane, work_part: np.ndarray) -> np.ndarray:
        if lane.keep is None:
            part = work_part
        else:
            # The facade's exact re-integration helper (graph/isolated.py).
            part = assign_isolated_nodes(
                lane.graph.n, self.k, lane.keep, lane.isolated, work_part,
                lane.work_host["node_w"],
                sync_stats.pull(lane.graph.node_w, phase="serve_lanestack"),
                lane.caps,
            )
        from ..utils.assertions import LIGHT, kassert

        kassert(
            lambda: part.size == 0
            or (part.min() >= 0 and part.max() < self.k),
            "partition labels out of range", LIGHT,
        )
        return part

    # -- entry ---------------------------------------------------------------

    def run(self) -> List[np.ndarray]:
        from concurrent.futures import ThreadPoolExecutor

        check_eligibility(self.base_ctx, self.graphs, self.k)
        workers = host_pool_workers(len(self.graphs))
        if workers > 1:
            # ONE host pool for every per-lane IP/extension stage of the
            # batch (thread churn would sit on the host serial tail).
            with ThreadPoolExecutor(max_workers=workers) as pool:
                self._pool = pool
                try:
                    return self._run()
                finally:
                    self._pool = None
        return self._run()

    def _run(self) -> List[np.ndarray]:
        with scoped_timer("serve_lanestack"):
            # Per-lane prep (host materialization + isolated strip) is
            # independent O(n+m) work — map it over the batch pool like
            # the IP/extension stages.
            lanes = _map_lanes(
                lambda i: self._prep_lane(i, self.graphs[i]),
                len(self.graphs), pool=self._pool,
            )
            self.report.caps = [lane.caps for lane in lanes]
            # Work graphs can leave the request cell (isolated-node strip
            # shrinks n); group by the stacked level-0 buckets.
            results: List[Optional[np.ndarray]] = [None] * len(lanes)
            cohorts = _group_indices([
                (_next_bucket(l.work_n), _next_bucket(l.work_m), l.weighted)
                for l in lanes
            ])
            self.report.cohorts = len(cohorts)
            lane_cohorts = [0] * len(lanes)
            for ci, grp in enumerate(cohorts):
                for li in grp:
                    lane_cohorts[li] = ci
            self.report.lane_cohorts = tuple(lane_cohorts)
            pre = sync_stats.phase_count("lanestack_coarsening")
            for grp in cohorts:
                c = _Cohort(lanes=[lanes[i] for i in grp])
                c.levels.append(self._stack_level0(c.lanes))
                finished = self._coarsen(c)
                for fc in finished:
                    for lane, work_part in self._uncoarsen_phase(fc):
                        results[lane.slot] = self._finalize(lane, work_part)
            self.report.layout_key = tuple(sorted(self._layout_shapes))
            attempts = self.report.levels
            # In-pipeline lane-accounted budget assert: exactly ONE stacked
            # blocking readback per attempted coarsening level per cohort
            # path (armed via sync_stats.enable_budget_checks, like the
            # sequential spine's per-level budget in deep.py).
            sync_stats.assert_phase_budget(
                "lanestack_coarsening", attempts, since=pre
            )
            from ..utils.assertions import LIGHT, kassert

            kassert(
                lambda: all(r is not None for r in results),
                "lane-stacked run dropped a lane (cohort-split invariant)",
                LIGHT,
            )
            return results


def run_lanestacked(ctx: Context, graphs: Sequence, k: int, epsilon: float,
                    trace_lane: str = ""):
    """Execute a same-cell batch lane-stacked; returns (partitions, report).
    Raises :class:`LaneStackUnsupported` for out-of-envelope batches.

    ``trace_lane`` (round 18, serve/fleet.py): when set and a trace
    recorder is active, the whole stacked execution additionally lands as
    ONE closed span on the named synthetic lane row (``replicaN``), so a
    fleet trace shows the device axis side by side — which replica ran
    which stacked batch at what occupancy — without touching the ambient
    thread rows."""
    from ..resilience.faults import maybe_inject

    # Named "execute" injection point of the stacked path (round 17): the
    # engine's lanestack breaker + per-graph fallback are exercised by
    # chaos plans targeting site "lanestack".  Before any lane prep, so a
    # faulted batch leaves no partial per-lane state behind.
    maybe_inject("execute", site="lanestack")
    runner = LaneStackRunner(ctx, graphs, k, epsilon)
    from ..telemetry import trace as ttrace

    rec = ttrace.active() if trace_lane else None
    t0 = rec._now_us() if rec is not None else 0.0
    parts = runner.run()
    if rec is not None:
        rec.lane_span(
            trace_lane, "lanestack_batch", t0, rec._now_us(),
            lanes=runner.report.lanes, cohorts=runner.report.cohorts,
            splits=runner.report.splits,
        )
    return parts, runner.report
