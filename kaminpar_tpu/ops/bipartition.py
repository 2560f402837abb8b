"""Lane-vmapped device-resident initial bipartitioning pool (round 9, ISSUE 4).

TPU-native redesign of the reference's ``InitialPoolBipartitioner``
(``initial_pool_bipartitioner.cc:24``): the pool's R repetitions of
{BFS, greedy-graph-growing, random} bipartitioning + 2-way refinement are
embarrassingly parallel, so instead of a sequential host loop every
repetition runs as one **vmapped lane** of a rank-polymorphic kernel:

- *seeded region growing* (BFS/GGG) is masked frontier expansion over the
  padded CSR: each of a fixed number of trips rates the frontier
  (edge-parallel segment-sum, the ops/lp.py idiom), then admits a maximal
  prefix of it — ordered randomly (BFS layers) or by connection-to-block-0
  (GGG) — subject to the remaining weight budget.  Bulk layer admission is
  the bulk-synchronous analog of the reference's node-at-a-time queues, the
  same documented Jacobi divergence as the LP engine (ops/lp.py docstring).
- *random* bipartitioning admits a random-order prefix of all nodes.
- the *2-way refiner* is round-based boundary LP/FM: alternating sides, a
  round moves the best positive-gain prefix of the source side that fits the
  receiving side's budget.  Single-side rounds are oscillation-free and
  monotone: simultaneous same-side movers only *improve* on their
  individually-estimated gains (a shared internal edge stays internal).
  A forced-balance pass before refinement repairs infeasible grown lanes
  (the role of host ``_rebalance_2way``), run unconditionally — it is a
  no-op on feasible lanes, so the kernel stays branch-free.

Per-lane streams come from the counter-based scheme in utils/rng.py
(``fold_in(graph_seed, lane_index)``): draws are lane-count invariant and
identical under vmap, scan, or a Python loop (tests/test_rng.py +
tests/test_device_pool.py).  Lane selection — feasible-first, then min
overload, then min cut, deterministic tie-break on lane index — happens on
device, and one pool invocation performs exactly ONE blocking readback: the
winning labels and the packed cut/feasibility stats ride a single
``sync_stats.pull``.

Shapes ride the PR 1 ladder: graph arrays are the PaddedView buckets
(weight-0 padding is inert in ratings, budgets, and cuts) and lane counts
are bucketed to powers of two, so one executable serves a whole
(n-bucket, m-bucket, lane-count) cell.
"""

from __future__ import annotations

import math
import threading
import time
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .segment import cumsum
from .sort import argsort, rank_below

# Packed-stats layout appended to the winning labels (all in the graph's
# index dtype): [cut, feasible, winner_lane, num_feasible_lanes, w0, w1].
STATS_LEN = 6


def grow_trip_count(n_pad: int) -> int:
    """Static frontier-expansion trip budget for an n_pad-bucket kernel.

    Weight-bounded layer admission reaches the target in O(eccentricity of
    the grown half) trips — ~2*sqrt(n) on mesh-like graphs, far fewer on
    expanders.  High-diameter outliers (paths) leave the lane underweight;
    the forced-balance pass then fills it with least-loss nodes, so a capped
    trip count costs quality only on pathological inputs, never feasibility.
    """
    return int(min(n_pad, 192, max(16, 2 * math.isqrt(int(n_pad)))))


def fm_round_count(n_pad: int, fm_iterations: int) -> int:
    """Static refinement-round budget: at least the configured FM iteration
    count per side, scaled with sqrt(n) — boundary diffusion straightens a
    mesh boundary one staircase step per round, so the round budget must
    cover the boundary length, not a constant (measured on grid16
    bisections: 38 rounds plateau at cut 19-22, 8*sqrt(n) rounds reach the
    optimum 16 = the host pool's median).  The rounds run inside one fused
    fori_loop, so the budget costs runtime only, never extra dispatches or
    compiles."""
    return int(min(256, max(2 * max(int(fm_iterations), 1),
                            8 * math.isqrt(int(n_pad)))))


def method_lane_counts(ipc, final_k: int) -> Tuple[Tuple[str, int], ...]:
    """Static (method, lane-count) layout of a pool dispatch.

    Repetitions follow the host pool's adaptive rule (reference:
    initial_pool_bipartitioner.cc adaptive selection, simplified exactly as
    initial/bipartitioner.py does): ``min_num_repetitions`` scaled by
    ceil(log2(final_k)) - 1, clamped to ``max_num_repetitions`` — then
    bucketed up to the next power of two so one compiled executable serves a
    whole lane-count cell.  Extra bucket lanes are *more* repetitions, not
    padding: they draw their own lane streams and compete like any other.
    Lane order is fixed (bfs, ggg, random), and each method keys its lanes
    from a disjoint counter window (:func:`method_lane_keys`), so lane j of
    a method keeps its stream across lane-count/bucket changes.
    """
    from ..utils.intmath import next_pow2

    reps = max(ipc.min_num_repetitions, 1)
    if ipc.use_adaptive_bipartitioner_selection and final_k > 2:
        mult = max(1, int(math.ceil(math.log2(final_k))) - 1)
        reps = min(reps * mult, ipc.max_num_repetitions)
    lanes = next_pow2(reps)
    methods = []
    if ipc.enable_bfs_bipartitioner:
        methods.append(("bfs", lanes))
    if ipc.enable_ggg_bipartitioner:
        methods.append(("ggg", lanes))
    if ipc.enable_random_bipartitioner:
        methods.append(("random", lanes))
    if not methods:
        raise ValueError("no bipartitioner enabled")
    return tuple(methods), reps


# Each method draws its lane keys from a disjoint counter window, so lane j
# of a method keeps its stream when another method's lane count (or the
# shared bucket) changes — positional slicing of one flat key range would
# shift every method after the first whenever the bucket grows.
_METHOD_STRIDE = 1 << 16
_METHOD_WINDOW = {"bfs": 0, "ggg": 1, "random": 2}


def method_lane_keys(seed: int, methods: Tuple[Tuple[str, int], ...]):
    """Stacked per-lane keys in kernel lane order: lane j of method m uses
    counter ``m_window * 2^16 + j`` — lane-count invariant per method."""
    import jax.numpy as jnp

    from ..utils.rng import lane_key

    idx = np.concatenate([
        np.arange(cnt, dtype=np.uint32) + _METHOD_WINDOW[name] * _METHOD_STRIDE
        for name, cnt in methods
    ])
    return jax.vmap(lambda l: lane_key(seed, l))(jnp.asarray(idx))


# ---------------------------------------------------------------------------
# Single-lane kernels (rank-polymorphic; jax.vmap stacks R lanes).
# ---------------------------------------------------------------------------


def _row_sums(vals, edge_u, n_pad: int):
    """Per-node sums of the edge values ``vals`` over the CSR rows, as
    differences of one prefix sum at the row bounds.  Equals
    ``segment_sum(vals, edge_u, n_pad)`` exactly (integers; ``edge_u`` is
    ascending), without the scatter-add, which a TPU runs slowly when it
    is repeated in every lane's grow and FM loops."""
    bounds = jnp.searchsorted(
        edge_u, jnp.arange(n_pad + 1, dtype=edge_u.dtype), side="left"
    )
    cs = jnp.concatenate([jnp.zeros(1, vals.dtype), cumsum(vals)])
    return cs[bounds[1:]] - cs[bounds[:-1]]


def _connections(in0, edge_u, col_idx, edge_w, n_pad: int):
    """Per-node edge weight into block 0 and into block 1.  Pad edges have
    weight 0, so padding contributes to neither."""
    to0 = _row_sums(jnp.where(in0[col_idx], edge_w, 0), edge_u, n_pad)
    degw = _row_sums(edge_w, edge_u, n_pad)
    return to0, degw - to0


def _admit_prefix(sort_keys, cand, node_w, budget):
    """Admit candidates in sorted order while the cumulative admitted weight
    stays within ``budget`` (the maximal fitting prefix).  ``sort_keys`` is a
    lexsort key tuple (last key primary).  Returns (admit mask in original
    order, admitted weight).

    Candidates individually heavier than the whole budget can never be
    admitted, so they are dropped from the cumulative sum up front —
    otherwise one heavy high-priority node would consume the window and
    block every lighter node behind it (the host pool's queues *skip*
    unmovable nodes and continue; this is the prefix-form equivalent)."""
    cand = cand & (node_w <= budget)
    keys = sort_keys[::-1]
    order = argsort(keys)
    cand_s = cand[order]
    w_s = jnp.where(cand_s, node_w[order], 0)
    cum = cumsum(w_s)
    fits = cum <= budget  # a prefix of the sorted order (cum is monotone)
    admit = cand & rank_below(keys, order, jnp.sum(fits))
    return admit, jnp.sum(jnp.where(cand_s & fits, w_s, 0))


def _rand_prio(key):
    def draw(shape):
        return jax.random.randint(
            key, shape, 0, jnp.iinfo(jnp.int32).max, dtype=jnp.int32
        )

    return draw


def _rebalance_side(key, in0, edge_u, col_idx, edge_w, node_w, max_w0, max_w1,
                    *, side: int):
    """Force-repair one overweight side: move the least-loss (max-gain)
    prefix of its nodes out, covering the overload, bounded by the receiving
    side's remaining room.  No-op when the side already fits."""
    n_pad = node_w.shape[0]
    conn0, conn1 = _connections(in0, edge_u, col_idx, edge_w, n_pad)
    total = jnp.sum(node_w)
    w0 = jnp.sum(jnp.where(in0, node_w, 0))
    w1 = total - w0
    if side == 0:
        over = jnp.maximum(w0 - max_w0, 0)
        room = jnp.maximum(max_w1 - w1, 0)
        cand = in0
        gain = conn1 - conn0
    else:
        over = jnp.maximum(w1 - max_w1, 0)
        room = jnp.maximum(max_w0 - w0, 0)
        cand = (~in0) & (node_w > 0)
        gain = conn0 - conn1
    prio = _rand_prio(key)((n_pad,))
    # A candidate heavier than the receiver's whole room can never move;
    # drop it from the cumulative sum so it cannot block lighter nodes
    # behind it (see _admit_prefix — without this, one unmovable heavy
    # node leaves a trivially repairable lane infeasible).
    cand = cand & (node_w <= room)
    keys = (-gain, prio)
    order = argsort(keys)
    cand_s = cand[order]
    w_s = jnp.where(cand_s, node_w[order], 0)
    cum = cumsum(w_s)
    # Minimal covering prefix: admit while the weight moved *before* this
    # node is still short of the overload, and the receiver keeps fitting
    # (both conditions hold on a prefix of the sorted order).
    prefix = (cum - w_s < over) & (cum <= room)
    move = cand & rank_below(keys, order, jnp.sum(prefix))
    return (in0 & ~move) if side == 0 else (in0 | move)


def _fm_round(key, in0, conn0, conn1, node_w, max_w0, max_w1, side0):
    """One boundary-LP/FM round from a single (traced) source side: move the
    best positive-gain prefix that fits the receiving side's budget.
    ``conn0``/``conn1`` are :func:`_connections` of ``in0``.

    Zero-gain moves are admitted with a per-node coin flip (the reference
    initial FM escapes plateaus through its rollback hill-climbing;
    lp_refiner.cc:258-260 uses the same coin) — on mesh-like graphs the
    boundary is mostly gain-0 staircase corners and strict improvement
    stalls far above the optimum (measured 26 vs 16 on grid16 bisections).
    Single-side rounds keep this safe: same-side simultaneous movers only
    improve on their estimated gains, so a round never *increases* the cut;
    the best-state tracker in the lane loop banks the best visit."""
    n_pad = node_w.shape[0]
    kp, kc = jax.random.split(key)
    total = jnp.sum(node_w)
    w0 = jnp.sum(jnp.where(in0, node_w, 0))
    w1 = total - w0
    gain = jnp.where(side0, conn1 - conn0, conn0 - conn1)
    src = jnp.where(side0, in0, (~in0) & (node_w > 0))
    coin = jax.random.bernoulli(kc, 0.5, gain.shape)
    movers = src & ((gain > 0) | ((gain == 0) & coin))
    room = jnp.where(
        side0, jnp.maximum(max_w1 - w1, 0), jnp.maximum(max_w0 - w0, 0)
    )
    prio = _rand_prio(kp)((n_pad,))
    move, _ = _admit_prefix((prio, -gain), movers, node_w, room)
    return jnp.where(side0, in0 & ~move, in0 | move)


def _lane_bipartition(key, edge_u, col_idx, edge_w, node_w, n, target,
                      max_w0, max_w1, *, method: str, grow_trips: int,
                      fm_rounds: int):
    """One pool lane: seed/grow (or random fill), forced balance, FM rounds.
    Returns the block-0 membership mask (n_pad,)."""
    n_pad = node_w.shape[0]
    k_seed, k_grow, k_reb, k_fm = jax.random.split(key, 4)

    if method == "random":
        # Reference initial_random_bipartitioner.cc: random-order fill up to
        # the proportional share.  node_w > 0 excludes shape padding.
        prio = _rand_prio(k_seed)((n_pad,))
        in0, _ = _admit_prefix((prio,), node_w > 0, node_w, target)
    else:
        seed = jax.random.randint(k_seed, (), 0, jnp.maximum(n, 1))
        seed_fits = node_w[seed] <= target
        in0 = jnp.zeros(n_pad, dtype=bool).at[seed].set(seed_fits)
        w0 = jnp.where(seed_fits, node_w[seed], jnp.zeros((), node_w.dtype))

        def grow(t, carry):
            in0, w0 = carry
            conn0 = _row_sums(jnp.where(in0[col_idx], edge_w, 0), edge_u, n_pad)
            cand = (~in0) & (conn0 > 0)  # frontier: adjacent to block 0
            prio = _rand_prio(jax.random.fold_in(k_grow, t))((n_pad,))
            # BFS admits the layer in random order; GGG orders it by
            # connection into block 0 (the host GGG's gain is 2*conn0 —
            # identical ordering), matching initial_{bfs,ggg}_bipartitioner.
            keys = (prio,) if method == "bfs" else (prio, -conn0)
            adm, w_adm = _admit_prefix(keys, cand, node_w, target - w0)
            return in0 | adm, w0 + w_adm

        in0, _ = jax.lax.fori_loop(0, grow_trips, grow, (in0, w0))

    for i, side in enumerate((0, 1)):
        in0 = _rebalance_side(
            jax.random.fold_in(k_reb, i), in0, edge_u, col_idx, edge_w,
            node_w, max_w0, max_w1, side=side,
        )

    # Per-node weighted degree, shared by every connection count below.
    degw = _row_sums(edge_w, edge_u, n_pad)

    def connections(mask):
        to0 = _row_sums(jnp.where(mask[col_idx], edge_w, 0), edge_u, n_pad)
        return to0, degw - to0

    def score(mask, conn0, conn1):
        """(overload, cut): lexicographically smaller is better; overload 0
        == feasible, so overload-first subsumes feasibility-first.  The cut
        counts each cut edge from both ends, from the connections."""
        w0 = jnp.sum(jnp.where(mask, node_w, 0))
        w1 = jnp.sum(node_w) - w0
        over = jnp.maximum(w0 - max_w0, 0) + jnp.maximum(w1 - max_w1, 0)
        cut = jnp.sum(jnp.where(mask, conn1, conn0))
        return over, cut

    def fm(t, carry):
        # The connections of in0 ride the carry: one count per round.
        in0, conn0, conn1, best, b_over, b_cut = carry
        in0 = _fm_round(
            jax.random.fold_in(k_fm, t), in0, conn0, conn1,
            node_w, max_w0, max_w1, (t % 2) == 0,
        )
        conn0, conn1 = connections(in0)
        over, cut = score(in0, conn0, conn1)
        better = (over < b_over) | ((over == b_over) & (cut < b_cut))
        return (
            in0, conn0, conn1,
            jnp.where(better, in0, best),
            jnp.where(better, over, b_over),
            jnp.where(better, cut, b_cut),
        )

    conn0, conn1 = connections(in0)
    over0, cut0 = score(in0, conn0, conn1)
    best = jax.lax.fori_loop(
        0, fm_rounds, fm, (in0, conn0, conn1, in0, over0, cut0)
    )[3]
    return best


# ---------------------------------------------------------------------------
# The pool dispatch: all lanes + on-device selection, one packed result.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("methods", "grow_trips", "fm_rounds"))
def _pool_kernel(keys, edge_u, col_idx, edge_w, node_w, n, target, max_w0,
                 max_w1, *, methods: Tuple[Tuple[str, int], ...],
                 grow_trips: int, fm_rounds: int):
    """Run every lane and select the winner on device.

    Returns one packed (n_pad + STATS_LEN,) array: winning labels followed
    by [cut, feasible, winner_lane, num_feasible, w0, w1] — a single
    ``sync_stats.pull`` is the bisection's only blocking readback.
    """
    from ..utils import compile_stats

    compile_stats.record(
        "ip_pool",
        arrays=[keys, col_idx, node_w],
        statics=(methods, grow_trips, fm_rounds),
    )
    stacks = []
    off = 0
    for name, cnt in methods:
        lane = partial(
            _lane_bipartition, edge_u=edge_u, col_idx=col_idx, edge_w=edge_w,
            node_w=node_w, n=n, target=target, max_w0=max_w0, max_w1=max_w1,
            method=name, grow_trips=grow_trips, fm_rounds=fm_rounds,
        )
        stacks.append(jax.vmap(lane)(keys[off : off + cnt]))
        off += cnt
    in0 = jnp.concatenate(stacks, axis=0)  # (R, n_pad) block-0 membership

    total = jnp.sum(node_w)
    w0 = jnp.sum(jnp.where(in0, node_w[None, :], 0), axis=1)
    w1 = total - w0
    cut = (
        jax.vmap(
            lambda m: jnp.sum(jnp.where(m[edge_u] != m[col_idx], edge_w, 0))
        )(in0)
        // 2
    )
    over = jnp.maximum(w0 - max_w0, 0) + jnp.maximum(w1 - max_w1, 0)
    feasible = over == 0
    R = in0.shape[0]
    # Selection: feasible first, then min overload (ranks the all-infeasible
    # case by least violation), then min cut; the lane index is the last
    # lexsort key, so ties break deterministically on the lowest lane —
    # lane identity, not scheduling, decides.
    order = jnp.lexsort((
        jnp.arange(R, dtype=jnp.int32), cut, over,
        (~feasible).astype(jnp.int32),
    ))
    win = order[0]
    idt = node_w.dtype
    labels = jnp.where(in0[win], 0, 1).astype(idt)
    stats = jnp.stack([
        cut[win].astype(idt),
        feasible[win].astype(idt),
        win.astype(idt),
        jnp.sum(feasible).astype(idt),
        w0[win].astype(idt),
        w1[win].astype(idt),
    ])
    return jnp.concatenate([labels, stats])


# ---------------------------------------------------------------------------
# Host orchestration: padding, lane keys, the single readback, accounting.
# ---------------------------------------------------------------------------

_stats_lock = threading.Lock()
_pool_stats: Dict[str, float] = {
    "calls": 0, "lanes_launched": 0, "lanes_requested": 0,
    "feasible_lanes": 0, "wall_s": 0.0, "fallbacks": 0,
}


def count_pool_fallback() -> None:
    """Count one bisection that resolved to the device pool but ran on the
    host pool (weights too wide for int32, see
    ``initial.bipartitioner.device_pool_supports``)."""
    with _stats_lock:
        _pool_stats["fallbacks"] += 1


def reset_pool_stats() -> None:
    with _stats_lock:
        for k in _pool_stats:
            _pool_stats[k] = 0


def pool_stats_snapshot() -> dict:
    """Device-pool census for bench.py: call count, lane occupancy (requested
    repetitions / bucketed lanes actually launched), feasible-lane rate."""
    with _stats_lock:
        snap = dict(_pool_stats)
    launched = snap["lanes_launched"]
    snap["lane_occupancy"] = (
        round(snap["lanes_requested"] / launched, 4) if launched else None
    )
    snap["feasible_lane_frac"] = (
        round(snap["feasible_lanes"] / launched, 4) if launched else None
    )
    snap["wall_s"] = round(snap["wall_s"], 4)
    return snap


def pool_bipartition_device(
    row_ptr: np.ndarray,
    col_idx: np.ndarray,
    node_w: np.ndarray,
    edge_w: np.ndarray,
    max_w,
    seed: int,
    ipc,
    final_k: int = 2,
) -> Tuple[np.ndarray, dict]:
    """One device-pool bisection of a host CSR graph.

    Builds the shape-bucketed device view (csr.py ladder), derives the
    per-lane key stack (utils/rng.lane_keys), runs every repetition as a
    vmapped lane, and performs the bisection's single blocking readback —
    the packed winning labels + stats.  Returns ``(labels[:n] int32, stats
    dict)``.  Raises on inputs the int32 kernel cannot carry (weights at or
    beyond 2^31); ``initial.bipartitioner.device_pool_supports`` routes
    those to the host pool before dispatch.
    """
    from ..graph.csr import from_numpy_csr
    from ..utils import sync_stats

    n = int(len(row_ptr)) - 1
    total = int(np.asarray(node_w, dtype=np.int64).sum())
    mw0, mw1 = int(max_w[0]), int(max_w[1])
    if max(total, mw0, mw1, int(np.asarray(edge_w, dtype=np.int64).sum())) >= 2**31:
        raise ValueError("device pool requires 32-bit-safe weights")

    methods, reps = method_lane_counts(ipc, final_k)
    lanes = sum(cnt for _, cnt in methods)
    # Grow target: proportional share of the total, capped by block 0's
    # budget (host _grow_target) — computed host-side in int64, then handed
    # to the kernel as a scalar (total * mw0 would overflow int32 on device).
    share = -((-total * mw0) // max(mw0 + mw1, 1))
    target = min(mw0, share)

    t0 = time.perf_counter()
    # The pool call's host span on the profiler's clock (no timer scope:
    # it runs inside initial partitioning's, on extension workers too).
    with jax.profiler.TraceAnnotation("kp/ip_pool"):
        g = from_numpy_csr(row_ptr, col_idx, node_w, edge_w)
        # Pin the owning engine's layout mode through the EngineRuntime
        # accessor: this runs on extension pool workers where thread-local
        # activation is otherwise invisible (kptlint runtime-isolation; the
        # pool submission sites wrap workers in context.propagate_runtime, and
        # the pin keeps the graph correct even if it outlives the activation).
        from ..context import current_runtime

        rt = current_runtime()
        g._layout_mode = rt.layout_build if rt is not None else None
        pv = g.padded()
        idt = pv.node_w.dtype
        keys = method_lane_keys(seed, methods)
        packed = _pool_kernel(
            keys, pv.edge_u, pv.col_idx, pv.edge_w, pv.node_w,
            jnp.asarray(n, dtype=idt), jnp.asarray(target, dtype=idt),
            jnp.asarray(mw0, dtype=idt), jnp.asarray(mw1, dtype=idt),
            methods=methods, grow_trips=grow_trip_count(pv.n_pad),
            fm_rounds=fm_round_count(pv.n_pad, ipc.fm_num_iterations),
        )
        host = sync_stats.pull(packed)  # THE bisection readback
    wall = time.perf_counter() - t0

    labels = host[:n].astype(np.int32)
    cut, feasible, win, n_feasible, w0, w1 = (int(x) for x in host[pv.n_pad :])
    stats = {
        "cut": cut, "feasible": bool(feasible), "winner_lane": win,
        "num_feasible": n_feasible, "block_weights": (w0, w1),
        "lanes": lanes, "lanes_requested": reps * len(methods),
    }
    with _stats_lock:
        _pool_stats["calls"] += 1
        _pool_stats["lanes_launched"] += lanes
        _pool_stats["lanes_requested"] += reps * len(methods)
        _pool_stats["feasible_lanes"] += n_feasible
        _pool_stats["wall_s"] += wall
    return labels, stats


def warm_pool_executable(
    n_pad: int, m_pad: int, lanes_by_method: Tuple[Tuple[str, int], ...],
    fm_iterations: int, dtype=np.int32,
) -> float:
    """AOT-compile the pool kernel for one (n-bucket, m-bucket, lane-count)
    cell (PartitionEngine warmup / ``tools warmup``): lowering + backend
    compile on representative zero operands populates the persistent XLA
    cache, so the first real bisection in that cell starts warm.  Returns
    the wall seconds spent."""
    idt = jnp.dtype(dtype)
    t0 = time.perf_counter()
    args = (
        method_lane_keys(0, lanes_by_method),
        jnp.zeros(m_pad, dtype=idt),  # edge_u
        jnp.zeros(m_pad, dtype=idt),  # col_idx
        jnp.zeros(m_pad, dtype=idt),  # edge_w
        jnp.zeros(n_pad, dtype=idt),  # node_w
        jnp.asarray(1, dtype=idt),    # n
        jnp.asarray(1, dtype=idt),    # target
        jnp.asarray(1, dtype=idt),    # max_w0
        jnp.asarray(1, dtype=idt),    # max_w1
    )
    _pool_kernel.lower(
        *args, methods=lanes_by_method,
        grow_trips=grow_trip_count(n_pad),
        fm_rounds=fm_round_count(n_pad, fm_iterations),
    ).compile()
    return time.perf_counter() - t0
