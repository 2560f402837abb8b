"""The label-propagation engine — THE hot path (north star).

TPU-native re-design of the reference's CRTP LP template
(``kaminpar-shm/label_propagation.h:83``; per-node kernel ``handle_node`` at
:331 accumulating neighbor-cluster ratings into a hash map, CAS weight moves
at :817-841).  Design per SURVEY §7 stage 3 / §2.8-2:

- The racy *asynchronous* CPU LP becomes *synchronous* (Jacobi-style) rounds:
  every node rates its neighbors' clusters against the labels from the start
  of the round, then moves are committed in bulk.  This is a documented
  semantic divergence; quality is recovered with random tie-breaking and more
  rounds (and matches the reference's own distributed LP, which is already
  bulk-synchronous per chunk, global_lp_clusterer.cc).
- Rating accumulation is edge-parallel sort-reduce (ops/gains.best_moves):
  sort CSR slots by (source, neighbor-label), reduce runs — no hash maps,
  static shapes, and high-degree nodes are handled *by construction* (their
  slots parallelize like everyone else's), subsuming the reference's
  two-phase machinery (label_propagation.h:571-601,640-815).
- The weight-constraint CAS race (load-bearing for balance in the reference)
  becomes a strict capacity auction: movers into each cluster are admitted in
  random priority order while the round-start cluster weight plus the running
  total stays within the limit — a deterministic, stricter variant of the
  dist LP refiner's PROBABILISTIC commitment (dkaminpar.h:116-120).

One engine serves both clustering (labels = cluster ids, num_labels = n, as
lp_clusterer.cc instantiates it) and refinement (labels = block ids,
num_labels = k, as lp_refiner.cc does).

Everything is int32-clean (weights, ratings, indices), mirroring the
reference's default 32-bit ID/weight build (CMakeLists.txt:71-79): total node
and edge weight must stay below 2^31.  The 64-bit mode enables jax x64.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .bucketed_gains import bucketed_best_moves, lookup
from .gains import best_moves
from .segment import (
    cumsum,
    row_cumsum,
    run_starts,
    segment_prefix_sum,
    take_rows,
)
from .sort import argsort


class LPState(NamedTuple):
    labels: jax.Array  # (n,) current label per node
    label_weights: jax.Array  # (num_labels,) total node weight per label
    num_moved: jax.Array  # () int32 — nodes moved in the last round
    rounds: jax.Array = np.int32(0)  # () int32 — rounds the last sweep loop ran


# -- LP round counter ---------------------------------------------------------
#
# The sweep loops run on device, so their round counts are device scalars.
# note_rounds keeps them unread; lp_stats_snapshot reads them.  The partition
# path therefore gets no readback and no dispatch from the count.  Past
# _FOLD_AT pending scalars, those already computed are folded into the host
# total, so a long-lived process holds a bounded list and never waits.

_FOLD_AT = 1024
_lp_stats_lock = threading.Lock()
_lp_stats = {"calls": 0, "rounds": 0}
_pending_rounds: list = []


def note_rounds(state: LPState) -> LPState:
    """Count one sweep loop (``lp_iterate_*``) and keep its ``rounds``
    unread until :func:`lp_stats_snapshot`; returns ``state``."""
    done = []
    with _lp_stats_lock:
        _lp_stats["calls"] += 1
        _pending_rounds.append(state.rounds)
        if len(_pending_rounds) >= _FOLD_AT:
            ready = [r.is_ready() for r in _pending_rounds]
            done = [r for r, ok in zip(_pending_rounds, ready) if ok]
            _pending_rounds[:] = [r for r, ok in zip(_pending_rounds, ready) if not ok]
    if done:
        # Finished values: copying them waits for nothing, and leaving them
        # out of sync_stats keeps a partition's count independent of how
        # many loops the process ran before it.
        with jax.transfer_guard_device_to_host("allow"):
            folded = int(np.sum(jax.device_get(done)))  # kpt: ignore[sync-discipline]
        with _lp_stats_lock:
            _lp_stats["rounds"] += folded
    return state


def lp_stats_snapshot() -> dict:
    """{calls, rounds}: sweep loops counted by :func:`note_rounds` and the
    LP rounds they ran.  Reads the pending device scalars, one counted
    transfer each (phase ``lp_stats``), and waits for the loops still
    running; call it outside the timed path."""
    from ..utils import sync_stats

    with _lp_stats_lock:
        pending = list(_pending_rounds)
        _pending_rounds.clear()
    folded = 0
    if pending:
        host = sync_stats.pull(*pending, phase="lp_stats")
        folded = int(np.sum(host if len(pending) > 1 else [host]))
    with _lp_stats_lock:
        _lp_stats["rounds"] += folded
        return dict(_lp_stats)


def num_labels_bucket(k: int, floor: int = 64) -> int:
    """Label-space shape bucket for refinement-mode LP (num_labels = k).

    Every deep/v-cycle run refines at the whole k ladder (2, 4, ..., k) on
    every level, and num_labels is a *shape* (the label-weight tables), so
    each intermediate k used to compile its own kernel.  Padding the label
    space to a floor bucket (empty labels carry weight 0 and a 0 max-weight,
    are adjacent to nothing, and thus are inert in ratings, the auction, and
    the commit) collapses the ladder onto one compiled shape per graph.
    The per-round results are bit-identical to the unpadded instantiation:
    no random draw's shape depends on num_labels, and the auction resolves
    thresholds per label independently."""
    from ..utils.intmath import next_pow2

    return max(floor, next_pow2(k))


@partial(jax.jit, static_argnames=("num_labels",))
def init_state(labels, node_w, num_labels: int) -> LPState:
    label_weights = jax.ops.segment_sum(node_w, labels, num_segments=num_labels)
    return LPState(jnp.asarray(labels), label_weights, jnp.int32(0))


def capacity_auction_sorted(key, movers, target, node_w, base_weights, max_weights, num_labels: int):
    """Admit movers into their target label in random priority order while
    ``base_weights[target] + running-total <= max_weights[target]`` holds.

    The strict bulk-synchronous stand-in for the reference's CAS loop
    (label_propagation.h:817-841).  Returns a boolean accept mask.

    Kept as the oracle implementation: it admits a *maximal* prefix per
    target, but its global n-element lexsort is the single most expensive op
    to compile for TPU (XLA unrolls 1D sort stages; ~10 s per shape at
    n >= 64k, measured), and it sits inside every LP round.  The default
    :func:`capacity_auction` below trades a slightly smaller admitted set for
    a sort-free kernel.
    """
    n = movers.shape[0]
    prio = jax.random.randint(key, (n,), 0, jnp.iinfo(jnp.int32).max, dtype=jnp.int32)
    tkey = jnp.where(movers, target, num_labels)  # sentinel for non-movers
    order = jnp.lexsort((prio, tkey))
    t_s = tkey[order]
    w_s = jnp.where(movers[order], node_w[order], 0)
    first = run_starts(t_s)
    prefix = segment_prefix_sum(w_s, first)
    t_valid = t_s < num_labels
    t_idx = jnp.where(t_valid, t_s, 0)
    ok = t_valid & (base_weights[t_idx] + prefix <= lookup(max_weights, t_idx))
    return jnp.zeros(n, dtype=bool).at[order].set(ok)


_RADIX_BITS = 5
_RADIX = 1 << _RADIX_BITS
_PRIO_BITS = 30  # 6 radix-32 levels resolve the threshold exactly
# Budget for the (num_labels, 32) per-level radix histogram transient.  The
# histogram is accumulated in the *promoted weight dtype*, so the label
# cutoff must scale with its itemsize: the old fixed 2^22-label gate meant a
# ~1 GB transient in 64-bit-weight builds (ADVICE r5 #3).  512 MB keeps the
# int32 cutoff at the measured 2^22 boundary and halves it for int64.
_RADIX_HIST_BYTE_LIMIT = 1 << 29
# Largest per-level histogram the XLA auction builds, in slots.  XLA's TPU
# compile time of a scatter-add grows with its output beyond about 2^21
# slots (12 s at 2^22 on a v5e, 0.3 s at 2^21), so a wide label set takes
# narrower digits and more levels; the resolved threshold is the same.
_HIST_SLOTS = 1 << 21


def _radix_bits(num_labels: int) -> int:
    """Digit width of the XLA auction: the widest divisor of _PRIO_BITS up
    to _RADIX_BITS whose histogram fits _HIST_SLOTS."""
    for bits in (5, 3, 2):
        if num_labels << bits <= _HIST_SLOTS:
            return bits
    return 1


def use_radix_auction(num_labels: int, weight_dtype) -> bool:
    """Whether the radix-32 auction's histogram fits the transient budget
    (else the 30-pass bitwise bisection is the safer trade).  Shared by the
    XLA auction below and the fused Pallas commit kernel (ops/pallas_lp.py)
    so both paths stay bit-identical."""
    itemsize = jnp.dtype(weight_dtype).itemsize
    return num_labels * _RADIX * itemsize <= _RADIX_HIST_BYTE_LIMIT


def capacity_auction(
    key, movers, target, node_w, base_weights, max_weights, num_labels: int,
    fast_compile: bool = False,
):
    """Strict capacity-respecting admission without a sort.

    Equivalent to the sorted-prefix oracle (:func:`capacity_auction_sorted`):
    each mover draws a 30-bit priority, and a per-target priority
    *threshold* is resolved radix-32 (6 levels; each level one histogram
    segment-sum into a (num_labels, 32) table + a tiny cumsum; wide label
    sets take narrower digits, see :func:`_radix_bits`) to the
    largest value whose admitted weight still fits
    ``max_weights[target] - base_weights[target]`` — i.e. the maximal
    random-priority prefix, computed without ordering anything.
    ``base + admitted <= max`` holds unconditionally.

    Cost: 6 x (1 histogram segment-sum + 2 gathers) over n.  History: a
    1D lexsort was first replaced by a bitwise bisection (31 x masked
    segment-sum) because XLA unrolls 1D sort stages on TPU (~10 s compile
    per shape); on-silicon profiling (r5, scripts/tpu_profile2.py) then
    showed the 31 fixed n-sized passes dominating _commit_moves (~36
    ns/edge, nearly half the LP round), and the radix form cuts those
    passes ~5x with bit-identical admission semantics.

    Integer priorities keep the admitted set exactly the sorted oracle's
    maximal prefix whenever priorities are distinct (collisions:
    birthday-bounded, ~5e-4 of movers at n=1M over 2^30; a float32
    threshold was measurably worse — its 2^-24 resolution dropped the
    marginal mover per target per round, a ~2.5% cut regression on
    road512).

    Falls back to the bitwise form when num_labels is too large for the
    (num_labels * 32) per-level histogram to be worth its memory
    (> 2^22 labels; the histogram is a multi-GB transient by 2^24).

    ``fast_compile`` takes the radix form whose TPU compile time does not
    grow with ``num_labels`` (narrower digits past 2^16 labels, log-step row
    scans, no narrow gather); the admitted set is the same.  The sharded
    path keeps the XLA form until it has run on a real mesh.
    """
    n = movers.shape[0]
    # Upper bound (1<<30)-1, NOT 1<<30: the bitwise fallback's threshold
    # maxes out at 2^30-1, so a mover drawing exactly 2^30-1 could never be
    # admitted there (the radix path has no such cap; keeping the draw
    # range below both keeps the two paths bit-identical).
    prio = jax.random.randint(
        key, (n,), 0, (1 << _PRIO_BITS) - 1, dtype=jnp.int32
    )
    # Radix needs a (num_labels * 32) histogram per level — fine for
    # refinement (num_labels = k) and mid-size clustering, but at
    # num_labels = n ~ 2^24 that is a multi-GB transient.  The cutoff is a
    # byte budget on the histogram (accumulated in the promoted weight
    # dtype), so 64-bit-weight builds switch to the bitwise form earlier.
    wdt = jnp.promote_types(
        jnp.asarray(node_w).dtype, jnp.asarray(base_weights).dtype
    )
    if not use_radix_auction(num_labels, wdt):
        return _auction_bitwise(
            prio, movers, target, node_w, base_weights, max_weights, num_labels
        )
    return _auction_radix(
        prio, movers, target, node_w, base_weights, max_weights, num_labels,
        fast_compile,
    )


def _auction_slack(movers, target, node_w, base_weights, max_weights,
                   num_labels: int):
    t_idx = jnp.where(movers, target, 0)
    wdt = jnp.promote_types(
        jnp.asarray(node_w).dtype, jnp.asarray(base_weights).dtype
    )
    w_mover = jnp.where(movers, node_w, 0).astype(wdt)
    max_w_l = lookup(
        max_weights, jnp.arange(num_labels, dtype=jnp.int32)
    ).astype(wdt)
    slack = max_w_l - jnp.asarray(base_weights, dtype=wdt)
    return t_idx, w_mover, slack


def _auction_radix(prio, movers, target, node_w, base_weights, max_weights,
                   num_labels: int, fast_compile: bool):
    """Radix-32 threshold resolution (see capacity_auction)."""
    t_idx, w_mover, slack = _auction_slack(
        movers, target, node_w, base_weights, max_weights, num_labels
    )
    bits = _radix_bits(num_labels) if fast_compile else _RADIX_BITS
    radix = 1 << bits

    def level(carry, shift):
        thr, admitted = carry
        thr_t = thr[t_idx]
        # movers still inside the undecided window [thr, thr + radix<<shift)
        in_window = movers & (
            (prio >> (shift + bits)) == (thr_t >> (shift + bits))
        ) & (prio >= thr_t)
        digit = (prio >> shift) & (radix - 1)
        # Movers outside the window get an out-of-range slot (dropped).
        seg = jnp.where(
            in_window, t_idx * radix + digit, num_labels * radix
        ).astype(jnp.int32)
        hist = jax.ops.segment_sum(
            jnp.where(in_window, w_mover, 0), seg,
            num_segments=num_labels * radix,
        ).reshape(num_labels, radix)
        cum = row_cumsum(hist) if fast_compile else jnp.cumsum(hist, axis=1)
        room = (slack - admitted)[:, None]
        j = jnp.sum((cum <= room) & (room >= 0), axis=1)  # digits fully admitted
        last = jnp.maximum(j - 1, 0)
        at = (
            take_rows(cum, last) if fast_compile
            else jnp.take_along_axis(cum, last[:, None], axis=1)[:, 0]
        )
        gained = jnp.where(j > 0, at, 0)
        admitted = admitted + gained
        thr = thr + (j << shift).astype(jnp.int32)
        return (thr, admitted), None

    # Derive carries elementwise from inputs so their varying manual axes
    # match inside shard_map (fresh jnp.zeros would be replicated and fail
    # the scan carry check).
    thr0 = jnp.zeros_like(slack, dtype=jnp.int32) * slack.astype(jnp.int32)
    adm0 = jnp.zeros_like(slack) * slack
    shifts = jnp.arange(_PRIO_BITS - bits, -1, -bits, dtype=jnp.int32)
    (thr, _), _ = jax.lax.scan(level, (thr0, adm0), shifts)
    return movers & (prio < thr[t_idx])


def _auction_bitwise(prio, movers, target, node_w, base_weights, max_weights,
                     num_labels: int):
    """Bit-at-a-time threshold bisection (the pre-r5 default; kept as the
    large-num_labels fallback)."""
    t_idx, w_mover, slack = _auction_slack(
        movers, target, node_w, base_weights, max_weights, num_labels
    )

    def body(i, thr):
        bit = jnp.int32(1) << (jnp.int32(_PRIO_BITS - 1) - i)
        cand = thr + bit
        adm = movers & (prio < cand[t_idx])
        demand = jax.ops.segment_sum(
            jnp.where(adm, w_mover, 0), t_idx, num_segments=num_labels
        )
        fits = demand <= slack
        return jnp.where(fits, cand, thr)

    thr = jnp.zeros_like(slack, dtype=jnp.int32) * slack.astype(jnp.int32)
    thr = jax.lax.fori_loop(0, _PRIO_BITS, body, thr)
    return movers & (prio < thr[t_idx])


@partial(jax.jit, static_argnames=("num_labels", "active_prob", "allow_tie_moves"))
def lp_round(
    state: LPState,
    key,
    edge_u,
    col_idx,
    edge_w,
    node_w,
    max_label_weights,  # (num_labels,)
    *,
    num_labels: int,
    active_prob: float = 1.0,
    allow_tie_moves: bool = False,
) -> LPState:
    """One synchronous LP round; returns the updated state.

    Equivalent work to one ``perform_iteration`` sweep of the reference
    (label_propagation.h:1682) over all nodes.
    """
    kr, kp = jax.random.split(key)
    target, tconn, own_conn, _ = best_moves(
        kr, state.labels, edge_u, col_idx, edge_w, node_w, state.label_weights,
        max_label_weights, num_labels=num_labels,
        external_only=False, respect_caps=True,
    )
    return _commit_moves(
        state, kp, target, tconn, own_conn, node_w, max_label_weights, num_labels,
        active_prob=active_prob, allow_tie_moves=allow_tie_moves,
    )


def _commit_moves(
    state: LPState,
    kp,
    target,
    tconn,
    own_conn,
    node_w,
    max_label_weights,
    num_labels: int,
    *,
    active_prob: float = 1.0,
    allow_tie_moves: bool = False,
    active=None,
):
    """Synchronous (Jacobi) LP needs two oscillation guards the reference's
    asynchronous sweep gets for free (label_propagation.h processes nodes
    in-place, so each node sees its predecessors' moves):

    - *tie stickiness*: move only on a strict rating improvement over the
      current cluster — otherwise equal-rated nodes flip between clusters
      forever on symmetric graphs (grids), and
    - *random active subset* (``active_prob`` < 1): the bulk-synchronous
      analog of the reference's chunked dist rounds
      (global_lp_clusterer.cc); breaks two-cycles where adjacent nodes
      adopt each other's labels (both strict improvements) and swap back
      and forth without ever merging.

    ``allow_tie_moves`` restores the reference LP *refiner's* zero-gain
    diffusion (lp_refiner.cc:258-260 accepts equal-gain clusters with a
    random bool) — a tie move happens with probability 1/2, and must be
    combined with ``active_prob`` < 1 to stay oscillation-safe under
    synchronous commits.  Clustering keeps strict stickiness.
    """
    labels, label_weights = state.labels, state.label_weights
    kp, ka, kt = jax.random.split(kp, 3)
    better = tconn > own_conn
    if allow_tie_moves:
        coin = jax.random.bernoulli(kt, 0.5, tconn.shape)
        better = better | ((tconn == own_conn) & coin)
    desired = jnp.where(better, target, labels)
    moved = desired != labels
    if active is not None:
        # Colored supersteps (CLP): only the given color class moves; the
        # class is an independent set, so every gain is exact and tie
        # moves cannot oscillate (no two movers are adjacent).
        moved = moved & active
    if active_prob < 1.0:
        moved = moved & jax.random.bernoulli(ka, active_prob, moved.shape)
    accept = capacity_auction(
        kp, moved, desired, node_w, label_weights, max_label_weights,
        num_labels, fast_compile=True,
    )
    commit = moved & accept
    new_labels = jnp.where(commit, desired, labels)
    new_weights = jax.ops.segment_sum(node_w, new_labels, num_segments=num_labels)
    return LPState(new_labels, new_weights, jnp.sum(commit).astype(jnp.int32))


@partial(
    jax.jit,
    static_argnames=("num_labels", "active_prob", "allow_tie_moves", "tie_break"),
)
def lp_round_bucketed(
    state: LPState,
    key,
    buckets,
    heavy,
    gather_idx,
    node_w,
    max_label_weights,
    *,
    num_labels: int,
    active_prob: float = 1.0,
    allow_tie_moves: bool = False,
    tie_break: str = "uniform",
) -> LPState:
    """lp_round over the degree-bucketed layout (the fast path)."""
    kr, kp = jax.random.split(key)
    target, tconn, own_conn, _ = bucketed_best_moves(
        kr, state.labels, buckets, heavy, gather_idx, node_w,
        state.label_weights, max_label_weights,
        external_only=False, respect_caps=True, tie_break=tie_break,
    )
    return _commit_moves(
        state, kp, target, tconn, own_conn, node_w, max_label_weights, num_labels,
        active_prob=active_prob, allow_tie_moves=allow_tie_moves,
    )


@partial(jax.jit, static_argnames=("num_labels", "allow_tie_moves"))
def lp_round_colored(
    state: LPState,
    key,
    buckets,
    heavy,
    gather_idx,
    node_w,
    max_label_weights,
    active,
    *,
    num_labels: int,
    allow_tie_moves: bool = True,
) -> LPState:
    """One colored superstep: only ``active`` (one color class = an
    independent set) may move.  The CLP refiner's inner kernel (reference:
    clp_refiner.cc supersteps)."""
    kr, kp = jax.random.split(key)
    target, tconn, own_conn, _ = bucketed_best_moves(
        kr, state.labels, buckets, heavy, gather_idx, node_w,
        state.label_weights, max_label_weights,
        external_only=False, respect_caps=True,
    )
    return _commit_moves(
        state, kp, target, tconn, own_conn, node_w, max_label_weights, num_labels,
        allow_tie_moves=allow_tie_moves, active=active,
    )


@partial(
    jax.jit,
    static_argnames=("num_labels", "allow_tie_moves"),
    donate_argnums=(0,),
)
def clp_iterate_colors(
    state: LPState,
    keys,
    buckets,
    heavy,
    gather_idx,
    node_w,
    max_label_weights,
    colors,
    num_colors,
    *,
    num_labels: int,
    allow_tie_moves: bool = True,
) -> LPState:
    """One full CLP iteration — every color class's superstep fused into one
    on-device ``fori_loop`` — so an iteration costs one dispatch and one
    batched moved-count readback instead of one of each per superstep (the
    device-resident analog of the clp_refiner.cc superstep loop).

    ``keys`` is the per-superstep key array drawn by the host in the exact
    pre-fusion order (one ``next_key()`` per color; pad rows beyond
    ``num_colors`` are never read), so the fused iteration is bit-identical
    to the dispatch-per-superstep loop it replaces.  The returned state
    carries the iteration's total moved count."""
    from ..utils import compile_stats

    compile_stats.record(
        "clp_iterate",
        arrays=[node_w, keys, *(b.cols for b in buckets), heavy.cols],
        statics=("xla", num_labels, allow_tie_moves),
    )

    def body(c, carry):
        st, moved = carry
        st = lp_round_colored(
            st, keys[c], buckets, heavy, gather_idx, node_w,
            max_label_weights, colors == c, num_labels=num_labels,
            allow_tie_moves=allow_tie_moves,
        )
        return st, moved + st.num_moved

    state, moved = jax.lax.fori_loop(
        0, jnp.asarray(num_colors, dtype=jnp.int32), body,
        (state, jnp.int32(0)),
    )
    return state._replace(num_moved=moved)


@partial(
    jax.jit,
    static_argnames=("num_labels", "active_prob", "allow_tie_moves", "tie_break"),
    donate_argnums=(0,),
)
def lp_iterate_bucketed(
    state: LPState,
    key,
    buckets,
    heavy,
    gather_idx,
    node_w,
    max_label_weights,
    min_moved,
    max_iterations,
    *,
    num_labels: int,
    active_prob: float = 1.0,
    allow_tie_moves: bool = False,
    tie_break: str = "uniform",
) -> LPState:
    """Up to ``max_iterations`` LP rounds fused into one on-device while loop
    with the early-exit condition (< min_moved nodes moved) evaluated on
    device — one dispatch per clustering instead of one per round (the
    host-loop equivalent of lp_clusterer.cc:94-105).  ``max_iterations`` is a
    traced scalar (like ``min_moved``): it only feeds the while-loop cond, and
    keeping it dynamic means one compile per shape bucket even when the
    low-degree boost varies the sweep budget across levels.

    The input state is donated: callers hand over a freshly built
    ``init_state`` and receive the converged state aliased into the same
    HBM buffers — the v-cycle ladder holds one live LP state per level, not
    one per dispatch."""
    from ..utils import compile_stats

    # Trace-time record: fires once per XLA specialization of this kernel
    # (the compile the padding policy tries to minimize), never per round.
    compile_stats.record(
        "lp_iterate",
        arrays=[node_w, *(b.cols for b in buckets), heavy.cols],
        statics=(
            "xla", num_labels, active_prob, allow_tie_moves, tie_break,
            jnp.asarray(max_label_weights).ndim,
        ),
    )
    max_iterations = jnp.asarray(max_iterations, dtype=jnp.int32)

    def cond(carry):
        i, st = carry
        return (i < max_iterations) & (st.num_moved > min_moved)

    def body(carry):
        i, st = carry
        st = lp_round_bucketed(
            st, jax.random.fold_in(key, i), buckets, heavy, gather_idx,
            node_w, max_label_weights, num_labels=num_labels,
            active_prob=active_prob, allow_tie_moves=allow_tie_moves,
            tie_break=tie_break,
        )
        return i + 1, st

    state = state._replace(num_moved=jnp.int32(jnp.iinfo(jnp.int32).max))
    rounds, state = jax.lax.while_loop(cond, body, (jnp.int32(0), state))
    return state._replace(rounds=rounds)


# ---------------------------------------------------------------------------
# Decode-fused LP over the compressed word stream (TeraPart compute tier).
#
# The XLA oracle twin of the fused Pallas compressed kernels
# (ops/pallas_lp.py): each bucket's (R, w) neighbor matrix is materialized
# *in-trace* from the packed gap stream (graph/device_compressed.decode_rows
# — one two-word gather + shift/mask per edge, a row cumsum for the prefix)
# and then rated by the exact dense per-bucket kernel, so no decoded m-sized
# array is ever resident between dispatches and the results are bit-identical
# to the dense bucketed path by construction (asserted in
# tests/test_device_compressed.py).  Heavy rows stay dense (rare; the flat
# edge-parallel path, mirroring the reference's two-phase LP split).
# ---------------------------------------------------------------------------


def compressed_best_moves(
    key,
    labels,
    cbuckets,
    stream,
    heavy,
    gather_idx,
    node_w,
    label_weights,
    max_label_weights,
    *,
    external_only: bool = True,
    respect_caps: bool = True,
    tie_break: str = "uniform",
):
    """bucketed_best_moves over the compressed layout — identical key
    schedule (per-bucket fold_in, heavy at index len(cbuckets)), identical
    rating math (the decoded Bucket feeds the same _bucket_moves)."""
    from ..graph.bucketed import Bucket
    from ..graph.device_compressed import decode_bucket
    from .bucketed_gains import _bucket_moves, _heavy_moves, assemble_moves

    outs = []
    for i, cb in enumerate(cbuckets):
        cols, wgts = decode_bucket(stream, cb, jnp.asarray(node_w).dtype)
        outs.append(
            _bucket_moves(
                jax.random.fold_in(key, i), labels,
                Bucket(cb.nodes, cols, wgts), node_w, label_weights,
                max_label_weights, external_only=external_only,
                respect_caps=respect_caps, tie_break=tie_break,
            )
        )
    if heavy.nodes.shape[0] > 0:
        outs.append(
            _heavy_moves(
                jax.random.fold_in(key, len(cbuckets)), labels, heavy,
                node_w, label_weights, max_label_weights,
                external_only=external_only, respect_caps=respect_caps,
                tie_break=tie_break,
            )
        )
    return assemble_moves(outs, gather_idx, labels)


@partial(
    jax.jit,
    static_argnames=("num_labels", "active_prob", "allow_tie_moves", "tie_break"),
)
def lp_round_compressed(
    state: LPState,
    key,
    cbuckets,
    stream,
    heavy,
    gather_idx,
    node_w,
    max_label_weights,
    *,
    num_labels: int,
    active_prob: float = 1.0,
    allow_tie_moves: bool = False,
    tie_break: str = "uniform",
) -> LPState:
    """One LP round off the compressed stream; bit-identical to
    lp_round_bucketed on the decompressed graph (same split/fold schedule,
    same commit)."""
    kr, kp = jax.random.split(key)
    target, tconn, own_conn, _ = compressed_best_moves(
        kr, state.labels, cbuckets, stream, heavy, gather_idx, node_w,
        state.label_weights, max_label_weights,
        external_only=False, respect_caps=True, tie_break=tie_break,
    )
    return _commit_moves(
        state, kp, target, tconn, own_conn, node_w, max_label_weights,
        num_labels, active_prob=active_prob, allow_tie_moves=allow_tie_moves,
    )


@partial(
    jax.jit,
    static_argnames=("num_labels", "active_prob", "allow_tie_moves", "tie_break"),
    donate_argnums=(0,),
)
def lp_iterate_compressed(
    state: LPState,
    key,
    cbuckets,
    stream,
    heavy,
    gather_idx,
    node_w,
    max_label_weights,
    min_moved,
    max_iterations,
    *,
    num_labels: int,
    active_prob: float = 1.0,
    allow_tie_moves: bool = False,
    tie_break: str = "uniform",
) -> LPState:
    """lp_iterate_bucketed off the compressed stream: the same fused
    on-device while loop (one dispatch per clustering, donated state, the
    early-exit condition on device), with the per-round decode living
    inside the loop body — the finest level's HBM never holds a decoded
    neighbor array between rounds."""
    from ..utils import compile_stats

    compile_stats.record(
        "lp_iterate_compressed",
        arrays=[node_w, stream.words, *(b.nodes for b in cbuckets), heavy.cols],
        statics=(
            "xla", num_labels, active_prob, allow_tie_moves, tie_break,
            jnp.asarray(max_label_weights).ndim,
        ),
    )
    max_iterations = jnp.asarray(max_iterations, dtype=jnp.int32)

    def cond(carry):
        i, st = carry
        return (i < max_iterations) & (st.num_moved > min_moved)

    def body(carry):
        i, st = carry
        st = lp_round_compressed(
            st, jax.random.fold_in(key, i), cbuckets, stream, heavy,
            gather_idx, node_w, max_label_weights, num_labels=num_labels,
            active_prob=active_prob, allow_tie_moves=allow_tie_moves,
            tie_break=tie_break,
        )
        return i + 1, st

    state = state._replace(num_moved=jnp.int32(jnp.iinfo(jnp.int32).max))
    rounds, state = jax.lax.while_loop(cond, body, (jnp.int32(0), state))
    return state._replace(rounds=rounds)


@partial(jax.jit, static_argnames=("num_labels",))
def cluster_two_hop_nodes_compressed(
    state: LPState,
    key,
    cbuckets,
    stream,
    heavy,
    gather_idx,
    node_w,
    max_label_weights,
    *,
    num_labels: int,
) -> LPState:
    """Two-hop clustering with the favored-cluster pass decoded in-trace
    from the compressed stream (the dense twin is
    cluster_two_hop_nodes_bucketed; same key split, same match)."""
    kr, kp = jax.random.split(key)
    favored, fconn, _, _ = compressed_best_moves(
        kr, state.labels, cbuckets, stream, heavy, gather_idx, node_w,
        state.label_weights, max_label_weights,
        external_only=False, respect_caps=False,
    )
    return two_hop_match(
        state, kp, favored, fconn, node_w, max_label_weights,
        num_labels=num_labels,
    )


@partial(jax.jit, static_argnames=("num_labels",))
def cluster_isolated_nodes(
    state: LPState,
    row_ptr,
    node_w,
    max_label_weights,
    *,
    num_labels: int,
) -> LPState:
    """Group isolated (degree-0) nodes into max-weight-respecting clusters.

    Reference: ``handle_isolated_nodes`` (label_propagation.h:872-917).  The
    TPU version packs isolated nodes by prefix weight into buckets of width
    ``cap - w_max + 1`` (w_max = heaviest isolated node): a bucket's total
    weight is <= width + w_max - 1 = cap even when a node straddles a bucket
    boundary, so no cluster exceeds the limit.  Slightly more fragmented than
    the reference's sequential greedy packing, never overweight.
    """
    labels, num_moved = state.labels, state.num_moved
    n = labels.shape[0]
    deg = row_ptr[1:] - row_ptr[:-1]
    iso = (deg == 0) & (node_w > 0)  # weight-0 degree-0 nodes are shape padding
    w = jnp.where(iso, node_w, 0)
    cap = jnp.maximum(lookup(max_label_weights, 0), 1)  # scalar limit for clustering
    w_max = jnp.max(w)
    width = jnp.maximum(cap - w_max + 1, 1)
    start = cumsum(w) - w
    bucket = jnp.where(iso, jnp.clip(start // width, 0, n - 1), n)
    bucket = bucket.astype(jnp.int32)
    ids = jnp.arange(n, dtype=labels.dtype)
    rep = jax.ops.segment_min(jnp.where(iso, ids, n), bucket, num_segments=n + 1)
    new_labels = jnp.where(iso, rep[bucket].astype(labels.dtype), labels)
    new_weights = jax.ops.segment_sum(node_w, new_labels, num_segments=num_labels)
    return LPState(new_labels, new_weights, num_moved)


@partial(jax.jit, static_argnames=("num_labels",))
def cluster_two_hop_nodes(
    state: LPState,
    key,
    edge_u,
    col_idx,
    edge_w,
    node_w,
    max_label_weights,
    *,
    num_labels: int,
) -> LPState:
    """Match still-singleton clusters through their favored cluster.

    Reference: two-hop clustering (label_propagation.h:919-1120): nodes that
    could not join any cluster are grouped with *other singletons that favor
    the same cluster* (two-hop neighbors).  TPU version: compute each
    singleton's favored (max-rated, feasibility-ignored) cluster, sort
    singletons by favored cluster, and merge odd run positions into the
    preceding slot's cluster subject to the weight limit.
    """
    kr, kp = jax.random.split(key)
    favored, fconn, _, _ = best_moves(
        kr, state.labels, edge_u, col_idx, edge_w, node_w, state.label_weights,
        max_label_weights, num_labels=num_labels,
        external_only=False, respect_caps=False,
    )
    return two_hop_match(state, kp, favored, fconn, node_w, max_label_weights, num_labels=num_labels)


@partial(jax.jit, static_argnames=("num_labels",))
def cluster_two_hop_nodes_bucketed(
    state: LPState,
    key,
    buckets,
    heavy,
    gather_idx,
    node_w,
    max_label_weights,
    *,
    num_labels: int,
) -> LPState:
    """Two-hop clustering with the favored-cluster pass on the bucketed
    layout."""
    kr, kp = jax.random.split(key)
    favored, fconn, _, _ = bucketed_best_moves(
        kr, state.labels, buckets, heavy, gather_idx, node_w,
        state.label_weights, max_label_weights,
        external_only=False, respect_caps=False,
    )
    return two_hop_match(state, kp, favored, fconn, node_w, max_label_weights, num_labels=num_labels)


@partial(jax.jit, static_argnames=("num_labels",))
def two_hop_match(
    state: LPState,
    kp,
    favored,
    fconn,
    node_w,
    max_label_weights,
    *,
    num_labels: int,
) -> LPState:
    labels, label_weights = state.labels, state.label_weights
    num_moved = state.num_moved
    n = labels.shape[0]

    # Singleton = node alone in its own cluster.
    cluster_sizes = jax.ops.segment_sum(
        jnp.ones(n, dtype=jnp.int32), labels, num_segments=num_labels
    )
    singleton = (labels == jnp.arange(n, dtype=labels.dtype)) & (
        cluster_sizes[labels] == 1
    )
    has = fconn > 0

    # Pair up singletons that favor the same cluster: sort by favored id and
    # merge odd positions into the preceding even position's cluster.
    # NOTE: this is deliberately the *pairwise* lexsort merge, not a
    # sort-free rep-grouping — grouping every singleton of a favored
    # cluster into one rep merges 2-hop nodes that are mutually
    # non-adjacent in bulk, which measured a ~10% cut regression on the
    # weighted-grid road class (round-4 bisect).  The lexsort runs once
    # per clustering level (not in the LP round loop), so its one-shape
    # compile cost is amortized by the persistent cache.
    fkey = jnp.where(singleton & has, favored, n)  # sentinel: not eligible
    prio = jax.random.randint(kp, (n,), 0, jnp.iinfo(jnp.int32).max, dtype=jnp.int32)
    order2 = argsort((fkey, prio))
    f_s = fkey[order2]
    first2 = run_starts(f_s)
    rid2 = cumsum(first2.astype(jnp.int32)) - 1
    starts = jax.ops.segment_max(
        jnp.where(first2, jnp.arange(n, dtype=jnp.int32), 0), rid2, num_segments=n
    )
    pos_in_run = jnp.arange(n, dtype=jnp.int32) - starts[rid2]
    prev_node = jnp.concatenate([order2[:1], order2[:-1]])
    partner_label = labels[prev_node]
    valid = (f_s < n) & (pos_in_run % 2 == 1)
    w_s = node_w[order2]
    w_prev = jnp.concatenate([w_s[:1], w_s[:-1]])
    # Clustering weight limits are a uniform scalar (every caller passes
    # one; lp_clusterer.py builds it as a 0-d array on purpose) — a
    # per-label table would need the favored cluster's own cap here.
    fits = w_s + w_prev <= lookup(max_label_weights, 0)
    merge = valid & fits
    new_labels = labels.at[order2].set(
        jnp.where(merge, partner_label, labels[order2])
    )
    new_weights = jax.ops.segment_sum(node_w, new_labels, num_segments=num_labels)
    return LPState(new_labels, new_weights, num_moved)
