"""LP engine kernel tests (reference: the LP engine is exercised through
lp_clusterer/lp_refiner tests; here we test the jitted rounds directly)."""

import jax.numpy as jnp
import numpy as np
import pytest

from kaminpar_tpu.graph import generators
from kaminpar_tpu.ops import lp
from kaminpar_tpu.utils import next_key


def _run_rounds(g, max_w_scalar, rounds=5):
    pv = g.padded()
    idt = pv.row_ptr.dtype
    labels = jnp.concatenate(
        [jnp.arange(pv.n, dtype=idt), jnp.full(pv.n_pad - pv.n, pv.anchor, dtype=idt)]
    )
    state = lp.init_state(labels, pv.node_w, pv.n_pad)
    max_w = jnp.full(pv.n_pad, max_w_scalar, dtype=idt)
    for _ in range(rounds):
        state = lp.lp_round(
            state, next_key(), pv.edge_u, pv.col_idx, pv.edge_w, pv.node_w,
            max_w, num_labels=pv.n_pad,
        )
    return pv, state, max_w


def test_lp_clusters_respect_weight_limit():
    g = generators.rmat_graph(9, 8, seed=2)
    pv, state, max_w = _run_rounds(g, 30)
    lw = np.asarray(state.label_weights)
    assert lw.max() <= 30
    assert lw.sum() == g.total_node_weight


def test_lp_merges_connected_nodes():
    g = generators.complete_graph(8)
    pv, state, _ = _run_rounds(g, 100)
    labels = np.asarray(state.labels)[: g.n]
    # complete graph with no weight limit pressure: everything merges
    assert len(np.unique(labels)) < 8


def test_lp_weight_conservation_on_grid():
    g = generators.grid2d_graph(8, 8)
    pv, state, _ = _run_rounds(g, 10)
    lw = np.asarray(state.label_weights)
    assert lw.sum() == 64
    assert lw.max() <= 10


def test_isolated_nodes_clustering():
    # 5 isolated nodes + one edge
    import numpy as np

    from kaminpar_tpu.graph import from_edge_list

    g = from_edge_list(7, np.array([[5, 6]]))
    pv = g.padded()
    idt = pv.row_ptr.dtype
    labels = jnp.concatenate(
        [jnp.arange(pv.n, dtype=idt), jnp.full(pv.n_pad - pv.n, pv.anchor, dtype=idt)]
    )
    state = lp.init_state(labels, pv.node_w, pv.n_pad)
    max_w = jnp.full(pv.n_pad, 2, dtype=idt)
    state = lp.cluster_isolated_nodes(state, pv.row_ptr, pv.node_w, max_w, num_labels=pv.n_pad)
    labels = np.asarray(state.labels)
    # isolated nodes 0..4 grouped in pairs of weight <= 2
    lw = np.asarray(state.label_weights)
    assert lw.max() <= 2
    iso_labels = labels[:5]
    # grouped: fewer clusters than nodes
    assert len(np.unique(iso_labels)) <= 3
    # pad nodes untouched (all on anchor)
    assert (labels[pv.n:] == pv.anchor).all()


def test_two_hop_clustering_on_star():
    # star: leaves can't join the center if its cluster is weight-capped,
    # but two-hop matches leaves pairwise through their favored cluster
    g = generators.star_graph(8)
    pv, state, max_w = _run_rounds(g, 2, rounds=3)
    state2 = lp.cluster_two_hop_nodes(
        state, next_key(), pv.edge_u, pv.col_idx, pv.edge_w, pv.node_w,
        max_w, num_labels=pv.n_pad,
    )
    lw = np.asarray(state2.label_weights)
    assert lw.max() <= 2
    n_clusters_before = len(np.unique(np.asarray(state.labels)[: g.n]))
    n_clusters_after = len(np.unique(np.asarray(state2.labels)[: g.n]))
    assert n_clusters_after <= n_clusters_before


def test_lp_refinement_mode_small_k():
    """LP with num_labels=k (block mode) reduces the cut of a bad partition."""
    from kaminpar_tpu.graph import metrics

    g = generators.grid2d_graph(8, 8)
    pv = g.padded()
    rng = np.random.default_rng(3)
    part = rng.integers(0, 2, g.n).astype(np.int32)
    init_cut = metrics.edge_cut(g, part)
    labels = pv.pad_node_array(jnp.asarray(part), 0)
    state = lp.init_state(labels, pv.node_w, 2)
    max_w = jnp.full(2, 40, dtype=pv.node_w.dtype)
    for _ in range(8):
        state = lp.lp_round(
            state, next_key(), pv.edge_u, pv.col_idx, pv.edge_w, pv.node_w,
            max_w, num_labels=2,
        )
    final_cut = metrics.edge_cut(g, np.asarray(state.labels)[: g.n])
    assert final_cut < init_cut
    bw = np.asarray(state.label_weights)
    assert bw.max() <= 40 and bw.sum() == 64


def test_capacity_auction_strict_and_matches_oracle_uncontended():
    """The probabilistic auction must (a) never admit past a target's cap —
    the invariant the sorted-prefix oracle (capacity_auction_sorted) was
    built for — and (b) admit *everything* the oracle admits in the
    uncontended case (demand <= slack), so the common path loses nothing."""
    rng = np.random.default_rng(11)
    n, L = 512, 16
    movers = jnp.asarray(rng.random(n) < 0.7)
    target = jnp.asarray(rng.integers(0, L, n).astype(np.int32))
    node_w = jnp.asarray(rng.integers(1, 5, n).astype(np.int32))
    base = jnp.zeros(L, dtype=jnp.int32)

    # (a) contended: tight caps, strictness must hold for both variants.
    cap = jnp.asarray(np.full(L, 23, dtype=np.int32))
    for fn in (lp.capacity_auction, lp.capacity_auction_sorted):
        acc = fn(next_key(), movers, target, node_w, base, cap, L)
        w = np.where(np.asarray(movers & acc), np.asarray(node_w), 0)
        per = np.bincount(np.asarray(target), weights=w, minlength=L)
        assert (per <= 23).all(), fn.__name__

    # (b) uncontended: both admit every mover.
    wide = jnp.asarray(np.full(L, 10**6, dtype=np.int32))
    key = next_key()
    acc_p = lp.capacity_auction(key, movers, target, node_w, base, wide, L)
    acc_s = lp.capacity_auction_sorted(key, movers, target, node_w, base, wide, L)
    assert bool(jnp.all((movers & acc_p) == movers))
    assert bool(jnp.all((movers & acc_s) == movers))


@pytest.mark.parametrize(
    "fast_compile,L", [(False, 24), (True, 24), (True, 1 << 17)]
)
def test_auction_radix_equals_bitwise_and_oracle(fast_compile, L):
    """The radix-32 threshold auction (r5 on-silicon rewrite) must admit
    EXACTLY the bitwise bisection's set, which is the maximal
    random-priority prefix per target (the sorted-oracle semantics) — also
    in its fast-compile form, whose digits narrow past 2^16 labels."""
    from kaminpar_tpu.ops.lp import _auction_bitwise, _auction_radix

    rng = np.random.default_rng(0)
    n = 2048  # fixed shapes: one compile for all trials
    for trial in range(6):
        movers = rng.random(n) < 0.6
        target = rng.integers(0, L, n)
        node_w = rng.integers(1, 9, n)
        base = rng.integers(0, 40, L)
        maxw = rng.integers(10, 80, L)
        # unique priorities: collisions make the oracle order ambiguous
        prio = rng.choice(1 << 30, size=n, replace=False).astype(np.int32)
        args = (jnp.asarray(prio), jnp.asarray(movers), jnp.asarray(target),
                jnp.asarray(node_w), jnp.asarray(base), jnp.asarray(maxw), L)
        a = np.asarray(_auction_radix(*args, fast_compile))
        b = np.asarray(_auction_bitwise(*args))
        assert np.array_equal(a, b), f"trial {trial}"
        if L > n:
            continue  # the bitwise form is the oracle's equal at small L
        acc = np.zeros(n, bool)
        for t in range(L):
            idx = np.flatnonzero(movers & (target == t))
            idx = idx[np.argsort(prio[idx])]
            room = maxw[t] - base[t]
            for u in idx:
                if node_w[u] <= room:
                    acc[u] = True
                    room -= node_w[u]
                else:
                    break  # maximal prefix stops at the first non-fit
        assert np.array_equal(a, acc), f"trial {trial} vs oracle"
