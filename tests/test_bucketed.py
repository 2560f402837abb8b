"""Bucketed fast-path vs flat reference-path equivalence.

The flat sort-reduce ops (ops/gains.py, ops/lp.py lp_round) are the semantic
reference; the degree-bucketed kernels (ops/bucketed_gains.py) must compute
identical ratings/feasibility (targets may differ only within random
tie-breaks, so we compare tie-break-independent quantities)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaminpar_tpu.graph import generators
from kaminpar_tpu.graph.bucketed import build_bucketed_view
from kaminpar_tpu.ops import lp
from kaminpar_tpu.ops.bucketed_gains import bucketed_best_moves
from kaminpar_tpu.ops.gains import best_moves
from kaminpar_tpu.utils import next_key


def _random_graph(rng, n=200, extra_edges=400, weighted=True):
    edges = rng.integers(0, n, (extra_edges, 2))
    w = rng.integers(1, 5, extra_edges) if weighted else None
    return generators.from_edge_list(n, edges, edge_weights=w)


def _views(graph, min_width=8, max_width=32, min_rows=4):
    """Small bucket params so tests exercise multiple buckets + heavy path."""
    pv = graph.padded()
    bv = build_bucketed_view(
        np.asarray(graph.row_ptr), np.asarray(graph.col_idx),
        np.asarray(graph.edge_w), graph.n, pv.anchor,
        min_width=min_width, max_width=max_width, min_rows=min_rows,
    )
    return pv, bv


@pytest.mark.parametrize("external_only,respect_caps", [
    (False, True), (True, True), (False, False), (True, False),
])
def test_best_moves_equivalence(rng, external_only, respect_caps):
    graph = _random_graph(rng)
    pv, bv = _views(graph)
    n_pad = pv.n_pad
    num_labels = n_pad
    labels = jnp.asarray(rng.integers(0, graph.n, n_pad).astype(np.int32))
    label_weights = jax.ops.segment_sum(pv.node_w, labels, num_segments=num_labels)
    max_w = jnp.full(num_labels, 6, dtype=jnp.int32)

    key = next_key()
    t_f, c_f, o_f, h_f = best_moves(
        key, labels, pv.edge_u, pv.col_idx, pv.edge_w, pv.node_w,
        label_weights, max_w, num_labels=num_labels,
        external_only=external_only, respect_caps=respect_caps,
    )
    t_b, c_b, o_b, h_b = bucketed_best_moves(
        key, labels, bv.buckets, bv.heavy, bv.gather_idx, pv.node_w,
        label_weights, max_w,
        external_only=external_only, respect_caps=respect_caps,
    )
    n = graph.n
    # Tie-break independent quantities must match exactly on real nodes.
    np.testing.assert_array_equal(np.asarray(o_f)[:n], np.asarray(o_b)[:n])
    np.testing.assert_array_equal(np.asarray(h_f)[:n], np.asarray(h_b)[:n])
    np.testing.assert_array_equal(np.asarray(c_f)[:n], np.asarray(c_b)[:n])
    # The chosen target must be a best-rated feasible candidate: its rating
    # equals the flat best rating (tconn), even if the tie-broken label differs.
    tf, tb = np.asarray(t_f)[:n], np.asarray(t_b)[:n]
    hf = np.asarray(h_f)[:n]
    lab = np.asarray(labels)[:n]
    assert np.array_equal(tf[~hf], lab[~hf])
    assert np.array_equal(tb[~hf], lab[~hf])


def test_no_pathological_merge_inflation(rng):
    """Undersized width classes must merge to the largest *naturally occupied*
    class, not cascade to MAX_WIDTH (a 2000-node graph must not become a
    (rows, 4096) monster)."""
    graph = _random_graph(rng, n=2000, extra_edges=8000)
    pv = graph.padded()
    bv = build_bucketed_view(
        np.asarray(graph.row_ptr), np.asarray(graph.col_idx),
        np.asarray(graph.edge_w), graph.n, pv.anchor,
    )  # default (production) merge parameters
    max_deg = int(np.max(np.diff(np.asarray(graph.row_ptr))))
    for b in bv.buckets:
        assert b.cols.shape[1] <= max(8, 1 << (max_deg - 1).bit_length())
    slots = sum(int(b.cols.shape[0]) * int(b.cols.shape[1]) for b in bv.buckets)
    assert slots <= 8 * graph.m + 8 * 4096  # padding bounded, no 500x blowup


def test_heavy_path_exercised(rng):
    graph = generators.star_graph(100)
    pv, bv = _views(graph, max_width=16)
    assert bv.heavy.nodes.shape[0] > 0  # hub has degree 100 > 16
    num_labels = pv.n_pad
    labels = jnp.arange(pv.n_pad, dtype=jnp.int32)
    label_weights = jax.ops.segment_sum(pv.node_w, labels, num_segments=num_labels)
    max_w = jnp.full(num_labels, 1000, dtype=jnp.int32)
    key = next_key()
    t_b, c_b, o_b, h_b = bucketed_best_moves(
        key, labels, bv.buckets, bv.heavy, bv.gather_idx, pv.node_w,
        label_weights, max_w, external_only=False, respect_caps=True,
    )
    # Hub (node 0, heavy) sees 100 singleton neighbors, each rating 1.
    assert bool(h_b[0])
    assert int(c_b[0]) == 1
    # Every leaf's best candidate is the hub's cluster with rating 1.
    leaves = np.arange(1, 101)
    np.testing.assert_array_equal(np.asarray(t_b)[leaves], 0)
    np.testing.assert_array_equal(np.asarray(c_b)[leaves], 1)


def test_lp_round_bucketed_matches_flat_cut_quality(rng):
    graph = generators.grid2d_graph(20, 20)
    pv, bv = _views(graph)
    n_pad = pv.n_pad
    idt = pv.row_ptr.dtype
    labels = jnp.concatenate([
        jnp.arange(pv.n, dtype=idt),
        jnp.full(n_pad - pv.n, pv.anchor, dtype=idt),
    ])
    max_w = jnp.full(n_pad, 16, dtype=jnp.int32)

    state_f = lp.init_state(labels, pv.node_w, n_pad)
    state_b = lp.init_state(labels, pv.node_w, n_pad)
    # active_prob < 1: the documented oscillation guard for symmetric grids
    # (ops/lp.py:_commit_moves) — with full activation, strict-improvement
    # synchronous LP barely merges on a grid and the internal-edge counts
    # below are single-digit tie-draw noise rather than a quality signal.
    for _ in range(5):
        state_f = lp.lp_round(
            state_f, next_key(), pv.edge_u, pv.col_idx, pv.edge_w, pv.node_w,
            max_w, num_labels=n_pad, active_prob=0.5,
        )
        state_b = lp.lp_round_bucketed(
            state_b, next_key(), bv.buckets, bv.heavy, bv.gather_idx,
            pv.node_w, max_w, num_labels=n_pad, active_prob=0.5,
        )

    def quality(state):
        lab = np.asarray(state.labels)
        u, v = np.asarray(pv.edge_u), np.asarray(pv.col_idx)
        clusters = len(np.unique(lab[: graph.n]))
        internal = int(np.sum((lab[u] == lab[v]) & (np.asarray(pv.edge_w) > 0)))
        return clusters, internal

    cl_f, in_f = quality(state_f)
    cl_b, in_b = quality(state_b)
    # Both paths should coarsen comparably (same algorithm, different layout).
    assert abs(cl_f - cl_b) <= max(5, 0.2 * cl_f)
    assert in_b >= 0.7 * in_f

    # Weight invariant: cluster weights respect the cap on both paths.
    for state in (state_f, state_b):
        w = np.asarray(state.label_weights)
        assert w.max() <= 16


def test_lp_iterate_bucketed(rng):
    graph = generators.grid2d_graph(16, 16)
    pv, bv = _views(graph)
    n_pad = pv.n_pad
    idt = pv.row_ptr.dtype
    labels = jnp.concatenate([
        jnp.arange(pv.n, dtype=idt),
        jnp.full(n_pad - pv.n, pv.anchor, dtype=idt),
    ])
    max_w = jnp.full(n_pad, 12, dtype=jnp.int32)
    state = lp.init_state(labels, pv.node_w, n_pad)
    out = lp.lp_iterate_bucketed(
        state, next_key(), bv.buckets, bv.heavy, bv.gather_idx,
        pv.node_w, max_w, jnp.int32(0), jnp.int32(5), num_labels=n_pad,
    )
    lab = np.asarray(out.labels)[: graph.n]
    assert len(np.unique(lab)) < graph.n  # clustering actually happened
    assert np.asarray(out.label_weights).max() <= 12


# ---------------------------------------------------------------------------
# Device-side layout build (ISSUE 2): bit-identical to the host builder.
# ---------------------------------------------------------------------------


def _assert_views_equal(a, b):
    assert len(a.buckets) == len(b.buckets), (len(a.buckets), len(b.buckets))
    for i, (ba, bb) in enumerate(zip(a.buckets, b.buckets)):
        for name in ("nodes", "cols", "wgts"):
            xa, xb = np.asarray(getattr(ba, name)), np.asarray(getattr(bb, name))
            assert xa.shape == xb.shape, (i, name, xa.shape, xb.shape)
            assert np.array_equal(xa, xb), (i, name)
    for name in ("nodes", "row", "cols", "wgts"):
        assert np.array_equal(
            np.asarray(getattr(a.heavy, name)), np.asarray(getattr(b.heavy, name))
        ), name
    assert np.array_equal(np.asarray(a.gather_idx), np.asarray(b.gather_idx))
    assert a.n == b.n


@pytest.mark.parametrize("gname", ["rmat", "grid", "star", "heavy_star"])
def test_device_layout_build_matches_host(gname):
    from kaminpar_tpu.graph.bucketed import build_bucketed_view_device

    graphs = {
        "rmat": lambda: generators.rmat_graph(10, 8, seed=5),
        "grid": lambda: generators.grid2d_graph(40, 40),
        "star": lambda: generators.star_graph(200),
        # center degree 4999 > MAX_WIDTH: exercises the heavy part
        "heavy_star": lambda: generators.star_graph(5000),
    }
    g = graphs[gname]()
    pv = g.padded()
    host = build_bucketed_view(
        np.asarray(g.row_ptr), np.asarray(g.col_idx), np.asarray(g.edge_w),
        g.n, pv.anchor,
    )
    dev = build_bucketed_view_device(pv, g.n, g.deg_histogram())
    _assert_views_equal(host, dev)


def test_deg_histogram_host_device_agree():
    from kaminpar_tpu.graph.bucketed import (
        device_deg_histogram, host_deg_histogram,
    )

    for g in (generators.rmat_graph(10, 8, seed=6), generators.star_graph(5000)):
        pv = g.padded()
        deg = pv.row_ptr[1:] - pv.row_ptr[:-1]
        real = jnp.arange(pv.n_pad) < pv.n
        dev = np.asarray(jax.jit(device_deg_histogram)(deg, real))
        host = host_deg_histogram(np.asarray(g.row_ptr), g.n)
        assert np.array_equal(dev.astype(np.int64), host), (dev, host)


def test_lp_round_identical_on_device_layout():
    """An LP round over the device-built layout commits exactly the same
    labels as over the host-built layout (the layouts are bit-identical,
    so the kernel results must be too)."""
    from kaminpar_tpu.graph.bucketed import build_bucketed_view_device
    from kaminpar_tpu.utils import reseed

    g = generators.rmat_graph(10, 8, seed=8)
    pv = g.padded()
    host = build_bucketed_view(
        np.asarray(g.row_ptr), np.asarray(g.col_idx), np.asarray(g.edge_w),
        g.n, pv.anchor,
    )
    dev = build_bucketed_view_device(pv, g.n, g.deg_histogram())
    idt = pv.row_ptr.dtype
    labels = jnp.concatenate(
        [jnp.arange(pv.n, dtype=idt), jnp.full(pv.n_pad - pv.n, pv.anchor, dtype=idt)]
    )
    max_w = jnp.asarray(30, dtype=idt)
    outs = {}
    for name, bv in (("host", host), ("device", dev)):
        reseed(21)
        state = lp.init_state(labels, pv.node_w, pv.n_pad)
        state = lp.lp_round_bucketed(
            state, next_key(), bv.buckets, bv.heavy, bv.gather_idx,
            pv.node_w, max_w, num_labels=pv.n_pad,
        )
        outs[name] = np.asarray(state.labels)
    assert np.array_equal(outs["host"], outs["device"])


# ---------------------------------------------------------------------------
# gather_idx on the shape ladder: (n_pad,), -1 on pad nodes.
# ---------------------------------------------------------------------------


def _both_layouts(g):
    from kaminpar_tpu.graph.bucketed import build_bucketed_view_device

    pv = g.padded()
    host = build_bucketed_view(
        np.asarray(g.row_ptr), np.asarray(g.col_idx), np.asarray(g.edge_w),
        g.n, pv.anchor, min_width=8, max_width=32, min_rows=4,
    )
    return pv, {
        "host": host,
        "device": build_bucketed_view_device(pv, g.n, g.deg_histogram()),
    }


@pytest.mark.parametrize("builder", ["host", "device"])
def test_pad_nodes_get_their_defaults(rng, builder):
    """Pad nodes gather no row: ``assemble_moves`` gives them (own label,
    0, 0, no candidate) and ``bucketed_neighbor_reduce`` gives them 0,
    whatever their labels say; real nodes get their rows' results."""
    from kaminpar_tpu.ops.bucketed_gains import bucketed_neighbor_reduce

    g = _random_graph(rng, n=300, extra_edges=900)
    pv, views = _both_layouts(g)
    bv = views[builder]
    n, n_pad = g.n, pv.n_pad
    gi = np.asarray(bv.gather_idx)
    assert gi.shape == (n_pad,)
    assert np.all(gi[n:] == -1) and np.all(gi[:n] >= 0)
    assert len(np.unique(gi[:n])) == n

    # Pads carry arbitrary labels, some of them real clusters.
    labels = jnp.asarray(rng.integers(0, n, n_pad).astype(np.int32))
    label_weights = jax.ops.segment_sum(pv.node_w, labels, num_segments=n_pad)
    max_w = jnp.full(n_pad, 1 << 20, dtype=jnp.int32)
    target, tconn, own_conn, has = (
        np.asarray(x) for x in bucketed_best_moves(
            next_key(), labels, bv.buckets, bv.heavy, bv.gather_idx,
            pv.node_w, label_weights, max_w, external_only=False,
        )
    )
    np.testing.assert_array_equal(target[n:], np.asarray(labels)[n:])
    assert not tconn[n:].any() and not own_conn[n:].any() and not has[n:].any()

    wdeg = np.asarray(bucketed_neighbor_reduce(
        lambda nodes, cols, w: w, bv.buckets, bv.heavy, bv.gather_idx
    ))
    assert wdeg.shape == (n_pad,)
    expect = np.add.reduceat(
        np.asarray(g.edge_w), np.asarray(g.row_ptr)[:-1]
    ) * (np.diff(np.asarray(g.row_ptr)) > 0)
    np.testing.assert_array_equal(wdeg[:n], expect)
    assert not wdeg[n:].any()


def test_new_graph_reuses_compiled_layout_programs():
    """Two graphs on the same ladder rung and bucket plan but of different
    real node counts share every compiled LP, two-hop and balancer program:
    the second graph compiles (or loads) none of them."""
    from kaminpar_tpu.graph.bucketed import MIN_ROWS, _merge_plan
    from kaminpar_tpu.refinement.balancer import _balance_round
    from kaminpar_tpu.utils import compile_stats

    programs = ("lp_iterate_bucketed", "cluster_two_hop_nodes_bucketed",
                "_balance_round")

    def backend_events():
        got = dict.fromkeys(programs, 0)
        for row in compile_stats.compile_ledger_snapshot():
            if row["program"] in got:
                got[row["program"]] += row["compiles"] + row["loads"]
        return got

    def run(g):
        g._layout_mode = "device"
        pv, bv = g.padded(), g.bucketed()
        idt = pv.row_ptr.dtype
        labels = jnp.where(jnp.arange(pv.n_pad) < pv.n,
                           jnp.arange(pv.n_pad, dtype=idt), pv.anchor)
        max_w = jnp.asarray(12, dtype=idt)
        args = (bv.buckets, bv.heavy, bv.gather_idx, pv.node_w)
        state = lp.lp_iterate_bucketed(
            lp.init_state(labels, pv.node_w, pv.n_pad), next_key(), *args,
            max_w, jnp.int32(0), jnp.int32(5), num_labels=pv.n_pad,
        )
        state = lp.cluster_two_hop_nodes_bucketed(
            state, next_key(), *args, max_w, num_labels=pv.n_pad,
        )
        part = jnp.where(jnp.arange(pv.n_pad) < pv.n // 3, 0, 1).astype(idt)
        new_part, _ = _balance_round(
            next_key(), part, *args, jnp.full(2, pv.n // 2 + 1, dtype=idt), k=2,
        )
        jax.block_until_ready((state.labels, new_part))
        return pv, _merge_plan(g.deg_histogram(), MIN_ROWS)[0]

    graphs = []
    for n in (1000, 1010):
        rng = np.random.default_rng(1)
        graphs.append(generators.from_edge_list(n, rng.integers(0, n, (4000, 2))))
    pv_a, plan_a = run(graphs[0])
    before = backend_events()
    pv_b, plan_b = run(graphs[1])
    # The premise: one rung, one bucket plan, two node counts.
    assert (pv_a.n_pad, pv_a.m_pad) == (pv_b.n_pad, pv_b.m_pad)
    assert [(w, rp) for w, _, rp in plan_a] == [(w, rp) for w, _, rp in plan_b]
    assert pv_a.n != pv_b.n
    assert backend_events() == before


_GOLDEN_GRAPHS = {
    "rmat": lambda: generators.rmat_graph(10, 8, seed=3),
    "grid": lambda: generators.grid2d_graph(24, 24),
}
# (cut, first 16 hex digits of the SHA-256 of the int32 partition), recorded
# when gather_idx still had the real node count as its length.
_GOLDEN_PARTITIONS = {
    ("rmat", 2): (438, "efdb406b2e9a4a11"),
    ("rmat", 4): (1829, "52e8fef7e1e9138b"),
    ("grid", 2): (28, "9eb6b2cb3bf923c1"),
    ("grid", 4): (52, "12d522c5130e5c01"),
}


@pytest.mark.parametrize("layout", ["host", "device"])
@pytest.mark.parametrize("gname,k", sorted(_GOLDEN_PARTITIONS))
def test_partition_matches_recorded(gname, k, layout):
    """The facade's partitions and cuts on fixed seeds, with either layout
    builder: how the layout marks pad nodes must not move a result."""
    import hashlib

    from kaminpar_tpu.graph import metrics
    from kaminpar_tpu.kaminpar import KaMinPar

    g = _GOLDEN_GRAPHS[gname]()
    solver = KaMinPar("default")
    solver.ctx.seed = 7
    solver.ctx.parallel.device_layout_build = layout
    solver.set_graph(g)
    part = np.asarray(solver.compute_partition(k=k))
    digest = hashlib.sha256(part.astype(np.int32).tobytes()).hexdigest()[:16]
    assert (int(metrics.edge_cut(g, part)), digest) == _GOLDEN_PARTITIONS[(gname, k)]
