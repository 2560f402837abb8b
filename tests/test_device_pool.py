"""Lane-vmapped device initial-bipartitioning pool (round 9, ISSUE 4).

Covers the acceptance criteria: seed-stable determinism on both backends,
lane-stream identity under vmap/scan/loop execution, host-pool oracle
parity (device best cut <= host-pool median over a seed sweep on
rmat/grid/star), the one-readback-per-bisection budget in-pipeline, and the
contraction-level edge cases (n <= 2, all-lanes-infeasible fallback).
"""

import dataclasses
from functools import partial

import jax
import numpy as np
import pytest

from kaminpar_tpu.context import Context, InitialPartitioningContext
from kaminpar_tpu.graph import generators
from kaminpar_tpu.initial.bipartitioner import (
    _block_weights,
    _cut,
    multilevel_bipartition,
    pool_bipartition,
    resolve_ip_backend,
)
from kaminpar_tpu.ops import bipartition as bip
from kaminpar_tpu.partitioning.kway import graph_to_host
from kaminpar_tpu.utils import sync_stats
from kaminpar_tpu.utils.rng import lane_key, lane_keys

IPC = InitialPartitioningContext()
DEVICE_IPC = dataclasses.replace(IPC, ip_backend="device")


def _budgets(host, frac=0.55):
    W = host.total_node_weight
    return np.array([int(frac * W), int(frac * W)], dtype=np.int64)


def _device_pool(host, mw, seed, final_k=2, ipc=IPC):
    return bip.pool_bipartition_device(
        host.row_ptr, host.col_idx, host.node_w, host.edge_w, mw, seed, ipc,
        final_k,
    )


def test_resolve_ip_backend_modes(monkeypatch):
    assert resolve_ip_backend(DEVICE_IPC) == "device"
    assert resolve_ip_backend(dataclasses.replace(IPC, ip_backend="host")) == "host"
    # "auto" on the CPU test backend = host.
    assert resolve_ip_backend(IPC) == "host"
    with pytest.raises(ValueError):
        resolve_ip_backend(dataclasses.replace(IPC, ip_backend="gpu"))
    # The env kill switch overrides the context knob (including bad ones).
    monkeypatch.setenv("KAMINPAR_TPU_IP_BACKEND", "device")
    assert resolve_ip_backend(IPC) == "device"
    assert resolve_ip_backend(dataclasses.replace(IPC, ip_backend="gpu")) == "device"


def test_device_pool_deterministic_and_feasible():
    host = graph_to_host(generators.grid2d_graph(12, 12))
    mw = _budgets(host)
    l1, s1 = _device_pool(host, mw, seed=5)
    l2, s2 = _device_pool(host, mw, seed=5)
    np.testing.assert_array_equal(l1, l2)
    assert s1 == s2
    assert s1["feasible"]
    bw = _block_weights(host, l1)
    assert (bw <= mw).all()
    assert s1["cut"] == _cut(host, l1)
    assert tuple(bw) == s1["block_weights"]
    # a different seed draws different lane streams
    l3, _ = _device_pool(host, mw, seed=6)
    assert not np.array_equal(l1, l3)


@pytest.mark.parametrize("make", [
    lambda: generators.rmat_graph(7, 8, seed=2),
    lambda: generators.star_graph(40),
], ids=["rmat", "star"])
def test_connections_equal_segment_sums(make):
    """The pool's prefix-sum row sums equal scatter-add segment sums over
    the padded CSR (pad rows and the anchor's pad edges included), and the
    cut the FM loop scores from them equals the edge-wise cut."""
    import jax.numpy as jnp

    from kaminpar_tpu.graph.csr import from_numpy_csr

    host = graph_to_host(make())
    pv = from_numpy_csr(
        host.row_ptr, host.col_idx, host.node_w, host.edge_w
    ).padded()
    rng = np.random.default_rng(0)
    in0 = jnp.asarray(rng.random(pv.n_pad) < 0.5)
    conn0, conn1 = bip._connections(
        in0, pv.edge_u, pv.col_idx, pv.edge_w, pv.n_pad
    )
    to0 = jax.ops.segment_sum(
        jnp.where(in0[pv.col_idx], pv.edge_w, 0), pv.edge_u,
        num_segments=pv.n_pad,
    )
    degw = jax.ops.segment_sum(pv.edge_w, pv.edge_u, num_segments=pv.n_pad)
    np.testing.assert_array_equal(np.asarray(conn0), np.asarray(to0))
    np.testing.assert_array_equal(np.asarray(conn1), np.asarray(degw - to0))
    both_ends = jnp.sum(
        jnp.where(in0[pv.edge_u] != in0[pv.col_idx], pv.edge_w, 0)
    )
    assert int(jnp.sum(jnp.where(in0, conn1, conn0))) == int(both_ends)


def test_lane_results_vmap_scan_loop_identical():
    """The single-lane kernel produces bit-identical partitions whether the
    lane stack executes as vmap, scan, or a Python loop — the ROADMAP's
    lane-stacking identity, on the real kernel rather than raw draws."""
    from kaminpar_tpu.graph.csr import from_numpy_csr

    host = graph_to_host(generators.rmat_graph(6, 8, seed=3))
    g = from_numpy_csr(host.row_ptr, host.col_idx, host.node_w, host.edge_w)
    pv = g.padded()
    idt = pv.node_w.dtype
    W = int(np.asarray(host.node_w).sum())
    lane = jax.jit(partial(
        bip._lane_bipartition,
        edge_u=pv.edge_u, col_idx=pv.col_idx, edge_w=pv.edge_w,
        node_w=pv.node_w, n=jax.numpy.asarray(pv.n, dtype=idt),
        target=jax.numpy.asarray(W // 2, dtype=idt),
        max_w0=jax.numpy.asarray(int(0.55 * W), dtype=idt),
        max_w1=jax.numpy.asarray(int(0.55 * W), dtype=idt),
        method="ggg", grow_trips=16, fm_rounds=8,
    ))
    R = 4
    keys = lane_keys(11, R)
    via_vmap = np.asarray(jax.vmap(lane)(keys))
    _, via_scan = jax.lax.scan(lambda c, k: (c, lane(k)), None, keys)
    via_loop = np.stack([np.asarray(lane(lane_key(11, i))) for i in range(R)])
    np.testing.assert_array_equal(via_vmap, np.asarray(via_scan))
    np.testing.assert_array_equal(via_vmap, via_loop)
    # lane-count invariance on the kernel: the first R lanes of a bigger
    # stack are the same partitions
    bigger = np.asarray(jax.vmap(lane)(lane_keys(11, 2 * R)))
    np.testing.assert_array_equal(via_vmap, bigger[:R])


@pytest.mark.parametrize("make", [
    lambda: generators.rmat_graph(7, 8, seed=1),
    lambda: generators.grid2d_graph(12, 12),
    lambda: generators.star_graph(48),
], ids=["rmat", "grid", "star"])
def test_device_pool_beats_host_pool_median(make):
    """Oracle parity (acceptance): device-pool best cut <= host-pool median
    cut over a 10-seed sweep."""
    host = graph_to_host(make())
    mw = _budgets(host)
    host_cuts = sorted(
        _cut(host, pool_bipartition(host, mw, np.random.default_rng(s), IPC))
        for s in range(10)
    )
    dev_best = min(
        s["cut"] for s in
        (_device_pool(host, mw, seed=s)[1] for s in range(10))
    )
    assert dev_best <= host_cuts[5], (dev_best, host_cuts)


def test_multilevel_bipartition_device_backend_routes_and_falls_back():
    host = graph_to_host(generators.grid2d_graph(8, 8))
    mw = _budgets(host)
    part = multilevel_bipartition(
        host, mw, np.random.default_rng(0), DEVICE_IPC
    )
    assert set(np.unique(part)) <= {0, 1}
    assert (_block_weights(host, part) <= mw).all()
    # n <= 2 contraction-level edge case: falls through to the host pool
    # (no device dispatch), stays deterministic and feasible.
    for n in (1, 2):
        tiny = graph_to_host(generators.path_graph(n))
        mw2 = np.array([1, 1], dtype=np.int64)
        p1 = multilevel_bipartition(tiny, mw2, np.random.default_rng(0), DEVICE_IPC)
        p2 = multilevel_bipartition(tiny, mw2, np.random.default_rng(0), DEVICE_IPC)
        np.testing.assert_array_equal(p1, p2)
        assert (_block_weights(tiny, p1) <= mw2).all()


def test_method_lane_keys_stable_across_bucket_growth():
    """Each method keys its lanes from a disjoint counter window: growing
    the shared lane bucket (more repetitions) must not shift any existing
    lane's stream in any method."""
    small = jax.random.key_data(
        bip.method_lane_keys(5, (("bfs", 4), ("ggg", 4), ("random", 4)))
    )
    big = jax.random.key_data(
        bip.method_lane_keys(5, (("bfs", 8), ("ggg", 8), ("random", 8)))
    )
    small_np, big_np = np.asarray(small), np.asarray(big)
    for m in range(3):
        np.testing.assert_array_equal(
            small_np[m * 4 : (m + 1) * 4], big_np[m * 8 : m * 8 + 4]
        )


def test_rebalance_skips_unmovable_heavy_node():
    """A max-gain candidate heavier than the receiver's room must not block
    lighter candidates behind it from repairing the overload (the host
    pool's queues skip unmovable nodes and continue)."""
    import jax.numpy as jnp

    from kaminpar_tpu.graph.csr import from_numpy_csr

    # Path 0-1-2 with node 0 heavy; block 0 = {0, 1} is overweight by 1 and
    # only moving node 1 (not the heavy node 0) can repair it.
    row_ptr = np.array([0, 1, 3, 4], dtype=np.int64)
    col = np.array([1, 0, 2, 1], dtype=np.int64)
    nw = np.array([100, 1, 1], dtype=np.int64)
    g = from_numpy_csr(row_ptr, col, nw, np.ones(4, dtype=np.int64))
    pv = g.padded()
    idt = pv.node_w.dtype
    in0 = jnp.zeros(pv.n_pad, dtype=bool).at[0].set(True).at[1].set(True)
    out = bip._rebalance_side(
        lane_key(0, 0), in0, pv.edge_u, pv.col_idx, pv.edge_w, pv.node_w,
        jnp.asarray(100, dtype=idt), jnp.asarray(50, dtype=idt), side=0,
    )
    out = np.asarray(out)
    assert out[0] and not out[1]  # heavy node stayed, light node moved
    bw0 = int(np.sum(np.where(out[: 3], nw, 0)))
    assert bw0 == 100  # overload repaired


def test_device_pool_tight_budgets_rebalance():
    """Near-perfect balance budgets: grown lanes overshoot and the forced
    balance pass must repair them — every lane, not just the winner."""
    host = graph_to_host(generators.grid2d_graph(8, 8))
    W = host.total_node_weight
    mw = np.array([W // 2 + 1, W // 2 + 1], dtype=np.int64)
    labels, stats = _device_pool(host, mw, seed=0)
    assert stats["feasible"]
    assert (_block_weights(host, labels) <= mw).all()
    assert stats["num_feasible"] == stats["lanes"]


def test_device_pool_all_lanes_infeasible_fallback():
    """Budgets no bipartition can satisfy: the pool still returns a valid
    labeling and reports infeasibility (minimum-overload lane) instead of
    crashing — the caller's refinement/balancing layers take it from there."""
    host = graph_to_host(generators.star_graph(16))
    W = host.total_node_weight
    mw = np.array([W // 3, W // 3], dtype=np.int64)  # sum < W: unsatisfiable
    labels, stats = _device_pool(host, mw, seed=1)
    assert not stats["feasible"]
    assert stats["num_feasible"] == 0
    assert set(np.unique(labels)) <= {0, 1}
    assert len(labels) == host.n


def test_device_pool_rejects_unsafe_weights():
    host = graph_to_host(generators.path_graph(4))
    big = host._replace(node_w=np.full(4, 2**30, dtype=np.int64))
    with pytest.raises(ValueError):
        _device_pool(big, np.array([2**33, 2**33], dtype=np.int64), seed=0)


def test_too_wide_bisection_counts_host_fallback():
    """Weights past int32 resolve to the device pool but run on the host
    pool; the pool census counts each such bisection."""
    host = graph_to_host(generators.path_graph(6))
    big = host._replace(node_w=np.full(6, 2**30, dtype=np.int64))
    mw = np.array([2**32, 2**32], dtype=np.int64)
    bip.reset_pool_stats()
    labels = multilevel_bipartition(big, mw, np.random.default_rng(0), DEVICE_IPC)
    snap = bip.pool_stats_snapshot()
    assert snap["fallbacks"] == 1 and snap["calls"] == 0
    assert set(np.unique(labels)) <= {0, 1} and len(labels) == 6
    multilevel_bipartition(host, _budgets(host), np.random.default_rng(0), DEVICE_IPC)
    snap = bip.pool_stats_snapshot()
    assert snap["fallbacks"] == 1 and snap["calls"] == 1


def test_deep_pipeline_device_backend_deterministic_and_budgeted():
    """End-to-end acceptance: ip_backend=device through the deep pipeline is
    seed-deterministic, feasible, and holds the <= 1-readback-per-bisection
    budget (asserted in-pipeline via enable_budget_checks)."""
    from kaminpar_tpu.graph.metrics import edge_cut, is_feasible
    from kaminpar_tpu.kaminpar import KaMinPar

    g = generators.grid2d_graph(24, 24)
    sync_stats.enable_budget_checks(True)
    try:
        parts = []
        for _ in range(2):
            ctx = Context()
            ctx.initial_partitioning.ip_backend = "device"
            solver = KaMinPar(ctx=ctx)
            solver.set_graph(g)
            parts.append(solver.compute_partition(4, 0.03))
        caps = ctx.partition.max_block_weights
    finally:
        sync_stats.enable_budget_checks(False)
    np.testing.assert_array_equal(parts[0], parts[1])
    assert bool(is_feasible(g, parts[0], 4, caps))
    assert int(edge_cut(g, parts[0])) > 0


def test_engine_warmup_reports_ip_pool_cells():
    """PartitionEngine warmup precompiles the pool per (bucket, lane-count,
    k=2) cell on the device backend and reports each cell's compile cost."""
    from kaminpar_tpu.serve.engine import PartitionEngine

    ctx = Context()
    ctx.initial_partitioning.ip_backend = "device"
    engine = PartitionEngine(ctx, warm_ladder=(64,), warm_ks=(4,))
    # warmup's pool pass, without the full ladder; the rung generator
    # mirrors _warmup's (scale 6 for the 64-rung, same edge factor/seed).
    from kaminpar_tpu.graph.generators import rmat_graph

    engine._warm_ip_pool(lambda n: (6, rmat_graph(
        6, edge_factor=engine.serve.warm_edge_factor, seed=1)))
    rows = [r for r in engine.warmup_report if r.get("kind") == "ip_pool"]
    assert rows, engine.warmup_report
    for row in rows:
        assert row["k"] == 2
        assert row["lanes"] > 0
        assert row["wall_s"] >= 0
        assert row["n_bucket"] > 64
    # host backend: nothing to compile, no rows
    ctx2 = Context()
    ctx2.initial_partitioning.ip_backend = "host"
    engine2 = PartitionEngine(ctx2, warm_ladder=(64,), warm_ks=(4,))
    engine2._warm_ip_pool(lambda n: (6, rmat_graph(
        6, edge_factor=engine2.serve.warm_edge_factor, seed=1)))
    assert not [r for r in engine2.warmup_report if r.get("kind") == "ip_pool"]
