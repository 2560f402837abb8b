"""AOT cross-platform lowering of the kernel set (TPU readiness without TPUs).

Kernels must not meet the TPU lowering path for the first time on silicon.
This module
pushes every jitted kernel — shm and the shard_map distributed rounds — through
``jax.export`` with ``platforms=("tpu",)``, which runs the *platform-specific
StableHLO lowering rules* (catching unsupported primitives, int64 lowerings,
degenerate shapes, while-loop/collective issues) without needing a TPU backend.
What it cannot catch is Mosaic/XLA-TPU *compile*-time failures; those are
caught by compiling for a described chip (tests/test_tpu_compile.py).

Reference counterpart: none — the reference compiles ahead of time by
construction (C++); this is the JAX equivalent of "it builds for the target".

Usage::

    from kaminpar_tpu.utils.aot import export_kernel_suite
    sizes = export_kernel_suite(platforms=("tpu",))   # raises on any failure

Exported per kernel: serialized StableHLO bytes (sizes returned for logging).
``tests/test_tpu_lowering.py`` runs this in CI (VERDICT r3 next-steps #2).
"""

from __future__ import annotations

from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import export as jax_export


class AotExportError(RuntimeError):
    """One or more kernels failed to lower for the target platform."""

    def __init__(self, failures: Dict[str, str]):
        self.failures = failures
        lines = "\n".join(f"  {k}: {v}" for k, v in failures.items())
        super().__init__(f"{len(failures)} kernel(s) failed to lower:\n{lines}")


def _export_one(results, failures, name, fn, *args, platforms, **kwargs):
    try:
        exp = jax_export.export(fn, platforms=list(platforms))(*args, **kwargs)
        results[name] = len(exp.mlir_module_serialized)
    except Exception as e:  # noqa: BLE001 — collect every failure, then raise
        failures[name] = f"{type(e).__name__}: {e}"
        return
    # Executable census at the AOT site (ISSUE 12): when armed, lower (and
    # compile for the AMBIENT backend — TPU-targeted compiles need the
    # chip) and harvest XLA's cost/memory analyses under the kernel's name.
    # Guarded by armed-ness so the tier-1 lowering suite pays nothing.
    from . import compile_stats

    if compile_stats.executable_census_armed():
        # Cell label: the largest flat operand shapes (the n_pad/m_pad
        # carriers) — pytrees/scalars among args carry no useful label.
        dims = sorted(
            {int(a.shape[0]) for a in args
             if hasattr(a, "shape") and getattr(a, "ndim", 0) == 1},
            reverse=True,
        )
        compile_stats.harvest_fn(
            f"aot_{name}", fn, *args, cell=tuple(dims[:2]), **kwargs
        )


def _shm_suite(results, failures, platforms, *, use_64bit: bool = False):
    from ..coarsening.hem_clusterer import _hem_round
    from ..coarsening.lp_clusterer import _intersect_clusterings
    from ..graph import generators
    from ..graph.bucketed import build_bucketed_view
    from ..graph.metrics import _block_weights, _edge_cut
    from ..ops import lp
    from ..ops.coloring import color_graph
    from ..ops.contraction import _contract_device, project_partition
    from ..refinement.balancer import _balance_round, _underload_round
    from ..refinement.jet import _jet_move_round

    sfx = "_x64" if use_64bit else ""
    g = generators.rmat_graph(8, 8, seed=3, use_64bit=use_64bit)
    pv = g.padded()
    bv = g.bucketed()
    k = 8
    idt = pv.row_ptr.dtype
    key = jax.random.key(0)
    n_pad = pv.n_pad

    labels = jnp.concatenate(
        [jnp.arange(pv.n, dtype=idt), jnp.full(n_pad - pv.n, pv.anchor, dtype=idt)]
    )
    state = lp.init_state(labels, pv.node_w, n_pad)
    max_w = jnp.asarray(1 << 20, dtype=idt)

    _export_one(
        results, failures, f"lp_init_state{sfx}", lp.init_state,
        labels, pv.node_w, num_labels=n_pad, platforms=platforms,
    )
    _export_one(
        results, failures, f"lp_round_flat{sfx}", lp.lp_round,
        state, key, pv.edge_u, pv.col_idx, pv.edge_w, pv.node_w, max_w,
        num_labels=n_pad, platforms=platforms,
    )
    _export_one(
        results, failures, f"lp_round_bucketed{sfx}", lp.lp_round_bucketed,
        state, key, bv.buckets, bv.heavy, bv.gather_idx, pv.node_w, max_w,
        num_labels=n_pad, platforms=platforms,
    )
    # Fused multi-round while-loop — the clustering hot path.
    _export_one(
        results, failures, f"lp_iterate_bucketed{sfx}", lp.lp_iterate_bucketed,
        state, key, bv.buckets, bv.heavy, bv.gather_idx, pv.node_w, max_w,
        jnp.int32(1), jnp.int32(5), num_labels=n_pad, active_prob=0.5,
        platforms=platforms,
    )
    # Non-empty heavy part (degree > max_width): the flat two-phase analog.
    bv_heavy = build_bucketed_view(
        np.asarray(g.row_ptr), np.asarray(g.col_idx), np.asarray(g.edge_w),
        g.n, pv.anchor, max_width=16,
    )
    _export_one(
        results, failures, f"lp_round_bucketed_heavy{sfx}", lp.lp_round_bucketed,
        state, key, bv_heavy.buckets, bv_heavy.heavy, bv_heavy.gather_idx,
        pv.node_w, max_w, num_labels=n_pad, platforms=platforms,
    )
    _export_one(
        results, failures, f"lp_cluster_isolated{sfx}", lp.cluster_isolated_nodes,
        state, pv.row_ptr, pv.node_w, max_w, num_labels=n_pad,
        platforms=platforms,
    )
    _export_one(
        results, failures, f"lp_two_hop_bucketed{sfx}",
        lp.cluster_two_hop_nodes_bucketed,
        state, key, bv.buckets, bv.heavy, bv.gather_idx, pv.node_w, max_w,
        num_labels=n_pad, platforms=platforms,
    )
    _export_one(
        results, failures, f"intersect_clusterings{sfx}", _intersect_clusterings,
        labels, labels, platforms=platforms,
    )
    _export_one(
        results, failures, f"contraction{sfx}", _contract_device,
        labels, pv.edge_u, pv.col_idx, pv.edge_w, pv.node_w,
        platforms=platforms,
    )
    _export_one(
        results, failures, f"project_partition{sfx}", project_partition,
        jnp.zeros(g.n, dtype=idt), jnp.zeros(64, dtype=jnp.int32),
        platforms=platforms,
    )

    part = jnp.zeros(n_pad, dtype=jnp.int32)
    max_bw = jnp.full((k,), 1 << 20, dtype=pv.node_w.dtype)
    min_bw = jnp.zeros((k,), dtype=pv.node_w.dtype)
    locked = jnp.zeros(n_pad, dtype=bool)
    _export_one(
        results, failures, f"jet_move_round{sfx}", _jet_move_round,
        key, part, locked, bv.buckets, bv.heavy, bv.gather_idx, pv.node_w,
        max_bw, jnp.float32(0.25), k=k, platforms=platforms,
    )
    _export_one(
        results, failures, f"balance_round{sfx}", _balance_round,
        key, part, bv.buckets, bv.heavy, bv.gather_idx, pv.node_w, max_bw,
        k=k, platforms=platforms,
    )
    _export_one(
        results, failures, f"underload_round{sfx}", _underload_round,
        key, part, bv.buckets, bv.heavy, bv.gather_idx, pv.node_w, max_bw,
        min_bw, k=k, platforms=platforms,
    )
    _export_one(
        results, failures, f"color_graph{sfx}", color_graph,
        key, pv.edge_u, pv.col_idx, pv.node_w > 0, n=n_pad,
        platforms=platforms,
    )
    match0 = jnp.arange(n_pad, dtype=idt)
    _export_one(
        results, failures, f"hem_round{sfx}", _hem_round,
        key, match0, pv.edge_u, pv.col_idx, pv.edge_w, pv.node_w, max_w,
        n_pad=n_pad, platforms=platforms,
    )
    _export_one(
        results, failures, f"metrics_block_weights{sfx}", _block_weights,
        part, pv.node_w, k=k, platforms=platforms,
    )
    _export_one(
        results, failures, f"metrics_edge_cut{sfx}", _edge_cut,
        pv.edge_u, pv.col_idx, pv.edge_w, part, platforms=platforms,
    )


def _initial_suite(results, failures, platforms, *, use_64bit: bool = False):
    """The lane-vmapped initial-bipartitioning pool (ISSUE 4): engine warmup
    / the first on-silicon bisection must not be where the vmapped
    grow/rebalance/FM stack meets the TPU lowering rules."""
    from ..context import InitialPartitioningContext
    from ..graph import generators
    from ..ops.bipartition import (
        _pool_kernel,
        fm_round_count,
        grow_trip_count,
        method_lane_counts,
    )
    from ..utils.rng import lane_keys

    sfx = "_x64" if use_64bit else ""
    g = generators.rmat_graph(7, 8, seed=2, use_64bit=use_64bit)
    pv = g.padded()
    idt = pv.node_w.dtype
    ipc = InitialPartitioningContext()
    methods, _ = method_lane_counts(ipc, final_k=8)
    keys = lane_keys(0, sum(cnt for _, cnt in methods))
    _export_one(
        results, failures, f"ip_pool{sfx}", _pool_kernel,
        keys, pv.edge_u, pv.col_idx, pv.edge_w, pv.node_w,
        jnp.asarray(pv.n, dtype=idt), jnp.asarray(64, dtype=idt),
        jnp.asarray(80, dtype=idt), jnp.asarray(80, dtype=idt),
        methods=methods, grow_trips=grow_trip_count(pv.n_pad),
        fm_rounds=fm_round_count(pv.n_pad, ipc.fm_num_iterations),
        platforms=platforms,
    )


def _compressed_suite(results, failures, platforms):
    """The decode-fused compressed-graph kernels (ISSUE 10): the XLA twins
    of the device-decode tier — the compressed LP sweep loop, the two-hop
    pass, the flat decode, and contraction-off-the-stream — must lower for
    TPU before the terapart pipeline meets silicon.  Covers both the
    weighted (rmat carries dedup-summed weights) and uniform edge-stream
    trace switches."""
    from ..graph import generators
    from ..graph.compressed import compress
    from ..graph.device_compressed import (
        DeviceCompressedView,
        _decode_flat_padded_jit,
    )
    from ..ops import lp
    from ..ops.contraction import _contract_compressed_device

    key = jax.random.key(0)
    for tag, g in (
        ("", generators.rmat_graph(8, 8, seed=3)),      # weighted stream
        ("_uniform", generators.grid2d_graph(16, 16)),  # all-1 dummy stream
    ):
        cv = DeviceCompressedView(compress(g))
        n_pad = cv.n_pad
        idt = cv.node_w_pad.dtype
        labels = jnp.concatenate(
            [
                jnp.arange(cv.n, dtype=idt),
                jnp.full(n_pad - cv.n, cv.anchor, dtype=idt),
            ]
        )
        state = lp.init_state(labels, cv.node_w_pad, n_pad)
        max_w = jnp.asarray(1 << 20, dtype=idt)
        _export_one(
            results, failures, f"lp_iterate_compressed{tag}",
            lp.lp_iterate_compressed,
            state, key, cv.buckets, cv.stream, cv.heavy, cv.gather_idx,
            cv.node_w_pad, max_w, jnp.int32(1), jnp.int32(5),
            num_labels=n_pad, active_prob=0.5, platforms=platforms,
        )
        if tag:
            continue  # the remaining cells only switch on the stream shape
        _export_one(
            results, failures, "lp_two_hop_compressed",
            lp.cluster_two_hop_nodes_compressed,
            state, key, cv.buckets, cv.stream, cv.heavy, cv.gather_idx,
            cv.node_w_pad, max_w, num_labels=n_pad, platforms=platforms,
        )
        _export_one(
            results, failures, "decode_flat_padded", _decode_flat_padded_jit,
            cv.stream, cv.wstart_pad, cv.width_pad, cv.degree_pad,
            m_pad=cv.m_pad, platforms=platforms,
        )
        _export_one(
            results, failures, "contract_compressed",
            _contract_compressed_device,
            labels, cv.stream, cv.wstart_pad, cv.width_pad, cv.degree_pad,
            cv.node_w_pad, m_pad=cv.m_pad, platforms=platforms,
        )


def _serve_suite(results, failures, platforms):
    """The serving runtime's batch kernels (serve/batching.py): packed
    disjoint-union metrics over two graphs in one cell.  Warmup on silicon
    must not be the first place these meet the TPU lowering rules."""
    from ..graph import generators
    from ..serve.batching import _packed_metrics, pack_graphs

    graphs = [generators.rmat_graph(6, 4, seed=s) for s in (1, 2)]
    packed = pack_graphs(graphs)
    pv = packed.union.padded()
    b, k = packed.num_graphs, 8
    labels = jnp.zeros(pv.n_pad, dtype=jnp.int32)
    egid = jnp.zeros(pv.m_pad, dtype=jnp.int32)
    egid = egid.at[: pv.m].set(jnp.asarray(packed.edge_gid))
    ngid = jnp.zeros(pv.n_pad, dtype=jnp.int32)
    ngid = ngid.at[: pv.n].set(jnp.asarray(packed.node_gid))
    _export_one(
        results, failures, "serve_packed_metrics", _packed_metrics,
        pv.edge_u, pv.col_idx, pv.edge_w, labels, egid, pv.node_w, ngid,
        num_graphs=b, k=k, platforms=platforms,
    )


def _dist_suite(results, failures, platforms, mesh):
    from ..dist import distribute_graph
    from ..dist.balancer import (
        make_dist_balance_round,
        make_dist_cluster_balance_round,
    )
    from ..dist.contraction import _s1, _s4, next_pow2
    from ..dist.jet import make_dist_jet_round
    from ..dist.lp import (
        make_dist_clp_round,
        make_dist_cluster_round,
        make_dist_coloring,
        make_dist_lp_round,
        make_dist_lp_round_best,
    )
    from ..graph import generators

    P = mesh.size
    g = generators.grid2d_graph(16, 16)
    dg = distribute_graph(g, P)
    k = 8
    key = jax.random.key(0)
    labels = jnp.zeros(dg.N, jnp.int32)
    max_w = jnp.full((k,), 1 << 20, jnp.int32)
    common = (dg.node_w, dg.edge_u, dg.col_loc, dg.edge_w)
    routing = (dg.send_idx, dg.recv_map)

    _export_one(
        results, failures, "dist_lp_round",
        make_dist_lp_round(mesh, num_labels=k),
        key, labels, *common, max_w, *routing, jnp.int32(0), jnp.int32(0),
        platforms=platforms,
    )
    _export_one(
        results, failures, "dist_lp_round_chunked",
        make_dist_lp_round(mesh, num_labels=k, num_chunks=8),
        key, labels, *common, max_w, *routing, jnp.int32(0), jnp.int32(0),
        platforms=platforms,
    )
    _export_one(
        results, failures, "dist_lp_round_best",
        make_dist_lp_round_best(mesh, num_labels=k),
        key, labels, *common, max_w, *routing, platforms=platforms,
    )
    cap_q = min(next_pow2(max(64, 2 * dg.n_loc // P), 8), dg.n_loc)
    clabels = jnp.arange(dg.N, dtype=jnp.int32)
    cmax_w = jnp.asarray(1 << 20, jnp.int32)
    _export_one(
        results, failures, "dist_cluster_round",
        make_dist_cluster_round(mesh, cap_q=cap_q),
        key, clabels, *common, cmax_w, *routing, platforms=platforms,
    )
    colors0 = jnp.where(jnp.arange(dg.N) < dg.n, jnp.int32(-1), jnp.int32(0))
    _export_one(
        results, failures, "dist_coloring",
        make_dist_coloring(mesh),
        colors0, dg.edge_u, dg.col_loc, dg.edge_w, *routing,
        platforms=platforms,
    )
    _export_one(
        results, failures, "dist_clp_round",
        make_dist_clp_round(mesh, num_labels=k),
        key, labels, jnp.zeros(dg.N, jnp.int32), jnp.int32(0), *common,
        max_w, *routing, platforms=platforms,
    )
    locked = jnp.zeros(dg.N, dtype=bool)
    _export_one(
        results, failures, "dist_jet_round",
        make_dist_jet_round(mesh, num_labels=k),
        key, labels, locked, *common, max_w, *routing, jnp.float32(0.25),
        platforms=platforms,
    )
    _export_one(
        results, failures, "dist_balance_round",
        make_dist_balance_round(mesh, k=k),
        key, labels, *common, max_w, *routing, platforms=platforms,
    )
    _export_one(
        results, failures, "dist_cluster_balance_round",
        make_dist_cluster_balance_round(mesh, k=k),
        key, labels, *common, max_w, *routing, platforms=platforms,
    )
    # Dist contraction stages S1 (owner aggregation) and S4 (compaction).
    # S2/S3's risky primitives (owner_query routing, dense all_to_all +
    # multi-operand lax.sort) are covered by dist_cluster_round above.
    _export_one(
        results, failures, "dist_contract_s1", _s1,
        mesh, clabels, dg.node_w, n_loc=dg.n_loc, cap_q=cap_q,
        platforms=platforms,
    )
    m_loc_c = max(dg.m_loc // 2, 1)
    _export_one(
        results, failures, "dist_contract_s4", _s4,
        mesh, dg.edge_u, dg.col_loc, dg.edge_w, m_loc_c=m_loc_c,
        platforms=platforms,
    )


def suite_total_bytes(sizes: Dict[str, int]) -> int:
    """Cumulative serialized StableHLO size of an exported suite — the
    number the serve-warmup artifact budget tracks (a sudden jump means a
    kernel family forked a new specialization)."""
    return sum(sizes.values())


def export_kernel_suite(
    platforms: Iterable[str] = ("tpu",),
    *,
    include_dist: bool = True,
    include_x64: bool = True,
    include_serve: bool = True,
    include_initial: bool = True,
    include_compressed: bool = True,
    mesh=None,
) -> Dict[str, int]:
    """Export every kernel for the target platform(s); returns name -> bytes
    (cumulative size via :func:`suite_total_bytes`).

    Raises :class:`AotExportError` listing every kernel that failed to lower.
    ``mesh`` defaults to an 8-device mesh over the available devices (tests
    force 8 CPU devices; the mesh's platform does not constrain the export
    target — lowering is cross-platform).
    """
    results: Dict[str, int] = {}
    failures: Dict[str, str] = {}
    platforms = tuple(platforms)

    _shm_suite(results, failures, platforms)
    if include_compressed:
        # Decode-fused compressed kernels (ISSUE 10): the terapart device
        # tier must not meet the TPU lowering rules for the first time
        # mid-pipeline on the chip.
        _compressed_suite(results, failures, platforms)
    if include_serve:
        # Serve batch kernels (ISSUE 3 satellite): a lowering failure here
        # is caught off-silicon instead of mid-warmup on the chip.
        _serve_suite(results, failures, platforms)
    if include_initial:
        # The vmapped bipartitioning pool (ISSUE 4): warmed per cell by the
        # serve engine, so it must lower before it meets the chip.
        _initial_suite(results, failures, platforms)
    if include_x64:
        # The 64-bit mode (reference: KAMINPAR_64BIT_* switches) changes every
        # sort/segment dtype — int64 lowerings are a classic TPU divergence.
        with jax.enable_x64(True):
            _shm_suite(results, failures, platforms, use_64bit=True)
            if include_initial:
                _initial_suite(results, failures, platforms, use_64bit=True)
    if include_dist:
        if mesh is None:
            from jax.sharding import Mesh

            devs = jax.devices()
            if len(devs) >= 8:
                mesh = Mesh(np.array(devs[:8]), ("nodes",))
        if mesh is not None:
            _dist_suite(results, failures, platforms, mesh)
        else:
            failures["dist_suite"] = "need >= 8 devices for the dist mesh"

    if failures:
        raise AotExportError(failures)
    return results
