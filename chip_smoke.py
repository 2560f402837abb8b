#!/usr/bin/env python
"""Chip smoke test: the deep-multilevel partitioner end to end on a TPU.

Default mode (one chip), on a Graph500 RMAT graph (``--scale`` 18, edge
factor 16, seed 1; n = 262,144, about 7.3 M directed edges) with k = 16 and
epsilon = 0.03:

1. cold run through the facade, ``kp.KaMinPar("default").compute_partition``;
2. the same graph written as METIS and partitioned again through the CLI,
   in-process (``kaminpar_tpu.cli.main``) — the warm run;
3. both partitions checked: feasible (``metrics.is_feasible``), all k blocks
   non-empty, and the cut recomputed in NumPy from the host CSR equal to the
   cut the run reported on its ``RESULT`` line.

The run fails when JAX's first device is not a TPU, on any circuit-breaker
demotion, when the initial partitioning did not resolve to the device pool,
when that pool never ran, or when any bisection took the host pool instead.

``--chips 4`` runs only the sharded path: ``DKaMinPar`` over a 4-device mesh
on the same graph (its sharded arrays must span 4 distinct devices), and the
one-chip facade partition as the comparison; it prints both cuts and their
ratio.

Earlier lines report what was measured; the last stdout line is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``, printed
only when every check passed.  Everything runs in this one process, which
holds the chip.

Usage::

    python chip_smoke.py               # one chip, rmat18, k=16
    python chip_smoke.py --chips 4     # sharded mesh vs one chip
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import sys
import tempfile
import time

import numpy as np

K = 16
EPSILON = 0.03
SEED = 1
EDGE_FACTOR = 16
_RESULT = re.compile(r"RESULT cut=(\d+)")


class SmokeFailure(Exception):
    """A check of the smoke failed; the message says which."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _say(line: str) -> None:
    print(line, flush=True)


def numpy_cut(row_ptr, col_idx, edge_w, part) -> int:
    """Edge cut of ``part`` from the host CSR, in plain NumPy."""
    src = np.repeat(np.arange(len(row_ptr) - 1), np.diff(row_ptr))
    cross = part[src] != part[col_idx]
    return int(edge_w[cross].astype(np.int64).sum()) // 2


def _reported_cut(log: str) -> int:
    """Cut from the last ``RESULT cut=`` line a run logged."""
    found = _RESULT.findall(log)
    _check(bool(found), "the run logged no RESULT line")
    return int(found[-1])


@contextlib.contextmanager
def _logged():
    """Route the package logger into a buffer at EXPERIMENT level (the
    RESULT line), echoing it to stderr; yields the buffer."""
    from kaminpar_tpu.utils.logger import Logger, OutputLevel

    buf = io.StringIO()
    prev = Logger.stream, Logger.level
    Logger.stream, Logger.level = buf, OutputLevel.EXPERIMENT
    try:
        yield buf
    finally:
        Logger.stream, Logger.level = prev
        sys.stderr.write(buf.getvalue())
        sys.stderr.flush()


def make_graph(scale: int):
    """(CSRGraph, host row_ptr, col_idx, edge_w, node_w) of the seeded RMAT
    graph."""
    from kaminpar_tpu.graph import generators

    g = generators.rmat_graph(scale, edge_factor=EDGE_FACTOR, seed=SEED)
    host = tuple(
        np.asarray(a) for a in (g.row_ptr, g.col_idx, g.edge_w, g.node_w)
    )
    return (g, *host)


def check_partition(name, graph, host, part, reported_cut, max_bw, k) -> dict:
    """Feasible, k non-empty blocks, NumPy cut == reported cut."""
    from kaminpar_tpu.graph import metrics

    row_ptr, col_idx, edge_w, node_w = host
    part = np.asarray(part, dtype=np.int64)
    _check(part.shape == (graph.n,), f"{name}: partition shape {part.shape}")
    _check(
        bool(np.all((part >= 0) & (part < k))), f"{name}: block id out of range"
    )
    _check(
        metrics.is_feasible(graph, part, k, max_bw), f"{name}: infeasible"
    )
    sizes = np.bincount(part, minlength=k)
    _check(bool(np.all(sizes > 0)), f"{name}: empty block among {sizes}")
    cut = numpy_cut(row_ptr, col_idx, edge_w, part)
    _check(
        cut == reported_cut,
        f"{name}: NumPy cut {cut} != reported cut {reported_cut}",
    )
    bw = np.bincount(part, weights=node_w, minlength=k)
    perfect = -(-int(node_w.sum()) // k)
    return {"cut": cut, "imbalance": float(bw.max() / perfect - 1.0)}


def facade_partition(graph, k: int = K):
    """Cold run through the facade: (partition, reported cut, wall s,
    solver)."""
    import kaminpar_tpu as kp

    solver = kp.KaMinPar("default")
    solver.set_graph(graph)
    with _logged() as log:
        t0 = time.perf_counter()
        part = solver.compute_partition(k=k, epsilon=EPSILON)
        wall = time.perf_counter() - t0
    return np.asarray(part), _reported_cut(log.getvalue()), wall, solver


def partition_phase(scale: int, k: int = K) -> dict:
    """Facade run on the seeded RMAT graph plus its checks; the record the
    smoke prints from."""
    g, *host = make_graph(scale)
    part, reported, wall, solver = facade_partition(g, k)
    rec = check_partition(
        "facade", g, host, part, reported,
        solver.ctx.partition.max_block_weights, k,
    )
    rec.update(
        graph=g, host=host, part=part, wall_s=wall, solver=solver,
        reported_cut=reported, n=g.n, m=g.m,
    )
    return rec


def cli_partition(graph, workdir: str, k: int = K):
    """Write ``graph`` as METIS and partition it through the CLI in this
    process: (partition, reported cut, write s, wall s)."""
    from kaminpar_tpu import cli
    from kaminpar_tpu import io as kio

    path = os.path.join(workdir, "graph.metis")
    out = os.path.join(workdir, "graph.part")
    t0 = time.perf_counter()
    kio.write_graph(graph, path, "metis")
    write_s = time.perf_counter() - t0
    with _logged() as log:
        t0 = time.perf_counter()
        rc = cli.main([path, str(k), "-e", str(EPSILON), "-o", out, "-E"])
        wall = time.perf_counter() - t0
    _check(rc == 0, f"cli: exit code {rc}")
    return kio.read_partition(out), _reported_cut(log.getvalue()), write_s, wall


def _device_line(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def _resilience_checks(ctx) -> dict:
    """No demotion, the device IP pool resolved and ran, and no bisection
    fell back to the host pool."""
    from kaminpar_tpu.initial.bipartitioner import resolve_ip_backend
    from kaminpar_tpu.ops.bipartition import pool_stats_snapshot
    from kaminpar_tpu.resilience.breakers import global_registry

    demotions = global_registry().demotions()
    _check(not demotions, f"demotions recorded: {demotions}")
    backend = resolve_ip_backend(ctx.initial_partitioning)
    _check(backend == "device", f"ip_backend resolved to {backend!r}")
    pool = pool_stats_snapshot()
    _check(pool["calls"] > 0, "the device IP pool never ran")
    _check(pool["fallbacks"] == 0,
           f"{pool['fallbacks']} bisections fell back to the host pool")
    return {"ip_backend": backend, "ip_pool_calls": pool["calls"],
            "ip_pool_fallbacks": pool["fallbacks"]}


def run_one_chip(scale: int, k: int = K) -> None:
    import jax

    from kaminpar_tpu.telemetry import capacity
    from kaminpar_tpu.utils import compile_stats, sync_stats

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    g, *host = make_graph(scale)
    _say(f"graph rmat scale={scale} n={g.n} m={g.m} k={k} "
         f"generation_s={time.perf_counter() - t0}")

    part, reported, cold_s, solver = facade_partition(g, k)
    cold = check_partition(
        "facade", g, host, part, reported,
        solver.ctx.partition.max_block_weights, k,
    )
    cold_compile = compile_stats.compile_time_snapshot()
    shapes = compile_stats.snapshot()
    syncs = sync_stats.snapshot()
    _say(f"facade cold: cut={cold['cut']} imbalance={cold['imbalance']} "
         f"wall_s={cold_s}")
    _say(f"facade compile: backend_compile_s="
         f"{cold_compile['backend_compile_s']} compile_events="
         f"{cold_compile['compile_events']} compiled_shape_count="
         f"{shapes['total']} {json.dumps(shapes)}")
    _say(f"facade blocking syncs: count={syncs['count']} "
         f"bytes={syncs['bytes']}")

    with tempfile.TemporaryDirectory() as workdir:
        part_w, reported_w, write_s, warm_s = cli_partition(g, workdir, k)
    warm = check_partition(
        "cli", g, host, part_w, reported_w,
        solver.ctx.partition.max_block_weights, k,
    )
    warm_compile = compile_stats.compile_time_snapshot()
    _say(f"cli warm: cut={warm['cut']} imbalance={warm['imbalance']} "
         f"wall_s={warm_s} metis_write_s={write_s} "
         f"compile_events_added="
         f"{warm_compile['compile_events'] - cold_compile['compile_events']}"
         f" same_partition={bool(np.array_equal(part, part_w))}")

    stats = dev.memory_stats() or {}
    pred = capacity.predict_for_graph(g, k, device_kind=dev.device_kind)
    _say(f"memory: peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
         f"bytes_limit={stats.get('bytes_limit')} "
         f"planner_predicted_peak_bytes={pred.predicted_peak_bytes}")
    res = _resilience_checks(solver.ctx)
    _say(f"resilience: demotions=0 ip_backend={res['ip_backend']} "
         f"ip_pool_calls={res['ip_pool_calls']} "
         f"ip_pool_fallbacks={res['ip_pool_fallbacks']}")


def run_four_chips(devices, scale: int, k: int = K) -> None:
    import jax
    from jax.sharding import Mesh

    import kaminpar_tpu.dist.partitioner as dpart
    from kaminpar_tpu.graph import metrics

    _check(len(devices) >= 4, f"--chips 4 needs 4 devices, found {len(devices)}")
    mesh = Mesh(np.array(devices[:4]), ("nodes",))
    g, *host = make_graph(scale)
    _say(f"graph rmat scale={scale} n={g.n} m={g.m} k={k} mesh=4")

    # Record the devices of every array the run shards onto the mesh.
    seen = set()
    shard_arrays = dpart.shard_arrays

    def observed(mesh_, dg, *arrays):
        out = shard_arrays(mesh_, dg, *arrays)
        for leaf in jax.tree_util.tree_leaves(out):
            if isinstance(leaf, jax.Array):
                seen.update(leaf.sharding.device_set)
        return out

    solver = dpart.DKaMinPar(mesh)
    dpart.shard_arrays = observed
    try:
        with _logged() as log:
            t0 = time.perf_counter()
            part4 = solver.compute_partition(g, k=k, epsilon=EPSILON)
            wall4 = time.perf_counter() - t0
    finally:
        dpart.shard_arrays = shard_arrays
    _check(len(seen) == 4, f"sharded arrays span {len(seen)} devices, not 4")
    total = int(host[3].sum())
    ceil_wk = -(-total // k)
    max_bw = np.full(
        k, max(int((1.0 + EPSILON) * ceil_wk), ceil_wk + g.max_node_weight)
    )
    sharded = check_partition(
        "sharded", g, host, part4,
        _reported_cut(log.getvalue().replace("dist RESULT", "RESULT")),
        max_bw, k,
    )
    _say(f"sharded: cut={sharded['cut']} imbalance={sharded['imbalance']} "
         f"wall_s={wall4} devices={len(seen)}")

    part1, reported1, wall1, solver1 = facade_partition(g, k)
    one = check_partition(
        "one-chip", g, host, part1, reported1,
        solver1.ctx.partition.max_block_weights, k,
    )
    _check(metrics.is_feasible(g, part1, k, max_bw), "one-chip: infeasible")
    _say(f"one-chip: cut={one['cut']} imbalance={one['imbalance']} "
         f"wall_s={wall1}")
    _say(f"cut ratio sharded/one-chip: {sharded['cut'] / max(one['cut'], 1)}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--scale", type=int, default=18,
                   help="RMAT scale (n = 2^scale); default 18")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: the sharded mesh path and its one-chip comparison")
    args = p.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    _say(f"device: kind={devices[0].device_kind} count={len(devices)} "
         f"compile_cache_dir={jax.config.jax_compilation_cache_dir}")
    try:
        if args.chips == 4:
            run_four_chips(devices, args.scale)
        else:
            run_one_chip(args.scale)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    _say(json.dumps({"ok": True, "device": _device_line(devices)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
