#!/usr/bin/env python
"""Driver benchmark: LP coarsening throughput + full-partition wall-clock.

Mirrors the reference's north-star microbenchmark
(``apps/benchmarks/shm_label_propagation_benchmark.cc:29-80``): build a graph,
run the LP clustering hot loop, report throughput.  BASELINE config 2 is RMAT
scale-22 / k=16; the scale is tunable via ``KPTPU_BENCH_SCALE`` so CI boxes
without a TPU can run a smaller instance.

Where it runs:

  * ``JAX_PLATFORMS=cpu``: every phase in this process (the shard/fleet
    phases in children on virtual CPU devices).
  * Otherwise the measurement needs a TPU: this parent never initializes a
    backend, and runs phases 1-4 in one child that holds the chip, then the
    shard/fleet children one after another.  A child that finds no TPU
    exits nonzero, and so does the parent — there is no CPU fallback.

The final stdout line is always the headline JSON record:
{"metric", "value", "unit", "vs_baseline", "backend", ...extras}.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# Measured reference anchor (VERDICT r1 weak #6: the previous 250e6 was a
# guess).  Measured 2026-07-30 on this box with the reference binary built
# from /root/reference (Release, -t 1, sparsehash/kassert off):
#   rgg64k (n=65k, m=1.63M directed): coarsening 0.079 s -> 20.6M edges/s
#   rmat14 (n=16k, m=0.22M directed): coarsening 0.016 s -> 13.6M edges/s
# Single-core LP-coarsening throughput ~= 17e6 edges/s.  The BASELINE.md
# north star compares against the 96-core TBB configuration; assuming 50%
# parallel efficiency (LP scales well but not linearly) gives the multicore
# anchor below.  This provenance is surfaced in the JSON as "baseline".
CPU_BASELINE_1CORE_EDGES_PER_SEC = 17e6
CPU_BASELINE_EDGES_PER_SEC = CPU_BASELINE_1CORE_EDGES_PER_SEC * 96 * 0.5
BASELINE_PROVENANCE = "estimated-96core (17e6 e/s measured single-core x96 x0.5 eff)"

# Peak HBM bandwidth (GB/s) by device_kind substring, for the interpretability
# estimate requested by VERDICT r2 next-steps #1.  Sources: public TPU specs.
_HBM_GBPS = [
    ("v6e", 1638.0),
    ("v5p", 2765.0),
    ("v5e", 819.0),
    ("v5 lite", 819.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
]


def _hbm_peak(device_kind: str) -> float | None:
    dk = device_kind.lower()
    for key, gbps in _HBM_GBPS:
        if key in dk:
            return gbps
    return None


def run_lp_phase() -> dict:
    """LP-clustering throughput on whatever backend JAX initializes in *this*
    process.  Prints the headline record the moment it exists and returns it."""
    import jax
    import jax.numpy as jnp

    from kaminpar_tpu.coarsening.max_cluster_weights import compute_max_cluster_weight
    from kaminpar_tpu.context import Context
    from kaminpar_tpu.graph.generators import rmat_graph
    from kaminpar_tpu.ops import lp, pallas_lp
    from kaminpar_tpu.utils import RandomState, next_key
    from kaminpar_tpu.utils import compile_stats, sync_stats

    compile_stats.reset()
    sync_stats.reset()

    dev = jax.devices()[0]
    backend = dev.platform
    device_kind = getattr(dev, "device_kind", backend)
    on_accel = backend != "cpu"

    default_scale = 22 if on_accel else 16
    scale = int(os.environ.get("KPTPU_BENCH_SCALE", default_scale))
    rounds = int(os.environ.get("KPTPU_BENCH_ROUNDS", 5))
    k = int(os.environ.get("KPTPU_BENCH_K", 16))
    # LP round kernel backend for the microbench: "xla" | "pallas" | "auto".
    lp_kernel = pallas_lp.resolve_lp_kernel(
        os.environ.get("KPTPU_BENCH_LP_KERNEL", "xla")
    )
    round_mod = pallas_lp if lp_kernel == "pallas" else lp

    RandomState.reseed(0)
    graph = rmat_graph(scale, edge_factor=16, seed=1)
    pv = graph.padded()
    n_pad = pv.n_pad

    bv = graph.bucketed()
    ctx = Context()
    max_cw = compute_max_cluster_weight(
        ctx.coarsening, graph.n, graph.total_node_weight, k, 0.03
    )
    idt = pv.row_ptr.dtype
    labels = jnp.concatenate(
        [jnp.arange(pv.n, dtype=idt), jnp.full(n_pad - pv.n, pv.anchor, dtype=idt)]
    )
    state = lp.init_state(labels, pv.node_w, n_pad)
    max_w = jnp.asarray(max_cw, dtype=idt)

    def one_round(state):
        return round_mod.lp_round_bucketed(
            state, next_key(), bv.buckets, bv.heavy, bv.gather_idx, pv.node_w,
            max_w, num_labels=n_pad,
        )

    # Warmup: compile + one real round.  Sync via a scalar readback,
    # routed through sync_stats so the fences show up in the host_sync
    # report rather than hiding from it.
    with sync_stats.scoped("lp_bench_fence"):
        state = one_round(state)
        sync_stats.pull(state.num_moved)

        start = time.perf_counter()
        for _ in range(rounds):
            state = one_round(state)
        sync_stats.pull(state.num_moved)
        elapsed = time.perf_counter() - start

    edges_per_sec = graph.m * rounds / elapsed
    # Lower-bound HBM traffic per LP round: per directed edge one adjacency
    # index read (4 B) + one neighbor-label gather (4 B) + one edge weight
    # (4 B); per node ~6 int32 reads/writes of label/weight/moved state.
    # Sort/scan traffic inside the bucketed kernels is NOT counted, so the
    # bandwidth figure is a floor on achieved DRAM throughput.
    bytes_lb = graph.m * 12 + graph.n * 24
    est_gbps = bytes_lb * rounds / elapsed / 1e9
    hbm_peak = _hbm_peak(str(device_kind)) if on_accel else None

    sync_snap = sync_stats.snapshot()
    record = {
        "metric": f"lp_clustering_throughput_rmat{scale}",
        "value": round(edges_per_sec, 1),
        "unit": "edges/sec",
        "vs_baseline": round(edges_per_sec / CPU_BASELINE_EDGES_PER_SEC, 4),
        "backend": backend,
        "device_kind": str(device_kind),
        "baseline": BASELINE_PROVENANCE,
        "est_hbm_gbps_lb": round(est_gbps, 1),
        "lp_kernel": lp_kernel,
        "lp_compile": compile_stats.compile_time_snapshot(),
        # Blocking device->host transfer census of the microbench window
        # (utils/sync_stats.py): count + bytes per timer phase.
        "host_sync_count": sync_snap["count"],
        "host_sync": sync_snap["phases"],
    }
    if hbm_peak:
        record["hbm_frac_of_peak_lb"] = round(est_gbps / hbm_peak, 4)
    # Flush the headline immediately: if the slower full-partition phase below
    # blows the parent's deadline, this line is salvaged as the result.
    print(json.dumps(record), flush=True)
    return record


def _timer_phase_seconds(*path: str) -> float | None:
    """Elapsed seconds of a timer-tree scope by path (e.g. "partitioning",
    "initial_partitioning"); None when the scope never ran.  Reads the
    merged (all-threads) tree via the public Timer API."""
    from kaminpar_tpu.utils import Timer

    return Timer.global_().phase_seconds(*path)


def _run_ip_ab(k: int) -> dict:
    """Initial-partitioning A/B (ISSUE 4 acceptance): wall of the same
    k-way recursive bisection on the host pool vs the lane-vmapped device
    pool, on a coarsest-graph-sized instance.  The device number is
    reported cold (first call pays per-cell compiles; the persistent cache
    keeps them paid) and warm (the steady-state cost every level of a real
    run pays)."""
    import dataclasses

    import numpy as np

    from kaminpar_tpu.context import InitialPartitioningContext
    from kaminpar_tpu.graph.generators import rmat_graph
    from kaminpar_tpu.initial.bipartitioner import _cut, recursive_bipartition
    from kaminpar_tpu.partitioning.kway import graph_to_host

    scale = int(os.environ.get("KPTPU_BENCH_IP_SCALE", 12))
    host = graph_to_host(rmat_graph(scale, edge_factor=8, seed=2))
    W = host.total_node_weight
    per = int(np.ceil(W / k) * 1.03) + 1
    budgets = np.full(k, per, dtype=np.int64)
    out: dict = {"scale": scale, "k": k}
    # The KAMINPAR_TPU_IP_BACKEND kill switch overrides the context knob,
    # which would make both A/B arms silently run the same pool; the A/B
    # pins each arm explicitly, so lift the override for its duration.
    env_override = os.environ.pop("KAMINPAR_TPU_IP_BACKEND", None)
    try:
        for backend in ("host", "device"):
            ipc = dataclasses.replace(
                InitialPartitioningContext(), ip_backend=backend
            )
            walls = []
            for rep in range(2):
                t0 = time.perf_counter()
                part = recursive_bipartition(
                    host, k, budgets, np.random.default_rng(1), ipc
                )
                walls.append(time.perf_counter() - t0)
            out[f"{backend}_cold_s"] = round(walls[0], 3)
            out[f"{backend}_warm_s"] = round(walls[1], 3)
            out[f"{backend}_cut"] = _cut(host, part)
    finally:
        if env_override is not None:
            os.environ["KAMINPAR_TPU_IP_BACKEND"] = env_override
    if out["device_warm_s"]:
        out["device_vs_host_warm"] = round(
            out["host_warm_s"] / out["device_warm_s"], 2
        )
    return out


def run_full_phase(record: dict | None = None) -> dict:
    """Phase 2: end-to-end compute_partition wall-clock (VERDICT r4 weak #2 —
    never recorded by any BENCH artifact before r5).  Scale defaults to one
    that finishes on CPU inside its own deadline; the persistent XLA
    compilation cache makes repeat runs warm."""
    import jax

    from kaminpar_tpu.context import Context
    from kaminpar_tpu.graph.generators import rmat_graph
    from kaminpar_tpu.graph.metrics import edge_cut
    from kaminpar_tpu.kaminpar import KaMinPar
    from kaminpar_tpu.utils import RandomState

    from kaminpar_tpu.utils import compile_stats, sync_stats

    compile_stats.reset()
    sync_stats.reset()

    record = dict(record or {})
    backend = jax.devices()[0].platform
    on_accel = backend != "cpu"
    k = int(os.environ.get("KPTPU_BENCH_K", 16))
    # CPU default 17: scale 16 measured 134 s warm on this box (r5); one
    # doubling keeps a safe margin inside the 900 s phase-2 deadline.
    default_full = 20 if on_accel else 17
    full_scale = int(os.environ.get("KPTPU_BENCH_FULL_SCALE", default_full))

    from kaminpar_tpu.initial.bipartitioner import resolve_ip_backend
    from kaminpar_tpu.ops import bipartition as ip_pool
    from kaminpar_tpu.telemetry import trace as ttrace
    from kaminpar_tpu.utils import heap_profiler
    from kaminpar_tpu.utils.heap_profiler import HeapProfiler

    # Executable census (ISSUE 12): armed for the bench so compile events
    # attribute to their phases and warmup/AOT harvest sites populate —
    # strictly host-side (zero transfers; tests assert neutrality).
    if os.environ.get("KPTPU_BENCH_CENSUS", "1") == "1":
        compile_stats.arm_executable_census()
    ip_pool.reset_pool_stats()
    RandomState.reseed(0)
    fgraph = rmat_graph(full_scale, edge_factor=16, seed=1)
    shm = KaMinPar(ctx=Context())
    shm.set_graph(fgraph)
    # Run telemetry (ISSUE 5): the full-partition phase records the unified
    # trace — spans, per-level quality rows, sync/compile/HBM counter
    # samples — and the artifact carries its summary + the trace path.
    trace_out = os.environ.get(
        "KPTPU_BENCH_TRACE_OUT", os.path.join(REPO, "BENCH_trace.json")
    )
    trace_rec = None if ttrace.active() is not None else ttrace.start()
    HeapProfiler.reset(enabled=True)
    t0 = time.perf_counter()
    try:
        part = shm.compute_partition(k, epsilon=0.03)
    finally:
        wall = time.perf_counter() - t0
        if trace_rec is not None:
            ttrace.stop()
    cut = int(edge_cut(fgraph, part))
    # Initial-partitioning share of the partition wall + device-pool lane
    # census (ISSUE 4): occupancy = requested repetitions / bucketed lanes
    # launched; zero calls on the host backend is the honest CPU reading.
    ip_wall = _timer_phase_seconds("partitioning", "initial_partitioning")
    part_wall = _timer_phase_seconds("partitioning")
    # Distinct kernel specializations + actual compile wall-time of the
    # full-partition phase — the cold-compile tax the geometric shape
    # buckets bound.
    shape_counts = compile_stats.snapshot()
    sync_snap = sync_stats.snapshot()
    record.update({
        "backend": record.get("backend", backend),
        "partition_wall_s": round(wall, 2),
        "partition_cut": cut,
        "partition_scale": full_scale,
        "partition_k": k,
        "partition_edges_per_sec": round(fgraph.m / wall, 1),
        "compiled_shape_count": shape_counts,
        "partition_compile": compile_stats.compile_time_snapshot(),
        "ip_backend": resolve_ip_backend(shm.ctx.initial_partitioning),
        "initial_partitioning_wall_s": round(ip_wall, 3)
        if ip_wall is not None else None,
        "initial_partitioning_share": round(ip_wall / part_wall, 4)
        if ip_wall is not None and part_wall else None,
        "ip_pool": ip_pool.pool_stats_snapshot(),
        # Blocking-transfer census of the full-partition phase: total count
        # + per-phase {count, bytes} keyed by the timer tree's scope names
        # (the one-batched-readback-per-coarsening-level contract shows up
        # as host_sync.coarsening.count == hierarchy depth).
        "host_sync_count": sync_snap["count"],
        "host_sync_bytes": sync_snap["bytes"],
        "host_sync": sync_snap["phases"],
        # Executable census + per-phase compile attribution (ISSUE 12):
        # what the compiled programs WOULD do (XLA cost/memory analyses)
        # and which phases paid the cold compiles.
        "executable_census": compile_stats.executable_census_snapshot(),
        "compile_by_phase": compile_stats.compile_by_phase_snapshot(),
    })
    # Telemetry summary (ISSUE 5): trace path + per-level quality rows +
    # the HBM watermark, embedded so the bench record carries the run's
    # structured record.
    if trace_rec is not None:
        try:
            trace_rec.meta.update(
                {"scale": full_scale, "k": k, "backend": backend}
            )
            trace_rec.write(trace_out)
            record["telemetry"] = {
                "trace_path": trace_out,
                **trace_rec.summary(),
                # Cap the embedded rows so a deep hierarchy cannot bloat the
                # one-line artifact; the full set lives in the trace file.
                "levels": trace_rec.quality[:48],
                "hbm": heap_profiler.watermark_report(),
            }
        except Exception as exc:  # noqa: BLE001 — telemetry must not void the record
            record["telemetry_error"] = f"{type(exc).__name__}: {exc}"[:300]
    # kptlint summary (ISSUE 7): rule counts + baseline size ride the
    # artifact so static-contract violation drift is visible in the perf
    # trajectory alongside the runtime sync census above.
    try:
        from kaminpar_tpu.analysis.cli import lint_summary

        record["lint"] = lint_summary()
    except Exception as exc:  # noqa: BLE001 — lint must not void the record
        record["lint_error"] = f"{type(exc).__name__}: {exc}"[:300]
    # Resilience census (round 17, ISSUE 13): any PIPELINE-rung
    # degradation (ip_device->host, device_decode->dense, lp_pallas->xla)
    # or breaker trips during the measured run ride the artifact — a
    # benchmark that silently served its numbers from a demoted path
    # must say so next to the headline.  Scope is the process-global
    # registry only: serve-tier rungs (lanestack/cell/quality) live on
    # each engine's private registry and surface through the serve
    # phase's own stats snapshot (lanestack_fallbacks etc.), not here.
    try:
        from kaminpar_tpu.resilience import breakers as _rbreakers
        from kaminpar_tpu.resilience import faults as _rfaults

        rsnap = _rbreakers.global_registry().snapshot()
        record["resilience"] = {
            "scope": "pipeline_rungs",
            "demotions": rsnap["demotions"],
            "breaker_trips": sum(
                b["trips"] for b in rsnap["breakers"].values()
            ),
            "faults_injected": _rfaults.injected_total(),
        }
    except Exception as exc:  # noqa: BLE001 — census must not void the record
        record["resilience_error"] = f"{type(exc).__name__}: {exc}"[:300]
    # Run-ledger inputs (round 13): top-level phase walls + the collective
    # census ride the record so the ledger entry (and the salvage path,
    # which runs in the parent process) sees the measuring process's state.
    try:
        from kaminpar_tpu.telemetry import ledger as _ledger
        from kaminpar_tpu.utils import collective_stats

        record["phase_walls_s"] = _ledger.phase_walls()
        record["collectives"] = collective_stats.snapshot()
    except Exception as exc:  # noqa: BLE001
        record["ledger_inputs_error"] = f"{type(exc).__name__}: {exc}"[:300]
    # Watermark captured — disarm the profiler so the serve phase's measured
    # request path does not pay per-scope allocator queries or accumulate
    # unbounded per-request heap-tree nodes.
    HeapProfiler.reset(enabled=False)
    # Measured host-vs-device pool speedup (ISSUE 4 acceptance); an A/B
    # failure must not void the partition record above.
    if os.environ.get("KPTPU_BENCH_IP_AB", "1") == "1":
        try:
            record["ip_ab"] = _run_ip_ab(k=min(k, 8))
        except Exception as exc:  # noqa: BLE001
            record["ip_ab_error"] = f"{type(exc).__name__}: {exc}"[:300]
    print(json.dumps(record), flush=True)
    return record


def _run_lanestack_ab(scale: int, k: int, occupancy: int = 8,
                      reps: int = 3) -> dict:
    """Execute-phase wall of one full-occupancy same-cell batch: warm
    per-graph loop vs ONE lane-stacked vmapped program, both measured over
    ``reps`` warm passes (first pass unmeasured on each arm)."""
    from kaminpar_tpu.graph.generators import rmat_graph
    from kaminpar_tpu.kaminpar import KaMinPar
    from kaminpar_tpu.presets import create_context_by_preset_name
    from kaminpar_tpu.serve.batching import shape_cell
    from kaminpar_tpu.serve.lanestack import run_lanestacked

    # Distinct seeds from one RMAT family, filtered to the dominant shape
    # cell — the batch the serve queue would actually form.
    pool = [rmat_graph(scale, edge_factor=8, seed=200 + i) for i in range(24)]
    cells = [shape_cell(g, k) for g in pool]
    head = max(set(cells), key=cells.count)
    graphs = [g for g, c in zip(pool, cells) if c == head][:occupancy]

    solver = KaMinPar(ctx="serve")

    def pergraph_once() -> None:
        for g in graphs:
            solver.set_graph(g)
            solver.compute_partition(k, 0.03)

    ctx = create_context_by_preset_name("serve")

    def lanestack_once():
        return run_lanestacked(ctx, graphs, k, 0.03)

    pergraph_once()  # warm (traces + compiles)
    t0 = time.perf_counter()
    for _ in range(reps):
        pergraph_once()
    pergraph_s = (time.perf_counter() - t0) / reps

    _, report = lanestack_once()  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        _, report = lanestack_once()
    lanestack_s = (time.perf_counter() - t0) / reps

    return {
        "scale": scale,
        "k": k,
        "occupancy": len(graphs),
        "reps": reps,
        "pergraph_s": round(pergraph_s, 4),
        "lanestack_s": round(lanestack_s, 4),
        "lanestack_vs_pergraph": round(pergraph_s / lanestack_s, 2)
        if lanestack_s else None,
        "cohorts": report.cohorts,
        "splits": report.splits,
        "stacked_pulls": report.stacked_pulls,
    }


def run_serve_phase(record: dict | None = None) -> dict:
    """Phase 3 (ISSUE 3): serving throughput under the warm engine vs the
    status-quo single-request pattern, over an offered-load sweep.

    ``single_request`` is the pattern the serve runtime replaces — a cold,
    single-graph, synchronous invocation: fresh facade per request with the
    in-process executable caches cleared (``jax.clear_caches()``), so every
    call pays the per-call rebuild (trace + cache load; the persistent disk
    cache stays, so XLA compiles are warm — this measures orchestration
    rebuild, not compiler time).  ``warm_single`` is the honest same-process
    lower bound (warm caches, still one request at a time).  The serve side
    warms the ladder once (reported as ``serve_warmup_s``, excluded from
    steady-state throughput) and then takes the same workload at two offered
    loads: a burst (maximum batchability) and a paced trickle (occupancy 1).
    """
    import jax

    from kaminpar_tpu.graph.generators import rmat_graph
    from kaminpar_tpu.kaminpar import KaMinPar
    from kaminpar_tpu.serve import PartitionEngine
    from kaminpar_tpu.utils import RandomState

    record = dict(record or {})
    backend = jax.devices()[0].platform
    n_req = int(os.environ.get("KPTPU_BENCH_SERVE_REQS", 24))
    scales = tuple(
        int(s) for s in os.environ.get("KPTPU_BENCH_SERVE_SCALES", "8,9").split(",")
    )
    k = int(os.environ.get("KPTPU_BENCH_SERVE_K", 8))
    base_n = min(int(os.environ.get("KPTPU_BENCH_SERVE_BASE_REQS", 6)), n_req)

    from kaminpar_tpu.utils import compile_stats

    if os.environ.get("KPTPU_BENCH_CENSUS", "1") == "1":
        # Engine warmup harvests per-cell executable censuses when armed
        # (ISSUE 12) — the serve record carries them below.
        compile_stats.arm_executable_census()

    RandomState.reseed(0)
    graphs = [
        rmat_graph(scales[i % len(scales)], edge_factor=8, seed=100 + i)
        for i in range(n_req)
    ]

    def single_sweep(n: int, cold: bool) -> float:
        t0 = time.perf_counter()
        for g in graphs[:n]:
            if cold:
                jax.clear_caches()
            solver = KaMinPar(ctx="serve")
            solver.set_graph(g)
            solver.compute_partition(k, 0.03)
        return n / (time.perf_counter() - t0)

    engine = PartitionEngine(
        "serve", warm_ladder=tuple(1 << s for s in scales), warm_ks=(k,)
    )
    t0 = time.perf_counter()
    engine.start(warmup=True)
    warmup_s = time.perf_counter() - t0

    from kaminpar_tpu.serve import QueueFullError

    def submit_backpressured(g):
        # An offered load beyond the queue bound is the backpressure path
        # working as designed — honor the retry-after hint instead of
        # letting the sweep crash on its own admission control.
        while True:
            try:
                return engine.submit(g, k)
            except QueueFullError as e:
                time.sleep(e.retry_after_s)

    sweep = []
    try:
        # Preflight (unmeasured): steady-state serving throughput is the
        # quantity of interest, and the warmup ladder cannot predict every
        # shape cell of the workload (edge buckets vary with the graphs),
        # so run the workload once to pay first-touch traces before the
        # measured windows.  Its wall is reported — it is the cold tax a
        # real deployment pays exactly once per cell per process.
        t0 = time.perf_counter()
        for fut in [submit_backpressured(g) for g in graphs]:
            fut.result()
        preflight_s = time.perf_counter() - t0

        for load, gap_s in (("burst", 0.0), ("paced", None)):
            engine.stats_.reset()
            t0 = time.perf_counter()
            if gap_s is None:
                # Paced = closed-loop, one in flight: the no-batching floor.
                for g in graphs:
                    engine.submit(g, k).result()
            else:
                futures = [submit_backpressured(g) for g in graphs]
                for fut in futures:
                    fut.result()
            wall = time.perf_counter() - t0
            snap = engine.stats_.snapshot()
            sweep.append({
                "offered_load": load,
                "throughput_gps": round(n_req / wall, 2),
                "batch_occupancy_mean": snap["batch_occupancy_mean"],
                "batch_occupancy_max": snap["batch_occupancy_max"],
                "p50_ms": snap["latency_ms"]["total_ms"].get("p50"),
                "p99_ms": snap["latency_ms"]["total_ms"].get("p99"),
                "timed_out": snap["timed_out"],
                # Lane-stack census (ISSUE 6): how many batches ran as one
                # vmapped stack, at what realized lane occupancy, and how
                # many fell back to the per-graph loop.
                "lanestack_batches": snap["lanestacked_batches"],
                "lanestack_occupancy_mean": snap["lanestack_occupancy_mean"],
                "lanestack_fallbacks": snap["lanestack_fallbacks"],
            })
    finally:
        engine.shutdown(drain=True)

    # Lane-stack execute-phase A/B (ISSUE 6): the same-cell batch the serve
    # queue forms at full occupancy, executed (a) once per graph on the warm
    # facade — the PR 3 pattern — and (b) as ONE lane-stacked vmapped
    # program (serve/lanestack.py).  Both arms run once unmeasured (warm
    # tax paid identically) and are then timed over `reps` passes; results
    # are bit-identical by the lane-stack contract, so this isolates pure
    # execute-phase wall.  Distinct seeds, honest workload: cohort splits
    # (hierarchy divergence) are reported, not hidden.
    try:
        record["lanestack_ab"] = _run_lanestack_ab(
            scale=max(scales), k=k,
            occupancy=int(os.environ.get("KPTPU_BENCH_LANESTACK_OCC", 8)),
        )
    except Exception as exc:  # noqa: BLE001
        record["lanestack_ab_error"] = f"{type(exc).__name__}: {exc}"[:300]

    # Baselines AFTER the engine phases so ordering cannot skew them:
    # warm_single shares the process's now-warm caches (the honest
    # same-process floor), and the cold-call pattern runs last because
    # jax.clear_caches() would throw away everyone else's warm state.
    warm_single_gps = single_sweep(base_n, cold=False)
    single_gps = single_sweep(base_n, cold=True)

    burst = sweep[0]
    record.update({
        "backend": record.get("backend", backend),
        "serve_requests": n_req,
        "serve_k": k,
        "serve_warmup_s": round(warmup_s, 2),
        "serve_preflight_s": round(preflight_s, 2),
        "serve_throughput_gps": burst["throughput_gps"],
        "serve_batch_occupancy": burst["batch_occupancy_mean"],
        "serve_p50_ms": burst["p50_ms"],
        "serve_p99_ms": burst["p99_ms"],
        "single_request_gps": round(single_gps, 3),
        "warm_single_gps": round(warm_single_gps, 3),
        "serve_vs_single_request": round(burst["throughput_gps"] / single_gps, 2)
        if single_gps else None,
        "serve_vs_warm_single": round(
            burst["throughput_gps"] / warm_single_gps, 2
        ) if warm_single_gps else None,
        "lanestack_vs_pergraph": (record.get("lanestack_ab") or {}).get(
            "lanestack_vs_pergraph"
        ),
        "serve_sweep": sweep,
        "executable_census": compile_stats.executable_census_snapshot(),
    })
    print(json.dumps(record), flush=True)
    return record


def run_compress_phase(record: dict | None = None) -> dict:
    """Phase 4 (ISSUE 10): compressed-graph device-pipeline A/B — the same
    terapart run with ``device_decode`` off (host decompress + dense
    kernels) vs ``finest`` (decode fused into the LP kernels), recording
    wall per level (the run-trace quality rows ride the existing per-level
    readbacks), resident bytes/edge of both adjacency tiers, the
    compression ratio, and the HBM watermark delta.  Keys ride the
    RUNS.jsonl ledger flat (``compress_ab_*``) so ``tools regress``
    baseline windows cover them."""
    import jax
    import numpy as np

    from kaminpar_tpu.graph.compressed import compress
    from kaminpar_tpu.graph.device_compressed import DeviceCompressedView
    from kaminpar_tpu.graph.generators import rmat_graph
    from kaminpar_tpu.kaminpar import KaMinPar
    from kaminpar_tpu.telemetry import trace as ttrace
    from kaminpar_tpu.utils import RandomState, Timer, heap_profiler
    from kaminpar_tpu.utils.heap_profiler import HeapProfiler

    record = dict(record or {})
    backend = jax.devices()[0].platform
    k = int(os.environ.get("KPTPU_BENCH_K", 16))
    # Scale 16 is the acceptance floor for the resident-bytes claim; warm
    # CPU runs finish each arm in ~2 min (the full phase's scale-17 single
    # run is the reference point).
    scale = int(os.environ.get("KPTPU_BENCH_COMPRESS_SCALE", 16))
    g = rmat_graph(scale, edge_factor=16, seed=1)
    cg = compress(g)
    cv = DeviceCompressedView(cg)
    dense_bytes = cv.dense_resident_bytes()
    comp_bytes = cv.resident_bytes()
    ab: dict = {
        "backend": backend,
        "scale": scale,
        "k": k,
        "compression_ratio": round(cg.compression_ratio(), 3),
        # Device-resident adjacency bytes of the finest level: what the
        # dense path keeps in HBM between dispatches vs the compressed
        # stream + decode metadata (graph/device_compressed.py).
        "resident_bytes_dense": dense_bytes,
        "resident_bytes_compressed": comp_bytes,
        "bytes_per_edge_dense": round(dense_bytes / max(g.m, 1), 2),
        "bytes_per_edge_compressed": round(comp_bytes / max(g.m, 1), 2),
        "resident_reduction": round(dense_bytes / max(comp_bytes, 1), 3),
    }
    del cv  # the finest arm rebuilds its own; keep the A honest
    # The env override beats the per-arm ctx knob (resolve_device_decode);
    # a leftover KAMINPAR_TPU_DEVICE_DECODE would silently run both arms in
    # the same mode and record a meaningless A/B into the ledger.
    env_override = os.environ.pop("KAMINPAR_TPU_DEVICE_DECODE", None)
    if env_override is not None:
        ab["env_override_cleared"] = env_override
    parts: dict = {}
    for mode, tag in (("off", "dense"), ("finest", "decode")):
        RandomState.reseed(0)
        Timer.reset_global()
        solver = KaMinPar("terapart")
        solver.ctx.compression.device_decode = mode
        trace_rec = None if ttrace.active() is not None else ttrace.start()
        HeapProfiler.reset(enabled=True)
        t0 = time.perf_counter()
        try:
            solver.set_graph(g)
            parts[mode] = solver.compute_partition(k, epsilon=0.03)
        finally:
            wall = time.perf_counter() - t0
            if trace_rec is not None:
                ttrace.stop()
        arm = {
            "wall_s": round(wall, 2),
            "coarsening_wall_s": _timer_phase_seconds(
                "partitioning", "coarsening"
            ),
            # Allocator truth (empty on backends without stats — the
            # honest CPU reading; the static resident_bytes_* above are
            # exact either way).
            "hbm": heap_profiler.watermark_report(),
        }
        if trace_rec is not None:
            # Per-level rows (n, m, wall between level readbacks) — they
            # rode the levels' existing single pulls, zero added transfers.
            arm["levels"] = trace_rec.quality[:24]
        ab[tag] = arm
        HeapProfiler.reset(enabled=False)
    if env_override is not None:
        os.environ["KAMINPAR_TPU_DEVICE_DECODE"] = env_override
    ab["identical_partition"] = bool(
        np.array_equal(parts["off"], parts["finest"])
    )
    peaks = [
        ab[tag].get("hbm", {}).get("peak_bytes_in_use")
        for tag in ("dense", "decode")
    ]
    if all(isinstance(p, int) for p in peaks):
        ab["hbm_peak_delta_bytes"] = peaks[0] - peaks[1]
    record["compress_ab"] = ab
    # Flat ledger keys (telemetry/ledger._numeric_metrics reads top-level
    # numerics; *_ratio/*_reduction are higher-better, *_s/_bytes lower).
    record.update({
        "compress_ab_dense_wall_s": ab["dense"]["wall_s"],
        "compress_ab_decode_wall_s": ab["decode"]["wall_s"],
        "compress_ab_resident_bytes_dense": dense_bytes,
        "compress_ab_resident_bytes_compressed": comp_bytes,
        "compress_ab_compression_ratio": ab["compression_ratio"],
        "compress_ab_resident_reduction": ab["resident_reduction"],
        "compress_ab_identical": int(ab["identical_partition"]),
    })
    print(json.dumps(record), flush=True)
    return record


def run_shard_phase(record: dict | None = None) -> dict:
    """Phase 5 (ISSUE 11): sharded deep-multilevel A/B on the P-device mesh.

    Three arms at one (scale, k, seed) workload: the single-device shm deep
    pipeline, the P-shard dist pipeline on the dense staging path, and the
    P-shard dist pipeline off the device-resident per-shard compressed
    streams (``device_decode``).  Per arm: end-to-end wall, per-level trace
    rows (they ride the levels' existing counted pulls), the per-shard pull
    census (``shard_pulls`` over the dist phases), the trace-time collective
    census, and the HBM watermark (allocator stats exist on TPU; the static
    resident-bytes figures are exact on every backend).  The dense-vs-
    compressed identical-partition check is the acceptance witness; flat
    ``shard_ab_*`` keys ride RUNS.jsonl so ``tools regress`` covers them.

    Runs on whatever mesh this process has; the ``--child`` entry forces
    ``KPTPU_BENCH_SHARD_P`` virtual CPU devices (the dryrun) unless
    ``KPTPU_BENCH_SHARD_NATIVE=1`` keeps the ambient multi-chip mesh.
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from kaminpar_tpu.dist.compressed import compress_distributed
    from kaminpar_tpu.dist.device_compressed import build_dist_device_view
    from kaminpar_tpu.dist.partitioner import DKaMinPar
    from kaminpar_tpu.graph import metrics as gmetrics
    from kaminpar_tpu.graph.generators import rmat_graph
    from kaminpar_tpu.kaminpar import KaMinPar
    from kaminpar_tpu.presets import create_context_by_preset_name
    from kaminpar_tpu.telemetry import trace as ttrace
    from kaminpar_tpu.utils import (
        RandomState, Timer, collective_stats, heap_profiler, sync_stats,
    )
    from kaminpar_tpu.utils.heap_profiler import HeapProfiler

    record = dict(record or {})
    P = int(os.environ.get("KPTPU_BENCH_SHARD_P", 8))
    scale = int(os.environ.get("KPTPU_BENCH_SHARD_SCALE", 12))
    k = int(os.environ.get("KPTPU_BENCH_SHARD_K", 8))
    # Contraction limit for the mesh arms: the default C=2000 stops dryrun-
    # sized graphs before any dist level forms; 256 gives a real hierarchy
    # (several coarsen/uncoarsen levels) at scale 12 so the per-level rows
    # and the coarsening pull census measure something.
    cl = int(os.environ.get("KPTPU_BENCH_SHARD_CL", 256))
    devs = jax.devices()
    backend = devs[0].platform
    if len(devs) < P:
        raise RuntimeError(
            f"shard_ab needs {P} devices, have {len(devs)} (the --child "
            "entry forces virtual CPU devices; in-process callers must)"
        )
    mesh = Mesh(np.array(devs[:P]), ("nodes",))
    g = rmat_graph(scale, edge_factor=8, seed=1)

    # Static resident-adjacency accounting straight from the view layout
    # (exact on every backend): dense = the three (P*m_loc,) structural
    # arrays, compressed = words + decode metadata + ghost table.
    dcg = compress_distributed(g, P)
    view = build_dist_device_view(dcg)
    dense_bytes = view.dense_resident_bytes()
    comp_bytes = view.resident_bytes()
    del view, dcg  # the measured arms rebuild their own

    ab: dict = {
        "backend": backend,
        "shards": P,
        "scale": scale,
        "k": k,
        "contraction_limit": cl,
        "resident_bytes_dense": dense_bytes,
        "resident_bytes_compressed": comp_bytes,
        "bytes_per_edge_dense": round(dense_bytes / max(g.m, 1), 2),
        "bytes_per_edge_compressed": round(comp_bytes / max(g.m, 1), 2),
        "resident_reduction": round(dense_bytes / max(comp_bytes, 1), 3),
    }
    # The env override beats the per-arm ctx knob (resolve_device_decode);
    # clear it so both mesh arms measure what they claim.
    env_override = os.environ.pop("KAMINPAR_TPU_DEVICE_DECODE", None)
    if env_override is not None:
        ab["env_override_cleared"] = env_override

    def _arm_record(wall: float, part, trace_rec) -> dict:
        arm = {
            "wall_s": round(wall, 2),
            "cut": int(gmetrics.edge_cut(g, part)),
            "hbm": heap_profiler.watermark_report(),
        }
        snap = sync_stats.snapshot()["phases"]
        arm["pull_census"] = {
            phase: {
                "count": row["count"],
                "shard_pulls": row.get("shard_pulls", 0),
            }
            for phase, row in sorted(snap.items())
            if phase.startswith("dist_")
        }
        coll = collective_stats.snapshot()
        arm["collectives_traced"] = {
            "count": coll.get("count", 0),
            "logical_bytes": coll.get("logical_bytes", coll.get("bytes", 0)),
        }
        if trace_rec is not None:
            # Per-level rows (n, m, shrink / k per level) — they rode the
            # levels' existing counted pulls, zero added transfers.
            arm["levels"] = [
                r for r in trace_rec.quality
                if str(r.get("kind", "")).startswith("dist_")
            ][:24]
        return arm

    # Arm 0: single-device shm deep at the same workload (the wall anchor
    # for the dryrun; on a CPU mesh the P-shard arms pay collective overhead
    # for no real parallelism — the honest reading is in TPU_NOTES r15).
    RandomState.reseed(0)
    Timer.reset_global()
    HeapProfiler.reset(enabled=True)
    t0 = time.perf_counter()
    solver = KaMinPar("default")
    solver.ctx.seed = 1
    solver.set_graph(g)
    part_single = solver.compute_partition(k, epsilon=0.03)
    ab["single"] = {
        "wall_s": round(time.perf_counter() - t0, 2),
        "cut": int(gmetrics.edge_cut(g, part_single)),
        "hbm": heap_profiler.watermark_report(),
    }
    HeapProfiler.reset(enabled=False)

    def _mesh_ctx(compress: bool, mode: str):
        ctx = create_context_by_preset_name("default")
        ctx.seed = 1
        ctx.coarsening.contraction_limit = cl
        ctx.compression.enabled = compress
        ctx.compression.device_decode = mode
        return ctx

    parts: dict = {}
    for tag, compress, mode in (
        ("dense", False, "off"), ("compressed", True, "finest")
    ):
        RandomState.reseed(0)
        Timer.reset_global()
        sync_stats.reset()
        collective_stats.reset()
        trace_rec = None if ttrace.active() is not None else ttrace.start()
        HeapProfiler.reset(enabled=True)
        t0 = time.perf_counter()
        try:
            parts[tag] = DKaMinPar(mesh, _mesh_ctx(compress, mode)).compute_partition(
                g, k=k, epsilon=0.03
            )
        finally:
            wall = time.perf_counter() - t0
            if trace_rec is not None:
                ttrace.stop()
        ab[tag] = _arm_record(wall, parts[tag], trace_rec)
        HeapProfiler.reset(enabled=False)
    if env_override is not None:
        os.environ["KAMINPAR_TPU_DEVICE_DECODE"] = env_override

    # Acceptance witness: the compressed mesh arm is bit-identical to the
    # dense mesh arm (same seed, same mesh, decode-fused kernels).
    ab["identical_partition"] = bool(
        np.array_equal(parts["dense"], parts["compressed"])
    )
    peaks = [
        ab[tag].get("hbm", {}).get("peak_bytes_in_use")
        for tag in ("dense", "compressed")
    ]
    if all(isinstance(p, int) for p in peaks):
        ab["hbm_peak_delta_bytes"] = peaks[0] - peaks[1]
    record["shard_ab"] = ab
    # Flat ledger keys (telemetry/ledger: *_wall_s/_bytes/count lower-better,
    # *_reduction higher-better; covered by the tools regress windows).
    comp_pulls = sum(
        row["shard_pulls"] for row in ab["compressed"]["pull_census"].values()
    )
    record.update({
        "shard_ab_single_wall_s": ab["single"]["wall_s"],
        "shard_ab_dense_wall_s": ab["dense"]["wall_s"],
        "shard_ab_compressed_wall_s": ab["compressed"]["wall_s"],
        "shard_ab_resident_bytes_dense": dense_bytes,
        "shard_ab_resident_bytes_compressed": comp_bytes,
        "shard_ab_resident_reduction": ab["resident_reduction"],
        "shard_ab_identical": int(ab["identical_partition"]),
        "shard_ab_shard_pull_count": comp_pulls,
        "shard_ab_collective_bytes":
            ab["compressed"]["collectives_traced"]["logical_bytes"],
    })
    print(json.dumps(record), flush=True)
    return record


def run_fleet_phase(record: dict | None = None) -> dict:
    """Phase 6 (ISSUE 14): single-engine vs N-replica fleet A/B on the
    P-device mesh (CPU dryrun: forced virtual host devices, like shard_ab).

    One same-cell burst workload served two ways: (a) ONE warm
    PartitionEngine — the PR 3/6 pattern, lane axis only — and (b) a
    :class:`~kaminpar_tpu.serve.fleet.PartitionFleet` of P per-device
    replicas behind the SLO-aware shape-cell router (lane x device).  Per
    arm: aggregate graphs/s, per-replica batch occupancy, p50/p99 total
    latency (computed fleet-wide from the request results themselves),
    steer/resteer counts, warm-cache inheritance counts (replica 0 pays
    the ladder, replicas 1..N-1 import it — the inherit ratio is a ledger
    metric), and a per-replica bit-identity probe against a sequential
    facade run.  Flat ``fleet_*`` keys ride RUNS.jsonl under the ``tools
    regress`` sentinel.

    CPU-dryrun honesty (TPU_NOTES round 18): virtual host devices
    SERIALIZE — the aggregate-throughput ratio is a *device* claim; on CPU
    this phase proves routing, occupancy, inheritance, and bit-identity,
    not speedup.
    """
    import jax
    import numpy as np

    from kaminpar_tpu.graph.generators import rmat_graph
    from kaminpar_tpu.kaminpar import KaMinPar
    from kaminpar_tpu.serve import PartitionEngine, PartitionFleet, QueueFullError
    from kaminpar_tpu.serve.batching import shape_cell
    from kaminpar_tpu.utils import RandomState

    record = dict(record or {})
    P = int(os.environ.get("KPTPU_BENCH_FLEET_P", 8))
    scale = int(os.environ.get("KPTPU_BENCH_FLEET_SCALE", 8))
    k = int(os.environ.get("KPTPU_BENCH_FLEET_K", 8))
    n_req = int(os.environ.get("KPTPU_BENCH_FLEET_REQS", 64))
    max_batch = int(os.environ.get("KPTPU_BENCH_FLEET_MAX_BATCH", 8))
    devs = jax.devices()
    backend = devs[0].platform
    if len(devs) < P:
        raise RuntimeError(
            f"fleet phase needs {P} devices, have {len(devs)} (the --child "
            "entry forces virtual CPU devices; in-process callers must)"
        )

    # Same-cell burst workload: distinct seeds from one RMAT family,
    # filtered to the dominant shape cell (the batch population the serve
    # queue and the router actually see).
    pool = [rmat_graph(scale, edge_factor=8, seed=300 + i)
            for i in range(2 * n_req)]
    cells = [shape_cell(g, k) for g in pool]
    head = max(set(cells), key=cells.count)
    graphs = [g for g, c in zip(pool, cells) if c == head][:n_req]
    n_req = len(graphs)

    serve_cfg = dict(
        warm_ladder=(1 << scale,), warm_ks=(k,), max_batch=max_batch,
        queue_bound=max(n_req, 8),
    )

    def _submit_backpressured(target, g):
        while True:
            try:
                return target.submit(g, k)
            except QueueFullError as e:
                time.sleep(e.retry_after_s)

    def _measure_burst(target) -> dict:
        # Burst with a held dispatcher so the queues (and the router's
        # batch-join fill) see the whole offered load, then release.
        target.pause()
        t0 = time.perf_counter()
        futures = [_submit_backpressured(target, g) for g in graphs]
        target.resume()
        results = [f.result() for f in futures]
        wall = time.perf_counter() - t0
        totals = [
            (r.queue_wait_s + r.execute_s) * 1e3 for r in results
        ]
        return {
            "wall_s": round(wall, 2),
            "throughput_gps": round(n_req / wall, 2),
            "p50_ms": round(float(np.percentile(totals, 50)), 1),
            "p99_ms": round(float(np.percentile(totals, 99)), 1),
            "results": results,
        }

    ab: dict = {"backend": backend, "replicas": P, "scale": scale, "k": k,
                "requests": n_req, "max_batch": max_batch}

    # Sequential reference for the bit-identity probe (the engine contract:
    # warm serve results == cold facade runs).
    RandomState.reseed(0)
    ref_solver = KaMinPar(ctx="serve")
    ref_solver.set_graph(graphs[0])
    ref_part = ref_solver.compute_partition(k, 0.03)

    # Arm A: one warm engine (lane axis only).
    RandomState.reseed(0)
    engine = PartitionEngine("serve", **serve_cfg)
    t0 = time.perf_counter()
    engine.start(warmup=True)
    single_warm_s = time.perf_counter() - t0
    try:
        for fut in [_submit_backpressured(engine, g) for g in graphs]:
            fut.result()  # preflight: pay first-touch traces unmeasured
        engine.stats_.reset()
        burst = _measure_burst(engine)
        snap = engine.stats_.snapshot()
        ab["single"] = {
            "warmup_s": round(single_warm_s, 2),
            "wall_s": burst["wall_s"],
            "throughput_gps": burst["throughput_gps"],
            "p50_ms": burst["p50_ms"],
            "p99_ms": burst["p99_ms"],
            "batch_occupancy_mean": snap["batch_occupancy_mean"],
            "batch_occupancy_max": snap["batch_occupancy_max"],
            "lanestacked_batches": snap["lanestacked_batches"],
        }
    finally:
        engine.shutdown(drain=True)

    # Arm B: the P-replica fleet (lane x device).
    RandomState.reseed(0)
    fleet = PartitionFleet("serve", replicas=P, **serve_cfg)
    t0 = time.perf_counter()
    fleet.start(warmup=True)
    fleet_warm_s = time.perf_counter() - t0
    try:
        inherit = [r.warmup_cell_counts() for r in fleet.replicas]
        inherited_total = sum(c["inherited"] for c in inherit[1:])
        report_total = sum(
            c["inherited"] + c["local"] for c in inherit[1:]
        )
        # Per-replica bit-identity probe: the same (graph, seed, k) request
        # pinned to the first and last replica must equal the sequential
        # facade run exactly (the acceptance witness).
        probes = [
            fleet.submit(graphs[0], k, replica=r).result().partition
            for r in (0, P - 1)
        ]
        ab["identical_partition"] = bool(all(
            np.array_equal(p, ref_part) for p in probes
        ))
        # Preflight (unmeasured): pay first-touch traces on every replica,
        # then zero the measured window.
        for fut in [_submit_backpressured(fleet, g) for g in graphs]:
            fut.result()
        for r in fleet.replicas:
            r.stats_.reset()
        # Router counters are cumulative (probes + preflight + every
        # backpressure retry re-entering submit): snapshot here so the
        # ledger reports the measured burst's DELTA, not process totals.
        pre = fleet.stats()
        burst = _measure_burst(fleet)
        per_replica = []
        agg_occupancy = 0.0
        for i, r in enumerate(fleet.replicas):
            snap = r.stats_.snapshot()
            agg_occupancy += snap["batch_occupancy_max"]
            per_replica.append({
                "replica": i,
                "completed": snap["completed"],
                "batch_occupancy_mean": snap["batch_occupancy_mean"],
                "batch_occupancy_max": snap["batch_occupancy_max"],
                "lanestacked_batches": snap["lanestacked_batches"],
                "lanestacked_lanes": snap["lanestacked_lanes"],
                "inherited_cells": inherit[i]["inherited"],
                "local_cells": inherit[i]["local"],
            })
        fstats = fleet.stats()
        ab["fleet"] = {
            "warmup_s": round(fleet_warm_s, 2),
            "wall_s": burst["wall_s"],
            "throughput_gps": burst["throughput_gps"],
            "p50_ms": burst["p50_ms"],
            "p99_ms": burst["p99_ms"],
            "aggregate_occupancy": agg_occupancy,
            "steered": (
                sum(r["steered"] for r in fstats["per_replica"])
                - sum(r["steered"] for r in pre["per_replica"])
            ),
            "resteers": fstats["resteers"] - pre["resteers"],
            "sticky_hits": fstats["sticky_hits"] - pre["sticky_hits"],
            "rejected_full": fstats["rejected_full"] - pre["rejected_full"],
            "inherited_cells": inherited_total,
            "per_replica": per_replica,
        }
        # Elastic exercise (ISSUE 15): one scale-down/scale-up cycle on
        # the live fleet — the drained replica's work resteers, the
        # revived slot restarts warm — so the fleet_scale_* ledger keys
        # measure a REAL drain/revive, not untouched zeros.
        if P >= 2:
            t0 = time.perf_counter()
            fleet.scale_to(P - 1, reason="bench elastic cycle")
            down_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            fleet.scale_to(P, reason="bench elastic cycle")
            up_s = time.perf_counter() - t0
            sstats = fleet.stats()
            ab["fleet"]["scale"] = {
                "down_s": round(down_s, 3),
                "up_s": round(up_s, 3),
                "active_after": sstats["active_replicas"],
                **{key: val for key, val in sstats.items()
                   if key.startswith("fleet_scale_")},
            }
    finally:
        fleet.shutdown(drain=True)

    record["fleet_ab"] = ab
    # Standalone child runs feed the ledger directly (tools ledger
    # append): tag the backend so baseline windows stay comparable.
    record.setdefault("backend", backend)
    # Flat ledger keys under the regress sentinel (telemetry/ledger
    # direction markers: _gps/_vs_/_ratio up, _ms/_s/count down).
    record.update({
        "fleet_single_gps": ab["single"]["throughput_gps"],
        "fleet_agg_gps": ab["fleet"]["throughput_gps"],
        "fleet_vs_single": round(
            ab["fleet"]["throughput_gps"]
            / max(ab["single"]["throughput_gps"], 1e-9), 2
        ),
        "fleet_p50_ms": ab["fleet"]["p50_ms"],
        "fleet_p99_ms": ab["fleet"]["p99_ms"],
        "fleet_aggregate_occupancy": ab["fleet"]["aggregate_occupancy"],
        "fleet_resteer_count": ab["fleet"]["resteers"],
        "fleet_identical": int(ab["identical_partition"]),
        "fleet_inherit_ratio": round(
            inherited_total / max(report_total, 1), 3
        ),
        "fleet_warmup_s": ab["fleet"]["warmup_s"],
    })
    # Elastic-cycle ledger keys (ISSUE 15): scale walls + the census the
    # cycle produced (retire/revive counts; zero lost resolutions is
    # asserted by the test matrix, the bench records the cost).
    if "scale" in ab["fleet"]:
        record.update({
            "fleet_scale_down_s": ab["fleet"]["scale"]["down_s"],
            "fleet_scale_up_s": ab["fleet"]["scale"]["up_s"],
            "fleet_scale_retires": ab["fleet"]["scale"]["fleet_scale_retires"],
            "fleet_scale_revives": ab["fleet"]["scale"]["fleet_scale_revives"],
        })
    print(json.dumps(record, default=str), flush=True)
    return record


def _merge_child_phase(rec: dict, phase: str, sentinel: str, prefix: str,
                       *, echo: bool = False) -> None:
    """Run one bench phase in its own child process and merge its
    ``prefix``-keyed results into ``rec`` — shard_ab and fleet_ab both
    need their own device topology (P virtual CPU devices for the dryrun,
    KPTPU_BENCH_*_NATIVE=1 keeps a real mesh), so they never run in this
    process.  ``sentinel`` gates success and names the error key; the
    timeout rides KPTPU_BENCH_<PHASE>_TIMEOUT."""
    timeout = float(
        os.environ.get(f"KPTPU_BENCH_{phase.upper()}_TIMEOUT", 900)
    )
    child_rec, child_err = _run_child(timeout, extra_env={
        "KPTPU_BENCH_PHASE": phase,
    })
    if child_rec and sentinel in child_rec:
        for key, val in child_rec.items():
            if key.startswith(prefix):
                rec[key] = val
        if echo:
            print(json.dumps(rec), flush=True)
    else:
        rec[f"{sentinel}_error"] = (
            child_err or f"{phase} phase produced no record"
        )


def run_benchmark() -> dict:
    """Phases 1-4 in this process (the shard/fleet phases need their own
    device topology and run in children, see :func:`_merge_mesh_phases`).
    Returns the headline record (the ledger entry's source)."""
    record = run_lp_phase()
    if os.environ.get("KPTPU_BENCH_FULL", "1") == "1":
        record = run_full_phase(record)
    if os.environ.get("KPTPU_BENCH_SERVE", "1") == "1":
        record = run_serve_phase(record)
    if os.environ.get("KPTPU_BENCH_COMPRESS", "1") == "1":
        try:
            record = run_compress_phase(record)
        except Exception as exc:  # noqa: BLE001 — A/B must not void phases 1-3
            record["compress_ab_error"] = f"{type(exc).__name__}: {exc}"[:300]
    return record


def _ledger_record(rec: dict | None, kind: str = "bench") -> None:
    """Append the run's compact summary to RUNS.jsonl (round 13; see
    telemetry/ledger.py).  Called only at the parent's terminal points so
    child re-runs cannot double-append; failures never void the record."""
    if not rec:
        return
    try:
        from kaminpar_tpu.telemetry import ledger

        ledger.record_run(
            rec, kind=kind, git_head=rec.get("git_head") or _git_head()
        )
    except Exception:  # noqa: BLE001
        pass


def _git_head() -> str:
    # Round 20: one resolver (env override -> rev-parse -> ""), cached
    # per process, shared with every ledger entry writer.
    from kaminpar_tpu.telemetry.ledger import resolve_git_head

    return resolve_git_head()


def _salvage(stdout: str) -> dict | None:
    """Last complete JSON object the child flushed, if any."""
    best = None
    for line in stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                best = json.loads(line)
            except ValueError:
                pass
    return best


def _run_child(timeout_s: float, extra_env: dict | None = None) -> tuple[dict | None, str]:
    """Run the benchmark in a killable subprocess on the ambient backend.

    Own process group so a timeout kill reaches any helper the child
    forked (a helper that inherits the pipes would otherwise leave
    communicate() blocked on pipe EOF forever).  Returns the salvaged
    headline record (or None) and an error string ('' = clean)."""
    env = dict(os.environ)
    env.update(extra_env or {})
    # Flight recorder (ISSUE 12): every killable bench child heartbeats to
    # its own sidecar with a stack dump armed just under the kill timeout,
    # so a timeout kill yields a dossier (phase + stack tail) instead of a
    # bare "killed after N s".  The sidecar env contract is single-sourced
    # in telemetry/flight_recorder.child_sidecar_env.
    from kaminpar_tpu.telemetry import flight_recorder

    phase_tag = (extra_env or {}).get("KPTPU_BENCH_PHASE", "bench")
    fr_env, hb_path, stack_path = flight_recorder.child_sidecar_env(
        os.path.join(REPO, f".bench_child_{phase_tag}"), timeout_s
    )
    env.update(fr_env)
    try:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child"],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            start_new_session=True,
            env=env,
        )
    except Exception as exc:  # noqa: BLE001
        return None, f"{type(exc).__name__}: {exc}"[:500]
    dossier = None
    try:
        out, errout = proc.communicate(timeout=timeout_s)
        err = ""
        if proc.returncode != 0:
            err = (errout.strip().splitlines() or ["child failed"])[-1][:500]
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        out, errout = proc.communicate()
        err = f"benchmark child killed after {timeout_s:.0f}s"
        try:
            dossier = flight_recorder.read_dossier(hb_path, stack_path)
        except Exception:  # noqa: BLE001 — forensics must not mask the kill
            dossier = None
        if dossier is not None:
            err += (f" (phase={dossier.get('phase')} "
                    f"class={dossier.get('phase_class')})")
    flight_recorder.cleanup_sidecars(hb_path, stack_path)
    rec = _salvage(out or "")
    if rec is not None and err:
        rec["note"] = err  # partial result: headline phase finished, later phase cut off
        if dossier is not None:
            rec["kill_dossier"] = dossier
        err = ""
    return rec, err


def _require_tpu() -> None:
    """A measurement that finds no TPU fails; it never falls back to the
    CPU (CPU runs are asked for with ``JAX_PLATFORMS=cpu``)."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"bench: needs a TPU (JAX found {platform!r}); "
                 "set JAX_PLATFORMS=cpu for a CPU run")


def _merge_mesh_phases(rec: dict) -> None:
    """Phases 5/6 (shard_ab / fleet_ab), each in its own child with its
    own device topology."""
    if os.environ.get("KPTPU_BENCH_SHARD", "1") == "1":
        _merge_child_phase(rec, "shard", "shard_ab", "shard_ab", echo=True)
    if os.environ.get("KPTPU_BENCH_FLEET", "1") == "1":
        _merge_child_phase(rec, "fleet", "fleet_ab", "fleet_", echo=True)


def main() -> None:
    if "--child" in sys.argv:
        # Flight recorder (ISSUE 12): heartbeat + armed stack dump when the
        # parent configured sidecars (bench _run_child does).
        try:
            from kaminpar_tpu.telemetry import flight_recorder

            flight_recorder.arm_from_env()
        except Exception:  # noqa: BLE001 — forensics must not break the child
            pass
        phase = os.environ.get("KPTPU_BENCH_PHASE")
        if phase == "shard":
            # The 8-device CPU-mesh dryrun (ISSUE 11): force the virtual
            # mesh BEFORE the backend initializes, unless the caller pinned
            # the ambient multi-chip mesh (KPTPU_BENCH_SHARD_NATIVE=1).
            if os.environ.get("KPTPU_BENCH_SHARD_NATIVE") != "1":
                from kaminpar_tpu.utils.platform import force_cpu_devices

                force_cpu_devices(int(os.environ.get("KPTPU_BENCH_SHARD_P", 8)))
            run_shard_phase()
            return
        if phase == "fleet":
            # The P-replica fleet dryrun (ISSUE 14): same virtual-mesh
            # forcing contract as the shard phase.
            if os.environ.get("KPTPU_BENCH_FLEET_NATIVE") != "1":
                from kaminpar_tpu.utils.platform import force_cpu_devices

                force_cpu_devices(int(os.environ.get("KPTPU_BENCH_FLEET_P", 8)))
            run_fleet_phase()
            return
        _require_tpu()
        run_benchmark()
        return
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # Explicitly CPU-pinned environment (tests/CI): measure in-process.
        from kaminpar_tpu.utils.platform import force_cpu_devices

        force_cpu_devices(1)
        rec = run_benchmark()
        _merge_mesh_phases(rec)
        rec.setdefault("git_head", _git_head())
        rec.setdefault("stale_vs_head", False)  # measured at head, in-process
        _ledger_record(rec)
        return
    # On the chip this parent never initializes a backend: a chip belongs
    # to one process at a time, so each child in turn holds it alone.
    timeout_s = float(os.environ.get("KPTPU_BENCH_TIMEOUT", 900))
    rec, err = _run_child(timeout_s)
    if rec is None:
        sys.exit(f"bench: {err}")
    _merge_mesh_phases(rec)
    rec.setdefault("git_head", _git_head())
    rec.setdefault("stale_vs_head", False)  # child measured at head
    print(json.dumps(rec))
    _ledger_record(rec)


if __name__ == "__main__":
    main()
