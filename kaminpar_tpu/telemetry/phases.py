"""Canonical phase-name registry (ISSUE 5 satellite).

One list of phase names shared by the timer tree (``utils/timer.scoped_timer``
pushes these as sync-accounting phases), :mod:`utils.sync_stats` (budget
assertions key on them), and the telemetry trace (spans and per-level quality
probes carry them).  Before this registry existed a misspelled phase name
silently escaped the sync budget: a budget assertion against a typo'd phase
counts a phase nobody ever pushed and trivially passes.  Now

- :func:`check` warns (once per process per name) when a scope opens under an
  unregistered name, and
- a tier-1 test (tests/test_telemetry.py) statically scans the source tree
  for phase-name literals and fails on any drift in either direction.
"""

from __future__ import annotations

import warnings

# The partitioning spine's phases — every scoped_timer scope in the library
# uses one of these names (reference: the timer-tree keys of
# kaminpar-shm/kaminpar.cc's TIME lines).
CORE_PHASES = (
    "partitioning",
    "coarsening",
    "lp_clustering",
    "hem_clustering",
    "initial_partitioning",
    "extend_partition",
    "uncoarsening",
    "lp_refinement",
    "clp_refinement",
    "fm_refinement",
    "jet_refinement",
    "overload_balancer",
    "underload_balancer",
    # distributed tier (dist/partitioner.py)
    "dist_coarsening",
    "dist_initial_partitioning",
    "dist_uncoarsening",
    # dist refinement drive (round 13): balancer/LP/CLP/JET convergence
    # pulls budget separately from the uncoarsening spine, mirroring the
    # shm split between "uncoarsening" and the per-refiner phases.
    "dist_refinement",
)

# Phases pushed outside the spine: serve-runtime internals and the bench
# driver's measurement fences.
AUX_PHASES = (
    "serve_batch_metrics",  # serve/batching.py packed-metrics readback
    "lp_bench_fence",       # bench.py microbench sync fences
    "untracked",            # sync_stats' default phase for unscoped pulls
    "lp_stats",             # ops/lp.lp_stats_snapshot's LP round-count readback
    # Lane-stacked serve execution (round 11, serve/lanestack.py): the
    # stacked pipeline's scope plus the phase keys its lane-accounted
    # stacked readbacks are counted under (one stacked pull serves the
    # whole lane stack; sync_stats records lanes per pull).
    "serve_lanestack",
    "lanestack_coarsening",
    "lanestack_ip",
    "lanestack_refinement",
    "lanestack_extend",
    # Dist-tier helper readbacks (round 12, kptlint sync-discipline): the
    # previously un-counted np.asarray sites in dist/{metrics,debug,
    # shard_stats,graph,bfs_extractor}.py now route through sync_stats.pull
    # under these phases, so the future sharded pipeline inherits accounted
    # transfers (ROADMAP item 1's per-shard accounting extends them).
    "dist_build",       # host->device staging views during DistGraph build
    "dist_metrics",     # cut/block-weight reductions pulled for reporting
    "dist_validation",  # debug.validate_partition consistency sweeps
    "dist_stats",       # shard_stats work-table collection
    "dist_extract",     # BFS-ball subgraph extraction readbacks
    "serve_pack",       # batching.pack_graphs per-member CSR readbacks
    # Compressed-graph device pipeline (round 14, ISSUE 10): view
    # construction (host pack -> device put, zero pulls — asserted with a
    # 0 budget in deep.py) and the finest-level device re-materialization
    # at final uncoarsening (one decode dispatch, zero pulls — asserted).
    "compressed_build",
    "compressed_decode",
    # Sharded compressed tier (round 15, ISSUE 11; dist/device_compressed.py):
    # the dist twins of the two phases above — per-shard view construction
    # (one host decode per shard for ghost routing + device puts, zero
    # pulls — asserted with a 0 budget in dist/partitioner.py) and the
    # per-level dense materialization at uncoarsening (one sharded decode
    # dispatch, zero pulls — asserted).
    "dist_compressed_build",
    "dist_compressed_decode",
    # Executable-grade observability (round 16, ISSUE 12): the serve
    # engine's HBM admission preflight (pure host arithmetic over the
    # request's shape cell — a pull here is a contract violation and would
    # be attributed loudly) and the flight recorder's heartbeat thread
    # (reads phase boards + /proc, never the device).
    "capacity_preflight",
    "heartbeat",
    # Mesh-replicated serve fleet (round 18, serve/fleet.py): the router's
    # steering decision — pure host arithmetic over the replicas' live
    # serving signals (queue drain estimate, p99 execute, open breakers,
    # capacity verdict); a pull under this phase is a contract violation
    # and would be attributed loudly.
    "fleet_steer",
    # Preemption-tolerant execution (round 19, ISSUE 15).
    # checkpoint_write: the deep pipeline's level-boundary snapshots —
    # each NEW coarse level's CSR arrays are pulled exactly once (cached
    # host-side thereafter) plus one partition pull per uncoarsening
    # boundary; deep.py asserts the writer's exact pull budget in-pipeline
    # and ZERO pulls when checkpointing is disarmed.
    # checkpoint_restore: resume-side hierarchy rebuild — host->device
    # puts only, zero pulls (asserted).
    "checkpoint_write",
    "checkpoint_restore",
    # Crash-safe serve journal (serve/journal.py): journal_write covers
    # the admit-side graph serialization (ONE counted bulk pull per
    # journaled admission via graph_to_host); journal_replay covers the
    # restart-side replay enqueue (decode + host->device puts, zero
    # pulls).
    "journal_write",
    "journal_replay",
    # Request-scoped tracing + SLO accounting (round 20, ISSUE 20;
    # telemetry/{reqtrace,slo}.py).  Both phases are pure host work —
    # reqtrace_export renders a finished request's event chain onto a
    # Chrome-trace lane / builds an explain() dossier; slo_eval scans the
    # burn tracker's event ring for stats()/metrics.  A pull under either
    # is a contract violation (request tracing adds ZERO blocking
    # transfers by construction — the armed budget suites assert it).
    "reqtrace_export",
    "slo_eval",
)

KNOWN_PHASES = frozenset(CORE_PHASES + AUX_PHASES)

_warned: set = set()


def is_known(name: str) -> bool:
    return name in KNOWN_PHASES


def check(name: str) -> bool:
    """Warn once per process about an unregistered phase name (tests and
    ad-hoc scopes are allowed to use arbitrary names — the warning exists so
    a misspelled *library* phase cannot silently escape the sync budget;
    library-side drift additionally fails the static registry test)."""
    if name in KNOWN_PHASES:
        return True
    if name not in _warned:
        _warned.add(name)
        warnings.warn(
            f"kaminpar_tpu: timer phase {name!r} is not in the canonical "
            "phase registry (kaminpar_tpu/telemetry/phases.py) — sync-budget "
            "assertions and telemetry dashboards key on registered names",
            RuntimeWarning,
            stacklevel=3,
        )
    return False
