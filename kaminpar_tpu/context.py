"""Configuration tree for the TPU-native partitioner.

Mirrors the reference's nested plain-struct ``Context``
(``/root/reference/include/kaminpar-shm/kaminpar.h:610-622`` and the enums at
``kaminpar.h:66-605``): one dataclass per subsystem, presets construct the tree
fully in code (see :mod:`kaminpar_tpu.presets`), and the CLI binds flags
directly onto the fields.  Unlike the reference we keep the tree small and add
TPU-specific knobs (index dtype, device mesh shape) instead of TBB/NUMA ones.
"""

from __future__ import annotations

import dataclasses
import enum
import json
from dataclasses import dataclass, field
from typing import Optional


class PartitioningMode(enum.Enum):
    """Orchestration scheme (reference: ``PartitioningMode``, kaminpar.h:66)."""

    DEEP = "deep"
    RB = "rb"
    KWAY = "kway"
    VCYCLE = "vcycle"


class ClusteringAlgorithm(enum.Enum):
    """Coarsening clusterer (reference: ``ClusteringAlgorithm``)."""

    NOOP = "noop"
    LP = "lp"
    HEM = "hem"


class DistClusteringAlgorithm(enum.Enum):
    """Distributed coarsening clusterer (reference: dist
    ClusteringAlgorithm, dkaminpar.h:73-78)."""

    GLOBAL_LP = "global-lp"
    # Shard-local clusters only: exchange-free, conflict-free rounds
    # (local_lp_clusterer.cc); never merges across shard boundaries.
    LOCAL_LP = "local-lp"
    # LOCAL_LP rounds first, then GLOBAL_LP rounds on what remains — the
    # cheap-first pairing the reference uses LOCAL_LP for.
    LOCAL_GLOBAL_LP = "local-global-lp"
    # Handshake heavy-edge matching across shards (hem_clusterer.cc; pairs
    # may span shards — dist/hem.py).
    GLOBAL_HEM = "global-hem"
    # HEM pass first, then GLOBAL_LP growing the matched pairs
    # (hem_lp_clusterer.cc).
    GLOBAL_HEM_LP = "global-hem-lp"


class RefinementAlgorithm(enum.Enum):
    """Refiners composable into a pipeline (reference: ``RefinementAlgorithm``)."""

    NOOP = "noop"
    LP = "lp"
    CLP = "clp"  # colored LP
    JET = "jet"
    KWAY_FM = "kway-fm"
    OVERLOAD_BALANCER = "overload-balancer"
    UNDERLOAD_BALANCER = "underload-balancer"
    GREEDY_BALANCER = "greedy-balancer"  # alias used by some presets


class InitialPartitioningMode(enum.Enum):
    SEQUENTIAL = "sequential"


class TieBreakingStrategy(enum.Enum):
    """LP tie-breaking (reference: ``TieBreakingStrategy``, kaminpar.h).

    LIGHTEST is TPU-specific: among equally-rated clusters prefer the one
    with the lowest current weight (then random).  On unweighted geometric
    graphs integer ratings tie constantly and uniform tie-breaking lets a
    few clusters snowball; biasing toward the lighter cluster grows
    rounder, evenly-sized clusters (the size-constrained-LP idea)."""

    UNIFORM = "uniform"
    GEOMETRIC = "geometric"
    LIGHTEST = "lightest"


class ClusterWeightLimit(enum.Enum):
    """Max-cluster-weight formula (reference: coarsening/max_cluster_weights.h)."""

    EPSILON_BLOCK_WEIGHT = "epsilon-block-weight"
    BLOCK_WEIGHT = "block-weight"
    ONE = "one"
    ZERO = "zero"


@dataclass
class LabelPropagationContext:
    """Knobs of the LP engine (reference: ``LabelPropagationCoarseningContext``
    / ``LabelPropagationRefinementContext``, and the CRTP config block at
    ``kaminpar-shm/label_propagation.h:36-74``)."""

    num_iterations: int = 5
    # LP round kernel backend: "xla" (the gather/sort-reduce/segment-sum
    # lowering), "pallas" (the fused gather+rate+commit kernels in
    # ops/pallas_lp.py; off-TPU they run in interpret mode and produce
    # bit-identical results), or "auto" (pallas on TPU backends, xla
    # elsewhere).  One knob serves both the clusterer and the refiner —
    # the same two kernels implement both instantiations.
    lp_kernel: str = "xla"
    # Nodes with degree above this are handled by the dedicated high-degree
    # (edge-parallel) path; mirrors the two-phase threshold of 10k at
    # label_propagation.h:62.
    large_degree_threshold: int = 1_000_000
    max_num_neighbors: int = -1  # -1 = unlimited
    tie_breaking: TieBreakingStrategy = TieBreakingStrategy.UNIFORM
    # Stop sweeping early once fewer than this fraction of nodes moved.
    min_moved_fraction: float = 0.001
    # Cluster isolated nodes together at the end of coarsening LP
    # (reference: label_propagation.h:872-917).
    cluster_isolated_nodes: bool = True
    # Match otherwise-unmergeable singleton clusters through their favored
    # cluster (reference two-hop clustering, label_propagation.h:919-1120).
    cluster_two_hop_nodes: bool = True
    # Fraction of nodes allowed to move per synchronous round — the
    # bulk-synchronous analog of the reference's chunked rounds; < 1 breaks
    # Jacobi-LP swap cycles (see ops/lp.py:_commit_moves).
    active_prob: float = 1.0
    # Accept zero-gain moves with probability 1/2 (the reference LP
    # refiner's tie behavior, lp_refiner.cc:258-260); requires
    # active_prob < 1 to stay oscillation-safe under synchronous commits.
    allow_tie_moves: bool = False
    # Low-degree boost (round-3 A/B, BASELINE_measured.md): synchronous LP
    # propagates labels one hop per sweep, so sparse graphs (grids, roads)
    # converge slower per sweep than dense ones — measured grid256 k=64
    # ratio 1.46 -> 1.20 at 3x sweeps, while 2x sweeps *hurt* dense
    # geometric rgg64k (1.26 -> 1.39).  Levels with avg degree below the
    # threshold get factor x num_iterations.
    low_degree_boost_threshold: float = 8.0
    low_degree_boost_factor: int = 3
    # Weighted-graph clustering mode (graphs with non-uniform edge weights;
    # see lp_clusterer.py): emulate asynchronous incremental growth with a
    # small active fraction and proportionally more sweeps.  Measured on
    # road512 (round 4): coarse-space bisection optimum 2.0x -> 1.07x of
    # the fine-space optimum.  Replaces the low-degree boost on this class.
    weighted_active_prob: float = 0.1
    weighted_sweep_factor: int = 6
    # None = auto-detect from the coarsener's input graph.  The facade pins
    # this to the *user's* graph before partitioning so nested extension
    # pipelines (whose subgraphs carry accumulated weights even for
    # unweighted inputs) inherit the right mode.
    weighted_mode: object = None


@dataclass
class SparsificationContext:
    """Threshold edge sparsification after contraction (reference:
    ``SparsificationClusterCoarseningContext`` + the threshold-sparsifying
    coarsener, sparsification_cluster_coarsener.cc:42-228, ESA'25): keep
    the target_m heaviest coarse edges (ties sampled by a symmetric hash so
    both directions agree), bounding per-level work for worst-case
    linear-time coarsening.  Defaults = reference presets.cc:172-177."""

    enabled: bool = False
    density_target_factor: float = 0.5
    edge_target_factor: float = 0.5
    laziness_factor: float = 4.0


@dataclass
class CoarseningContext:
    """Reference: ``CoarseningContext`` (kaminpar.h) + max_cluster_weights.h."""

    algorithm: ClusteringAlgorithm = ClusteringAlgorithm.LP
    lp: LabelPropagationContext = field(
        default_factory=lambda: LabelPropagationContext(active_prob=0.5)
    )
    # Coarsen until n <= contraction_limit * k (kway) or 2*contraction_limit
    # (deep); reference default C = 2000 (deep_multilevel.cc:170-183).
    contraction_limit: int = 2000
    # Bound per-level shrink: cluster weight additionally capped at
    # max_shrink_factor * average node weight (0 disables).  See
    # cluster_coarsener.coarsen_once for why synchronous LP needs this.
    max_shrink_factor: float = 3.5
    # Stop coarsening when a level shrinks by less than this factor
    # (reference: convergence_threshold).
    convergence_threshold: float = 0.05
    cluster_weight_limit: ClusterWeightLimit = ClusterWeightLimit.EPSILON_BLOCK_WEIGHT
    cluster_weight_multiplier: float = 1.0
    # Overlay clustering (reference: overlay_cluster_coarsener.cc, ESA'25):
    # intersect this many independent LP clusterings; two nodes share an
    # overlay cluster only if every run agrees.  Slower shrink per level,
    # rounder clusters (variance of any single randomized run cancels).
    # <= 1 disables.
    overlay_levels: int = 1
    sparsification: SparsificationContext = field(
        default_factory=SparsificationContext
    )
    # Distributed clusterer selection (dist ClusteringAlgorithm,
    # dkaminpar.h:73-78).
    dist_clustering: DistClusteringAlgorithm = DistClusteringAlgorithm.GLOBAL_LP


@dataclass
class InitialPartitioningContext:
    """Reference: ``InitialPartitioningContext`` — pool of sequential flat
    bipartitioners + 2-way FM (initial_pool_bipartitioner.cc:24)."""

    mode: InitialPartitioningMode = InitialPartitioningMode.SEQUENTIAL
    # Bipartitioning-pool backend (round 9, ISSUE 4): "host" = the
    # sequential NumPy pool + mini-multilevel below (the reference-faithful
    # oracle), "device" = every repetition as a vmapped lane of the JAX pool
    # (ops/bipartition.py; one blocking readback per bisection, per-lane
    # streams from utils/rng.lane_keys), "auto" = device on accelerator
    # backends, host on CPU.  The host pool stays the fallback: a device
    # dispatch failure falls back per bisection instead of aborting.
    # KAMINPAR_TPU_IP_BACKEND overrides.
    ip_backend: str = "auto"
    # Spend the imbalance budget evenly across bisection levels (reference:
    # use_adaptive_epsilon / create_twoway_context, helper.cc:103-130).
    use_adaptive_epsilon: bool = True
    # Number of repetitions of each enabled flat bipartitioner.
    min_num_repetitions: int = 4
    max_num_repetitions: int = 12
    num_seed_iterations: int = 1
    use_adaptive_bipartitioner_selection: bool = True
    enable_bfs_bipartitioner: bool = True
    enable_ggg_bipartitioner: bool = True
    enable_random_bipartitioner: bool = True
    # 2-way FM refinement of each bipartition.
    fm_num_iterations: int = 5
    fm_alpha: float = 1.0  # adaptive stopping alpha (Osipov/Sanders)
    # Sequential mini-multilevel inside each bisection (reference:
    # initial_multilevel_bipartitioner.cc:67-74, C=20).
    coarsening_contraction_limit: int = 20
    # Shrink factor below which IP coarsening is considered converged
    # (reference: InitialCoarseningContext::convergence_threshold = 0.05).
    coarsening_convergence_threshold: float = 0.05
    # Extension splits into >= 4 parts on subgraphs at least this large use
    # a nested (device) deep pipeline instead of chained host bisections —
    # measured stronger on dense geometric graphs (extend_partition).
    nested_extension_n: int = 4096
    # Independent nested attempts per extension block; best cut wins.
    # Round-2 measured on rgg64k k=64: reps=2 cuts seed variance ~4x
    # (spread 8.9k -> 1.9k) at unchanged mean; round-3 on grid256 k=64 it
    # moves the default-tier mean 1.38 -> 1.24 over seeds {1,2,3}
    # (QUALITY_NOTES.md) — bad extension splits were the variance source.
    # Cost: ~+20% wall on mesh configs.  Default 2 since round 3.
    nested_extension_reps: int = 2
    # Up to this finest-graph size, also run the flat pool on the finest
    # graph and keep the better of {mini-ML, flat} — measured divergence
    # from the reference (which always uses ML): on expander-like coarse
    # graphs (RMAT) flat pool+FM beats the projected ML partition, while
    # on geometric/mesh graphs ML wins; best-of is cheap at this size.
    flat_pool_fallback_n: int = 2048
    # Device-side extension (round 5, VERDICT r4 missing #4): on graphs at
    # least device_extension_n nodes, extension runs ONE restricted nested
    # multilevel batched over all blocks (partitioning/extension.py) instead
    # of host per-block subgraph pipelines.  The host only sees the nested
    # coarsest graph (~device_extension_cpb coarse nodes per new block).
    device_extension: bool = False
    device_extension_n: int = 1 << 15
    device_extension_cpb: int = 320
    # Independent device-extension attempts, best full-graph cut wins
    # (extension variance was the rgg64k plateau driver; same rationale as
    # nested_extension_reps on the host path).
    device_extension_reps: int = 1


@dataclass
class JetContext:
    """Reference: ``JetRefinementContext`` (refinement/jet/jet_refiner.cc)."""

    # Number of full JET invocations chained per refinement step (reference:
    # create_jet_context(num_rounds), presets.cc "jet"/"4xjet").
    num_rounds: int = 1
    num_iterations: int = 12
    num_fruitless_iterations: int = 12
    fruitless_threshold: float = 0.999
    # Negative-gain filter temperatures on fine/coarse levels
    # (reference: jet_refiner.cc fine/coarse temperature schedule).
    initial_gain_temp_on_fine_level: float = 0.25
    final_gain_temp_on_fine_level: float = 0.25
    initial_gain_temp_on_coarse_level: float = 0.75
    final_gain_temp_on_coarse_level: float = 0.75


@dataclass
class BalancerContext:
    max_num_rounds: int = 8


@dataclass
class ColoredLPContext:
    """Colored LP refiner parameters (reference: ``ColoredLPRefinementContext``,
    clp_refiner.cc)."""

    num_iterations: int = 2
    # Zero-gain moves are oscillation-safe inside a color class (independent
    # set); they restore async-LP boundary diffusion.
    allow_tie_moves: bool = True
    # Same backend switch as LabelPropagationContext.lp_kernel — the CLP
    # superstep is the same fused round with a color-class mask.
    lp_kernel: str = "xla"


@dataclass
class FMContext:
    """k-way FM refiner parameters (reference: ``KwayFMRefinementContext``,
    presets.cc:348-365)."""

    num_iterations: int = 10
    alpha: float = 1.0  # adaptive stopping (Osipov/Sanders)
    num_fruitless_moves: int = 100
    abortion_threshold: float = 0.999
    # Border seeds consumed per localized search region (presets.cc:350).
    num_seed_nodes: int = 10
    # Deterministic per-pass work budget: a pass stops (after finishing its
    # current region) once the summed degree of popped nodes exceeds
    # factor * n.  Bounds the *sequential host* pass on dense graphs
    # (rgg64k: deg ~50 makes full-border passes ~30x a road pass for no
    # measured cut gain); the reference affords full passes because its
    # searches run on all cores.  <= 0 disables.
    pass_work_budget_factor: float = 32.0
    # TPU divergence: FM runs as a sequential host pass; JET is the at-scale
    # device refiner (see fm_refiner.py module docstring).  Below
    # ``dense_nk_threshold`` connection entries the pass uses a dense (n, k)
    # matrix (the reference's dense_gain_cache.h analog); above it, a lazily
    # materialized border-row table (sparse_gain_cache.h role) whose memory
    # scales with the border, so there is no n*k gate anymore (VERDICT r3
    # next #6).  ``max_n`` bounds the sequential pass wall-time only.
    max_n: int = 1 << 23
    dense_nk_threshold: int = 1 << 26


class MoveExecutionStrategy(enum.Enum):
    """Distributed LP move commitment (reference:
    LabelPropagationMoveExecutionStrategy, dkaminpar.h:116-120).
    LOCAL_MOVES is the bulk-synchronous analog of the reference's eager
    PE-local application: proposals ignore block caps and departures are
    credited to their block's capacity before arrivals are admitted
    (best-gain-first), so swaps between at-cap blocks stay reachable."""

    PROBABILISTIC = "probabilistic"
    BEST_MOVES = "best-moves"
    LOCAL_MOVES = "local-moves"


@dataclass
class RefinementContext:
    """Pipeline of refiners, run in order on every uncoarsening level
    (reference: MultiRefiner, factories.cc:97-147)."""

    dist_move_execution: MoveExecutionStrategy = MoveExecutionStrategy.PROBABILISTIC
    # Sub-rounds over disjoint hash-chunks of the nodes per dist LP round
    # (reference: dist lp_refiner.cc processes 8 chunks per round to bound
    # move staleness; commits happen between chunks).
    dist_num_chunks: int = 8
    algorithms: tuple = (
        RefinementAlgorithm.OVERLOAD_BALANCER,
        RefinementAlgorithm.LP,
    )
    # Strict-improvement LP (measured: bulk-synchronous zero-gain "tie"
    # moves *hurt* — simultaneous tie movers interact; async diffusion has
    # no safe sync analog here.  JET plays that role instead.)
    lp: LabelPropagationContext = field(
        default_factory=lambda: LabelPropagationContext(num_iterations=5)
    )
    jet: JetContext = field(default_factory=JetContext)
    balancer: BalancerContext = field(default_factory=BalancerContext)
    fm: FMContext = field(default_factory=FMContext)
    clp: ColoredLPContext = field(default_factory=ColoredLPContext)


@dataclass
class PartitionContext:
    """Target partition parameters (reference: ``PartitionContext``), filled in
    by ``setup`` once graph + k are known (kaminpar.cc:315-331)."""

    k: int = 2
    epsilon: float = 0.03
    # Minimum block-weight imbalance; 0 disables minimum weights (reference:
    # KaMinPar::set_uniform_min_block_weights, kaminpar.cc:266-269).
    min_epsilon: float = 0.0
    # Filled by setup():
    total_node_weight: int = 0
    max_block_weights: Optional[object] = None  # np.ndarray[k], set by setup()
    min_block_weights: Optional[object] = None  # np.ndarray[k] or None

    def setup(
        self, total_node_weight: int, k: int, epsilon: float, min_epsilon: float = 0.0
    ) -> None:
        import math

        import numpy as np

        self.k = int(k)
        self.epsilon = float(epsilon)
        self.min_epsilon = float(min_epsilon)
        self.total_node_weight = int(total_node_weight)
        perfect = (total_node_weight + k - 1) // k
        max_bw = int((1.0 + epsilon) * perfect)
        # Strict balance for unweighted graphs requires max >= perfect + max
        # node weight; the facade adjusts for node weights (kaminpar.cc).
        self.max_block_weights = np.full(k, max(max_bw, perfect + 1), dtype=np.int64)
        if min_epsilon > 0.0:
            # min_bw = ceil((1 - min_eps) * perfect) (context.cc:72-81),
            # clamped so k * min_bw <= W stays satisfiable (perfect is
            # already rounded up, so the raw formula can over-demand).
            min_bw = int(math.ceil((1.0 - min_epsilon) * perfect))
            min_bw = min(min_bw, total_node_weight // k)
            self.min_block_weights = np.full(k, min_bw, dtype=np.int64)
        else:
            self.min_block_weights = None


@dataclass
class ParallelContext:
    """TPU execution parameters (replaces the reference's thread counts)."""

    # Shape of the device mesh for the distributed tier; None = single chip.
    mesh_shape: Optional[tuple] = None
    mesh_axis_names: tuple = ("nodes",)
    # Degree-bucketed layout construction backend (graph/csr.py):
    # "host" = numpy over pulled CSR arrays (zero-copy on the CPU backend,
    # a full-graph device->host round trip per hierarchy level on an
    # accelerator); "device" = jitted gathers fed by the 12-int
    # degree histogram riding each contraction level's single batched
    # readback (no bulk transfer, a few small extra kernel shapes);
    # "auto" = device on accelerator backends, host on CPU.
    device_layout_build: str = "auto"
    # Profiling aid (utils/timer.py): make sync-eligible timer scopes block
    # on their sentinel so phase wall-clock measures compute, not dispatch.
    # Off by default — it serializes the async pipeline it measures.
    sync_timers: bool = False
    # Fleet placement (round 18, serve/fleet.py): index into jax.devices()
    # this engine's dispatches default to.  None = jax's own default (device
    # 0).  The EngineRuntime activation wraps jax.default_device around the
    # owning engine's pipeline runs, so N single-device engine replicas in
    # one process land on N distinct mesh devices (arrays stay uncommitted —
    # placement steers dispatch, it never forbids a transfer).  On the CPU
    # backend the "devices" are the forced virtual host devices (the same
    # dryrun substrate the shard_ab bench uses), which SERIALIZE — see
    # TPU_NOTES round 18 for what a CPU fleet number does and does not claim.
    placement_device: Optional[int] = None


# ---------------------------------------------------------------------------
# Per-engine runtime ownership.
#
# The layout-build / sync-timer / placement settings used to be first-wins
# process globals: the first facade or engine won, and a second one with a
# conflicting context silently inherited its behavior.  `EngineRuntime`
# replaces them with *ownership*: every facade/engine owns a runtime derived
# from its own ParallelContext and activates it (thread-locally) around its
# pipeline runs, so two engines with different configs coexist and each
# one's dispatches see its own settings.  The compilation cache is not among
# them: its directory is a deployment setting fixed once at import
# (kaminpar_tpu/__init__.py).
# ---------------------------------------------------------------------------

import threading as _threading
from contextlib import contextmanager as _contextmanager

_tls_runtime = _threading.local()


def current_runtime() -> "Optional[EngineRuntime]":
    """The :class:`EngineRuntime` active on this thread (innermost
    activation), or None outside any activation."""
    stack = getattr(_tls_runtime, "stack", None)
    return stack[-1] if stack else None


@dataclass(frozen=True)
class EngineRuntime:
    """Per-engine ownership of the layout / sync-timer / placement settings
    (the knobs that used to be first-wins process globals).

    Built from a :class:`ParallelContext` by the facade or engine that owns
    the pipeline, and *activated* (a thread-local stack, so nested runs and
    concurrent engine dispatcher threads stay independent) around every
    pipeline run:

    - **layout build**: ``graph.csr.resolve_layout_build_mode`` consults the
      active runtime before the process default, so graphs built inside an
      activation use this engine's builder even without a per-graph pin.
    - **sync timers**: ``scoped_timer(..., sync=True)`` blocks per the
      active runtime's flag, not a global switch.
    - **placement**: the runtime's device index becomes jax's default
      device for the activating thread.
    """

    layout_build: str = "auto"
    sync_timers: bool = False
    # Fleet placement (serve/fleet.py): jax.devices() index the activation
    # pins as jax.default_device for this thread; None = backend default.
    device_index: Optional[int] = None

    @classmethod
    def from_parallel(cls, parallel: "ParallelContext") -> "EngineRuntime":
        return cls(
            layout_build=parallel.device_layout_build,
            sync_timers=bool(parallel.sync_timers),
            device_index=parallel.placement_device,
        )

    @_contextmanager
    def activate(self):
        """Context manager making this runtime current on this thread (and,
        with a device index, pinning jax's default device) until exit."""
        stack = getattr(_tls_runtime, "stack", None)
        if stack is None:
            stack = _tls_runtime.stack = []
        stack.append(self)
        device_ctx = None
        if self.device_index is not None:
            # Fleet placement: pin jax's (thread-local) default device so
            # this runtime's dispatches land on its replica's mesh device.
            # Arrays stay uncommitted — a graph buffer created under another
            # replica's activation is transferred, never rejected — so
            # replicas may legally share input graphs.
            try:
                import jax

                devs = jax.devices()
                device_ctx = jax.default_device(
                    devs[self.device_index % len(devs)]
                )
                device_ctx.__enter__()
            except Exception:  # pragma: no cover — placement is a locality
                device_ctx = None  # optimization, never a correctness gate
        try:
            yield self
        finally:
            if device_ctx is not None:
                device_ctx.__exit__(None, None, None)
            stack.pop()


def propagate_runtime(fn):
    """Wrap a thread-pool worker so the *submitting* thread's active
    :class:`EngineRuntime` is re-activated inside the worker.

    Thread-local activation does not cross pool threads: a
    nested-extension worker resolving layout/sync settings would fall
    through to the process default even while its owning engine's
    activation is live on the dispatcher thread.  Every pool submission in
    the device-disciplined tier wraps its worker with this (the per-graph
    ``_layout_mode`` pins remain as belt-and-braces for graphs that outlive
    the activation).  No-op (returns ``fn`` unchanged) outside any
    activation."""
    rt = current_runtime()
    if rt is None:
        return fn

    def _wrapped(*args, **kwargs):
        with rt.activate():
            return fn(*args, **kwargs)

    return _wrapped


@dataclass
class ServeContext:
    """Knobs of the partition-serving runtime (:mod:`kaminpar_tpu.serve`).

    A :class:`~kaminpar_tpu.serve.PartitionEngine` owns one long-lived device
    context: it precompiles the executable set over the ``warm_ladder`` x
    ``warm_ks`` grid at startup, keeps workspaces device-resident between
    requests, and serves a bounded async queue with admission control,
    deadlines, and micro-batching of same-shape-cell requests."""

    # Node-count rungs to warm at startup (powers of two; each rung warms
    # its whole padded bucket chain by running one synthetic partition).
    warm_ladder: tuple = (256, 1024)
    # k values to warm per rung.
    warm_ks: tuple = (8,)
    # Edge factor of the synthetic (RMAT) warmup graphs.
    warm_edge_factor: int = 8
    # Max requests fused into one micro-batch (same (n-bucket, m-bucket, k)
    # shape cell only; see serve/batching.py).
    max_batch: int = 8
    # Admission bound of the request queue; submits beyond it are rejected
    # with a retry-after estimate (backpressure) instead of queueing without
    # limit.
    queue_bound: int = 64
    # After the first request of a batch arrives, wait up to this long for
    # more same-cell requests before dispatching the batch.
    batch_window_ms: float = 2.0
    # Default per-request deadline; 0 disables (requests wait forever).
    default_deadline_ms: float = 0.0
    # Graceful-shutdown budget: how long shutdown(drain=True) waits for the
    # queue to empty before giving up on the dispatcher thread.
    drain_timeout_s: float = 60.0
    # Lane-stacked batch execution (round 11, serve/lanestack.py): run a
    # whole same-cell micro-batch through the multilevel pipeline as ONE
    # vmapped program instead of once per graph.  "auto" lane-stacks
    # eligible batches of >= 2 requests; "on" additionally stacks
    # single-request batches (and makes fallbacks warn); "off" keeps the
    # per-graph loop.  KAMINPAR_TPU_LANE_STACK overrides.
    lane_stack: str = "auto"
    # Lane counts to warm the lane-stacked pipeline at per (rung, k) cell
    # during startup warmup (kind="lanestack" warmup-report rows); empty
    # disables the pass (the per-graph warmup stays as is).
    warm_lanes: tuple = ()
    # HBM admission preflight (ISSUE 12; telemetry/capacity.py): "auto"
    # rejects a request whose predicted watermark exceeds the engine's
    # ceiling when a ceiling is known (explicit override below, measured
    # allocator limit, or the device-kind table — CPU without allocator
    # stats has none, so "auto" passes everything there); "off" disables.
    capacity_preflight: str = "auto"
    # Explicit admission ceiling in bytes; 0 = derive (allocator bytes_limit
    # when the backend exposes one, else the per-device-kind HBM table at
    # the planner's headroom).  Tests pin small values to force rejection.
    capacity_ceiling_bytes: int = 0
    # Crash-safe serve journal (round 19, serve/journal.py): append-only
    # JSONL path ("" = off; env KPTPU_SERVE_JOURNAL overrides).  Every
    # admitted request is journaled at admit (graph payload + params) and
    # again at resolution; a restarted engine replays unresolved entries
    # idempotently — restart mid-burst loses zero accepted requests — and
    # restores the warm state (warmup report, warm cells, breaker trips,
    # EMA seed) recorded alongside, so the replacement starts warm with a
    # zero warmup compile-event delta.
    journal_path: str = ""
    # fsync the journal every N appended records (durability vs latency;
    # the un-fsynced suffix is the crash-loss window — TPU_NOTES r19 on
    # what batched fsync does and does not guarantee).  Resolutions and
    # the warm-state record force an fsync regardless.
    journal_fsync_every: int = 8
    # -- SLO objectives (round 20, telemetry/slo.py) ------------------------
    # Declared service objectives; ALL default off (0.0), which disables
    # burn-rate accounting entirely.  When any is armed the engine keeps
    # rolling multi-window error budgets (slo_windows_s), exposes them in
    # stats()["slo"] + kaminpar_slo_* Prometheus families, and exports a
    # dimensionless pressure signal max(0, worst_burn - 1) that the fleet
    # steering score and the autoscaler consume.  Pressure is a control
    # input only — it never reaches the partitioning math, so partitions
    # stay bit-identical with SLOs armed or off (asserted in tests).
    #
    # Per-quality-tier latency targets in milliseconds (queue wait +
    # execute, i.e. the caller-observed service path of a completed
    # request); a completed request over its tier's target spends latency
    # error budget (budget = 1 - slo_availability, or 1% when no
    # availability objective is set).
    slo_strong_ms: float = 0.0
    slo_fast_ms: float = 0.0
    # Availability target as a fraction (e.g. 0.999): failed/expired
    # requests spend the (1 - target) error budget.
    slo_availability: float = 0.0
    # Tolerated capacity-reject rate as a fraction of submissions (e.g.
    # 0.01): typed CapacityError rejections beyond it burn budget.
    slo_capacity_reject_rate: float = 0.0
    # Rolling evaluation windows in seconds (fast burn / slow burn pair).
    slo_windows_s: tuple = (60.0, 600.0)


@dataclass
class FleetContext:
    """Knobs of the mesh-replicated serve fleet (round 18,
    :mod:`kaminpar_tpu.serve.fleet`).

    A :class:`~kaminpar_tpu.serve.fleet.PartitionFleet` owns N
    :class:`~kaminpar_tpu.serve.PartitionEngine` replicas — one per mesh
    device by default — and steers each request to a replica with an
    SLO-aware score over the replicas' live serving signals (queue drain
    estimate, p99 execute, open breakers, capacity-preflight verdict)
    instead of a single EMA.  Same-cell load fans in per replica up to the
    engine's ``max_batch`` (the lane axis) before spilling to the next
    replica (the device axis) — the lane x device 2D plane."""

    # Replica count; 0 = one per visible jax device (the whole local mesh).
    replicas: int = 0
    # Graph-id-sticky routing: a request carrying ``graph_id`` keeps landing
    # on the replica that first served that id while it stays healthy, so a
    # tenant's warm graph state (and, once incremental repartitioning
    # lands, its resident delta-graph) stays on one device.
    sticky_routing: bool = True
    # Steering-score weights: queue term (drain-time estimate of the
    # replica's queued work) and tail-latency term (p99 execute seconds).
    steer_queue_weight: float = 1.0
    steer_p99_weight: float = 1.0
    # Score bonus (in service-time units) for joining a replica's *forming*
    # same-cell batch (0 < same-cell depth < max_batch): fills the lane
    # axis to max_batch before spilling to the next device, maximizing
    # stacked occupancy.  >= (max_batch-1)/max_batch keeps a forming batch
    # preferred over an idle sibling until it is full.
    batch_join_bonus: float = 1.0
    # Floor for the per-request service-time estimate used by the steering
    # score and the fleet drain estimate before any EMA exists.
    steer_service_floor_s: float = 0.05
    # Cross-replica requeue budget per request: how many times a request
    # force-resolved by a draining/hung replica (typed EngineStoppedError /
    # WorkerHung / watchdog ExecuteFault) is resubmitted elsewhere before
    # the typed error surfaces to the caller.
    max_resteers: int = 2
    # Drain a replica automatically when its watchdog fires or when at
    # least ``auto_drain_open_cells`` of its cell breakers latch open
    # (0 disables auto-drain; ``drain_replica`` stays available).
    auto_drain: bool = True
    auto_drain_open_cells: int = 2
    # Fleet-scoped replica breaker: a drained replica re-admits one probe
    # request after this cooldown (restart + half-open, like every other
    # ladder rung).
    replica_cooldown_s: float = 30.0
    # Warm-cache inheritance: replica N+1 shares the fleet's persistent
    # compilation cache dir and imports the warmup report of the first
    # warmed replica, skipping every cell already traced (inherited vs
    # locally-compiled counts ride warmup_report and Prometheus).
    inherit_warm_cache: bool = True
    # Bounded per-replica drain budget used by drain_replica/shutdown.
    drain_timeout_s: float = 30.0
    # -- elastic scaling (round 19, ISSUE 15) -------------------------------
    # ``PartitionFleet.scale_to(N)`` adds/removes replicas under live
    # traffic: scale-up revives retired slots (warm state carries over)
    # before spawning fresh replicas (which inherit the fleet's warm
    # cache); scale-down retires the highest-index active replicas through
    # the PR 14 drain/resteer machinery — zero lost/duplicated resolutions
    # (asserted in tests/test_elastic.py).
    #
    # ``autoscale`` drives scale_to from the live steer signals: the mean
    # per-replica queue-drain estimate (depth x unamortized EMA /
    # max_batch) crossing the high watermark for ``autoscale_hysteresis``
    # consecutive health sweeps scales up one replica; staying under the
    # low watermark scales down one (never past the min/max bounds).
    autoscale: bool = False
    autoscale_min_replicas: int = 1
    autoscale_max_replicas: int = 8
    autoscale_high_s: float = 1.0
    autoscale_low_s: float = 0.05
    autoscale_hysteresis: int = 3
    # -- SLO pressure feedback (round 20, telemetry/slo.py) -----------------
    # Weight of a replica's SLO burn pressure in the steering score, in
    # service-time units per unit of excess burn: a replica burning its
    # error budget looks "slower" to the router and sheds new load to
    # healthier siblings.  Inert (term is 0) unless the engines' ServeContext
    # arms SLO objectives.
    steer_slo_weight: float = 1.0
    # Seconds added to the autoscaler's mean drain estimate per unit of
    # mean excess burn across active replicas: sustained budget burn pulls
    # the fleet toward the high watermark (scale-up) even when raw queue
    # depth alone would not cross it.  Inert unless SLOs are armed.
    autoscale_slo_boost: float = 1.0
    # Replace (not just drain) a replica the health sweep takes out of
    # rotation — a fresh replica inheriting the fleet's warm state spawns
    # immediately so capacity does not dip for the drain cooldown.
    # Implied by ``autoscale``.
    replace_drained: bool = False


@dataclass
class ResilienceContext:
    """Knobs of the unified resilience layer (round 17,
    :mod:`kaminpar_tpu.resilience`): fault injection, circuit breakers,
    the execution watchdog.  All defaults are production-safe no-ops —
    injection disarmed, watchdog off, breakers at the documented
    threshold/cooldown."""

    # Fault plan armed at engine start (resilience/faults.py syntax:
    # "point[@site]:error[:key=val ...]", comma-separated).  Empty =
    # disarmed.  Env KPTPU_FAULTS (+ KPTPU_FAULTS_SEED) arms globally and
    # reaches child processes; an armed plan makes chaos runs replayable
    # because injection decisions are seed-keyed, not drawn from any RNG
    # stream.
    fault_plan: str = ""
    fault_seed: int = 0
    # Consecutive failures that open a (path, cell) breaker, and how long
    # it stays open before the half-open probe re-admits one dispatch.
    # These govern the ENGINE's registry; pipeline sites outside any
    # engine use the process-global registry (env-tunable via
    # KPTPU_BREAKER_THRESHOLD / KPTPU_BREAKER_COOLDOWN_S).
    breaker_threshold: int = 3
    breaker_cooldown_s: float = 30.0
    # Execution-watchdog deadlines (resilience/watchdog.py); 0 disables.
    # A serve batch overrunning execute_timeout_s has its futures
    # force-resolved with a typed ExecuteFault and its cell breaker
    # tripped — the dispatch itself is abandoned, not cancelled (threads
    # are not interruptible; the idempotent future discards late
    # results).
    execute_timeout_s: float = 0.0
    compile_timeout_s: float = 0.0
    # JSONL sidecar for watchdog dossiers ("" = in-memory only; the last
    # 16 ride engine.stats()).
    dossier_path: str = ""
    # Preemption-tolerant execution (round 19, resilience/checkpoint.py):
    # directory for deep-pipeline level-boundary checkpoints ("" =
    # disarmed; env KPTPU_CHECKPOINT arms globally and reaches child
    # processes).  At every coarsening/uncoarsening level boundary the
    # resumable state — level-stack CSR arrays (pulled through ONE
    # counted pull batch under the ``checkpoint_write`` phase), the
    # current partition, the RNG chain position (seed + draw counter),
    # and a context fingerprint — is written with an atomic rename;
    # ``KaMinPar.compute_partition(resume=...)`` / ``tools resume``
    # validates the fingerprint and continues BIT-IDENTICAL to the
    # uninterrupted run (asserted in tests/test_checkpoint.py).
    checkpoint_dir: str = ""
    # Write a checkpoint every N level boundaries (>= 1).
    checkpoint_every_levels: int = 1
    # Keep every boundary's checkpoint file instead of only the latest —
    # the kill-anywhere test matrix resumes from each of them.
    checkpoint_keep_all: bool = False


@dataclass
class GraphCompressionContext:
    """Reference: ``GraphCompressionContext`` (kaminpar.h) — whether the
    input graph is stored compressed (graph/compressed.py, the TeraPart
    analog)."""

    enabled: bool = False
    # Device-decode routing of the compressed stream (ISSUE 10 tentpole;
    # graph/device_compressed.py):
    # - "off": the storage tier only — the DEEP pipeline decompresses the
    #   finest CSR on host before device work (the pre-round-14 behavior).
    # - "finest": the finest level runs directly off the device-resident
    #   compressed stream — clustering + contraction + the final LP
    #   refinement pass decode in-kernel, and the finest re-materialization
    #   at uncoarsening is a device decode kernel.  Bit-identical to the
    #   dense path (asserted); warns + falls back dense outside the
    #   envelope (64-bit build, HEM clustering, v-cycle communities).
    # - "auto": like "finest" but falls back silently.
    # KAMINPAR_TPU_DEVICE_DECODE overrides.
    # The dist tier consumes the SAME knob (round 15,
    # dist/device_compressed.py): under it the finest dist level's
    # adjacency stays resident as per-shard gap streams and the LP/
    # contraction kernels decode in-kernel inside shard_map (envelope:
    # 32-bit + GLOBAL_LP dist clustering; dense staging fallback).
    device_decode: str = "off"


@dataclass
class DebugContext:
    """Reference: the debug dump options consumed by
    kaminpar-shm/partitioning/debug.cc."""

    save_hierarchy: bool = False
    validate_graph: bool = False
    graph_name: str = ""
    dump_dir: str = "."
    dump_graph_hierarchy: bool = False
    dump_partition_hierarchy: bool = False


@dataclass
class Context:
    """Root of the config tree (reference: ``Context``, kaminpar.h:610-622)."""

    preset_name: str = "default"
    mode: PartitioningMode = PartitioningMode.KWAY
    partition: PartitionContext = field(default_factory=PartitionContext)
    coarsening: CoarseningContext = field(default_factory=CoarseningContext)
    initial_partitioning: InitialPartitioningContext = field(
        default_factory=InitialPartitioningContext
    )
    refinement: RefinementContext = field(default_factory=RefinementContext)
    parallel: ParallelContext = field(default_factory=ParallelContext)
    compression: GraphCompressionContext = field(
        default_factory=GraphCompressionContext
    )
    serve: ServeContext = field(default_factory=ServeContext)
    fleet: FleetContext = field(default_factory=FleetContext)
    resilience: ResilienceContext = field(default_factory=ResilienceContext)
    debug: DebugContext = field(default_factory=DebugContext)
    seed: int = 0
    # v-cycle mode: intermediate k values partitioned before the final k
    # (reference: PartitioningContext::vcycles, vcycle_deep_multilevel.cc).
    vcycles: tuple = ()
    # Forbid refinement moves across the previous cycle's blocks
    # (reference: restrict_vcycle_refinement).
    restrict_vcycle_refinement: bool = False
    # int32 by default; int64 mirrors the reference's 64-bit ID/weight build
    # switches (CMakeLists.txt:71-79).
    use_64bit_ids: bool = False

    def to_dict(self) -> dict:
        def conv(obj):
            if dataclasses.is_dataclass(obj):
                return {f.name: conv(getattr(obj, f.name)) for f in dataclasses.fields(obj)}
            if isinstance(obj, enum.Enum):
                return obj.value
            if isinstance(obj, tuple):
                return [conv(x) for x in obj]
            if hasattr(obj, "tolist"):
                return obj.tolist()
            return obj

        return conv(self)

    def dump(self) -> str:
        """Round-trippable config dump (reference: ``--dump-config``,
        apps/KaMinPar.cc:107)."""
        return json.dumps(self.to_dict(), indent=2)
