"""The partition-serving engine: one long-lived warm device context.

``KaMinPar.compute_partition`` is a cold, single-graph, synchronous call —
the shape ladder, compile cache, and device workspaces are rebuilt per call
and idle between calls.  :class:`PartitionEngine` turns that machinery into
a persistent runtime, the standard inference-stack shape:

* **Warmup** — at startup the engine precompiles/warms the executable set
  over a configured shape-bucket ladder and k-range (one synthetic
  partition per (rung, k); every padded bucket the multilevel hierarchy
  visits below that rung gets traced and lands in the persistent XLA
  cache).  Per-cell warm cost is recorded from ``utils/compile_stats`` and
  exposed via :attr:`warmup_report` (the ``tools warmup`` subcommand prints
  it).
* **Bounded async queue** — ``submit`` performs admission control against
  a bounded queue and returns a :class:`ServeFuture`; a full queue rejects
  with a retry-after estimate (backpressure), per-request deadlines expire
  queued work, and ``shutdown(drain=True)`` drains gracefully.
* **Micro-batching** — requests in the same (node-bucket, edge-bucket, k)
  shape cell are dispatched as one batch: partitions are produced by the
  engine's warm pipeline per graph (bit-identical to sequential facade
  runs — asserted in tests/test_serve.py), then the whole batch's quality
  metrics are computed in a single dispatch over the packed disjoint-union
  buffer with one batched readback (serve/batching.py).

A synchronous convenience wrapper (:meth:`partition`) lets the facade
delegate to a warm engine (``KaMinPar(ctx, engine=...)``).
"""

from __future__ import annotations

import copy
import itertools
import threading
import time
from dataclasses import dataclass, replace
from typing import List, Optional, Sequence, Union

import numpy as np

from ..context import Context, ServeContext
from .batching import ShapeCell, batched_metrics, pack_graphs, shape_cell
from .errors import (
    CapacityError,
    DeadlineExceededError,
    EngineStoppedError,
    QueueFullError,
    RequestCancelledError,
    ServeError,
)
from .queue import BoundedServeQueue
from .stats import ServeStats


@dataclass
class ServeResult:
    """What a fulfilled request resolves to."""

    partition: np.ndarray
    cut: int
    feasible: bool
    batch_size: int
    queue_wait_s: float
    execute_s: float
    warm_hit: bool
    request_id: int


class ServeFuture:
    """Completion handle for a submitted request."""

    def __init__(self, request_id: int):
        self.request_id = request_id
        self._ev = threading.Event()
        self._result: Optional[ServeResult] = None
        self._error: Optional[BaseException] = None
        self._cancelled = False
        self._started = False
        # First-wins claim, distinct from the waiter event (round 19):
        # the finalization hook must run BETWEEN claiming the outcome and
        # releasing the waiter — a caller must never act on a result the
        # journal has not recorded — so _done claims under the lock,
        # _on_done fires outside it, and only then does _ev wake waiters.
        self._done = False
        self._lock = threading.Lock()
        # Finalization hook (round 19, serve/journal.py): invoked exactly
        # once — on the FIRST-WINS resolution/rejection, outside the lock
        # but BEFORE the waiter event — with (result | None, error |
        # None).  The engine points it at the journal's resolution
        # writer, so every terminal path (dispatcher, watchdog, deadline,
        # drain) journals through one funnel.
        self._on_done = None

    def cancel(self) -> bool:
        """Cancel if execution has not started; returns success.  A running
        XLA computation cannot be interrupted — late cancels return False."""
        with self._lock:
            if self._started or self._done:
                return False
            self._cancelled = True
        return True

    @property
    def cancelled(self) -> bool:
        with self._lock:
            return self._cancelled

    def _mark_started(self) -> bool:
        """Engine-side: claim the request for execution; False if it was
        cancelled first."""
        with self._lock:
            if self._cancelled:
                return False
            self._started = True
            return True

    def _resolve(self, result: ServeResult) -> bool:
        """First resolution wins (round 17): the execution watchdog may
        force-reject a hung batch's futures from its monitor thread; if
        the abandoned dispatch later returns, its late result is
        discarded here.  Returns whether THIS call resolved the future.

        The finalization hook fires BEFORE the waiter event (round 19):
        a journaled resolution must be durable before ``result()`` can
        return it (serve/journal.py durability contract)."""
        with self._lock:
            if self._done:
                return False
            self._done = True
            self._result = result
        self._fire_on_done(result, None)
        self._ev.set()
        return True

    def _reject(self, error: BaseException) -> bool:
        with self._lock:
            if self._done:
                return False
            self._done = True
            self._error = error
        self._fire_on_done(None, error)
        self._ev.set()
        return True

    def _fire_on_done(self, result, error) -> None:
        cb = self._on_done
        if cb is None:
            return
        try:
            cb(result, error)
        except Exception:  # noqa: BLE001 — a journaling failure must never
            pass           # un-resolve a finished request

    def done(self) -> bool:
        return self._ev.is_set()

    def result(self, timeout: Optional[float] = None) -> ServeResult:
        """Block for the result; raises the request's error (deadline,
        cancellation, engine-stopped, or the pipeline's own exception)."""
        if not self._ev.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not finished within {timeout}s"
            )
        if self._error is not None:
            raise self._error
        assert self._result is not None
        return self._result


@dataclass
class ServeRequest:
    """One queued unit of work (internal; carries the batching cell)."""

    id: int
    graph: object
    k: int
    epsilon: float
    cell: ShapeCell
    future: ServeFuture
    enqueue_t: float
    deadline_t: Optional[float]  # absolute monotonic; None = no deadline
    warm_hit: bool
    max_block_weights: Optional[Sequence[int]] = None
    min_epsilon: float = 0.0
    min_block_weights: Optional[Sequence[int]] = None
    # Quality tier (round 17): "strong" = the engine's full pipeline;
    # "fast" = the trimmed-refinement solver.  The quality_strong ->
    # quality_fast ladder rung demotes strong requests per shape cell
    # under capacity-class failures (counted, reversible).
    quality: str = "strong"
    # Request-scoped trace id (round 20, telemetry/reqtrace.py): minted at
    # submit (or inherited from the fleet / the journal on replay) and
    # carried for the request's whole life — one connected event chain per
    # request even across resteers and crash replays.
    trace_id: str = ""
    # Queue depth observed at admission (stamped by BoundedServeQueue.put;
    # rides the admit trace event).
    queue_position: int = 0
    # The tier that actually served the request ("" until dispatch; may
    # differ from ``quality`` under a quality_strong demotion) — warm
    # accounting is tier-keyed, because the two tiers compile different
    # executable sets.
    quality_served: str = ""
    # Filled during execution:
    partition: Optional[np.ndarray] = None
    caps: Optional[np.ndarray] = None
    execute_s: float = 0.0
    queue_wait_s: float = 0.0
    # Unamortized service cost feeding the retry-after EMA.  Lane-stacked
    # requests report execute_s = batch wall / occupancy (the latency
    # share), but the drain-rate estimate divides the EMA by max_batch
    # itself — feeding it the amortized share would double-count the batch
    # width.  None = use execute_s (the per-graph loop, where they agree).
    service_s: Optional[float] = None

    def expired(self, now: float) -> bool:
        return self.deadline_t is not None and now > self.deadline_t


class PartitionEngine:
    """Persistent partition-serving runtime over one warm device context.

    Usage::

        from kaminpar_tpu.serve import PartitionEngine
        with PartitionEngine("serve") as engine:        # starts + warms
            fut = engine.submit(graph, k=8)             # async
            part = fut.result().partition
            part2 = engine.partition(graph2, k=8)       # sync wrapper

    Thread model: ``submit``/``partition`` are called from any thread; a
    single dispatcher thread owns the pipeline (batch formation, the warm
    facade, the packed metrics dispatch), so device work is never issued
    concurrently and per-request RNG streams stay deterministic.
    """

    def __init__(
        self,
        ctx: Union[Context, str, None] = None,
        name: str = "",
        **serve_overrides,
    ):
        from ..presets import create_context_by_preset_name

        # Replica tag (round 18, serve/fleet.py): names the dispatcher
        # thread (so per-replica trace lanes fall out of the trace
        # recorder's thread_name metadata) and prefixes log/warning text.
        self.name = str(name)

        if ctx is None:
            ctx = create_context_by_preset_name("serve")
        elif isinstance(ctx, str):
            ctx = create_context_by_preset_name(ctx)
        else:
            # The engine owns its tree: a caller mutating the context they
            # passed must not skew results of in-flight requests.
            ctx = copy.deepcopy(ctx)
        self.ctx = ctx
        if serve_overrides:
            ctx.serve = replace(ctx.serve, **serve_overrides)
        self.serve: ServeContext = ctx.serve
        # This engine OWNS its runtime settings (compilation cache, layout
        # build, sync timers): the runtime is activated thread-locally
        # around engine-side pipeline work (warmup, lane-stacked batches),
        # so engines with conflicting configs coexist in one process
        # (ISSUE 6; the internal facade activates its own equivalent
        # runtime around per-graph runs).
        from ..context import EngineRuntime

        self.runtime = EngineRuntime.from_parallel(ctx.parallel)
        lane_mode = str(getattr(self.serve, "lane_stack", "off")).strip().lower()
        if lane_mode not in ("auto", "on", "off"):
            raise ValueError(
                f"ServeContext.lane_stack {self.serve.lane_stack!r}: "
                "expected 'auto', 'on', or 'off'"
            )
        self._queue = BoundedServeQueue(self.serve.queue_bound)
        self.stats_ = ServeStats()
        # Request-scoped tracing + SLO burn accounting (round 20,
        # telemetry/{reqtrace,slo}.py).  A fleet replaces ``reqtrace`` with
        # one registry shared across its replicas so resteered requests
        # keep one connected event chain.  ``_slo`` is None unless the
        # ServeContext arms at least one objective.
        from ..telemetry.reqtrace import ReqTrace
        from ..telemetry.slo import BurnTracker

        self.reqtrace = ReqTrace()
        self._slo = BurnTracker.from_serve(self.serve)
        # (n_bucket, k, tier) — warm-hit accounting, keyed by the quality
        # tier that served the cell (the two tiers compile different
        # executable sets, so a fast-served cell is not warm for strong).
        self._warm_nk: set = set()
        self._warm_cells: set = set()  # exact (n_bucket, m_bucket, k) cells
        # Lane-stack shape keys THIS engine has already traced (warmup rows
        # or a served batch): (LaneStackReport.layout_key, k, epsilon).
        # Keying engine-locally keeps the warm-hit demotion in
        # _try_lanestacked from misfiring on compile events raised by OTHER
        # engines/facades in the process (the compile census is
        # process-global).
        self._warm_stack_keys: set = set()
        # Unified resilience layer (round 17, kaminpar_tpu/resilience):
        # this engine owns a private breaker registry for the serve-tier
        # ladder rungs — per-cell "lanestack" breakers (generalizing the
        # round-11 engine-global latch, now reversible via half-open
        # probing), per-cell "cell" breakers (a poisoned shape cell
        # fast-fails new admissions instead of wedging the queue), and
        # per-cell "quality_strong" breakers (capacity-class failures
        # demote strong requests to the fast tier).  The pipeline rung
        # (device_decode) lives on the process-global registry.  The watchdog bounds hung executes.
        from ..resilience.breakers import BreakerRegistry
        from ..resilience.watchdog import ExecutionWatchdog

        self.resilience = ctx.resilience
        self.breakers = BreakerRegistry(
            threshold=self.resilience.breaker_threshold,
            cooldown_s=self.resilience.breaker_cooldown_s,
        )
        self.watchdog = ExecutionWatchdog(self.resilience.dossier_path)
        self.warmup_report: List[dict] = []
        # Warm-cache inheritance (round 18, serve/fleet.py): True once
        # inherit_warmup imported another replica's warm state — the
        # warmup passes then skip every inherited cell (and the aux
        # passes entirely: their executables are process-warm from the
        # source replica, and the shared persistent cache dir covers the
        # cross-process case).
        self._inherited = False
        # Requests currently being executed by the dispatcher (the bounded
        # shutdown force-resolves these when the worker dies mid-batch).
        self._inflight: List[ServeRequest] = []
        # Lazily-built trimmed-refinement solver serving quality="fast"
        # requests and quality_strong demotions.
        self._fast_solver = None
        # Whether THIS engine armed the process-wide fault plan (start()
        # arms, shutdown() disarms — injections must not outlive us).
        self._armed_faults = False
        # Admission-preflight ceiling (ISSUE 12): resolved lazily at start()
        # — explicit override > measured allocator limit > device-kind
        # table; None disables (no ceiling is knowable, e.g. CPU without
        # allocator stats).
        self._capacity_ceiling: Optional[int] = None
        self._device_kind: str = ""
        # Crash-safe journal (round 19, serve/journal.py): admitted
        # requests are journaled at admit and at first-wins resolution;
        # start() replays unresolved entries + restores the warm state.
        # Env KPTPU_SERVE_JOURNAL overrides (reaches child processes).
        import os as _os

        env_journal = _os.environ.get("KPTPU_SERVE_JOURNAL", "")
        if env_journal and self.name:
            # A fleet's replicas all see the same env var: suffix by the
            # engine name or N engines would interleave one journal file
            # with colliding request ids (the context-knob path gets its
            # per-replica suffix from the fleet constructor).
            env_journal += f".{self.name}"
        self._journal_path = env_journal or getattr(
            self.serve, "journal_path", ""
        )
        self._journal = None
        self._ids = itertools.count(1)
        self._solver = None
        self._thread: Optional[threading.Thread] = None
        self._running = False
        self._gate = threading.Event()  # pause/resume; set == dispatching
        self._gate.set()
        self._lock = threading.Lock()

    # -- lifecycle ---------------------------------------------------------

    def start(self, warmup: bool = True) -> "PartitionEngine":
        """Initialize the warm context (idempotent).  ``warmup=True`` runs
        the ladder precompile before the first request is accepted."""
        with self._lock:
            if self._running:
                return self
            if self._queue.closed:
                # Restart after shutdown: the old queue was closed to drain
                # the dispatcher, so a fresh one is needed (warm state —
                # solver caches, warm cells, stats — carries over).
                self._queue = BoundedServeQueue(self.serve.queue_bound)
            from ..kaminpar import KaMinPar

            # The internal facade owns an EngineRuntime built from the same
            # context, so its per-graph runs see this engine's settings
            # regardless of other engines in the process (ISSUE 6).
            if self._solver is None:
                self._solver = KaMinPar(copy.deepcopy(self.ctx))
            if self.resilience.fault_plan:
                # Arm the context's chaos plan process-wide (seed-keyed, so
                # the run replays bit-for-bit); env KPTPU_FAULTS outranks
                # it by arming earlier via the lazy env discovery.  The
                # engine remembers that IT armed and disarms at shutdown —
                # chaos injections must not outlive the engine and leak
                # into unrelated engines/pipelines in the process.
                from ..resilience import faults

                if faults.active_plan() is None:
                    faults.arm(faults.FaultPlan.parse(
                        self.resilience.fault_plan,
                        seed=self.resilience.fault_seed,
                    ))
                    self._armed_faults = True
                else:
                    import warnings

                    warnings.warn(
                        "kaminpar_tpu serve: a fault plan is already "
                        "armed in this process — this engine's "
                        "resilience.fault_plan is ignored (one plan per "
                        "process; disarm the active one first).",
                        RuntimeWarning,
                        stacklevel=2,
                    )
            recovery = None
            if self._journal_path and self._journal is None:
                # Crash recovery (round 19, serve/journal.py): parse the
                # journal BEFORE warmup — the warm-state record seeds the
                # warm sets through the PR 14 inheritance path, so
                # warmup below raises zero compile events for restored
                # cells; unresolved admits replay once the queue exists.
                from . import journal as _journal

                recovery = _journal.read_journal(self._journal_path)
                if recovery["max_id"]:
                    # Resume the id counter past the dead run's ids so a
                    # fresh admission can never collide with a journal
                    # entry awaiting replay.
                    self._ids = itertools.count(recovery["max_id"] + 1)
                if recovery["warm_state"] is not None:
                    _journal.apply_warm_state(self, recovery["warm_state"])
            try:
                self._resolve_capacity_ceiling()
                if warmup:
                    self._warmup()
            except BaseException:
                # start() failing after arming must not leak the chaos
                # plan into the process (shutdown's disarm is unreachable
                # for a never-running engine).
                self._disarm_faults()
                raise
            if recovery is not None:
                from ..utils.timer import scoped_timer
                from . import journal as _journal

                self._journal = _journal.ServeJournal(
                    self._journal_path,
                    fsync_every=self.serve.journal_fsync_every,
                )
                # Durable warm state as of THIS start (first runs write
                # their fresh warmup here; restarts refresh the record).
                self._journal.append(
                    _journal.warm_state_record(self), force_fsync=True
                )
                if recovery["unresolved"]:
                    with scoped_timer("journal_replay"):
                        self._replay_journal(recovery["unresolved"])
            self._running = True
            thread_name = "kaminpar-serve-dispatch" + (
                f"-{self.name}" if self.name else ""
            )
            self._thread = threading.Thread(
                target=self._loop, name=thread_name, daemon=True
            )
            self._thread.start()
        return self

    def _resolve_capacity_ceiling(self) -> None:
        """Resolve the admission-preflight ceiling (ISSUE 12): the explicit
        ServeContext override, else the device allocator's measured
        bytes_limit, else the per-device-kind HBM table
        (telemetry/capacity.py) — None when nothing is knowable."""
        from ..telemetry import capacity
        from ..utils import heap_profiler

        try:
            import jax

            self._device_kind = str(
                getattr(jax.devices()[0], "device_kind", "")
            )
        except Exception:  # noqa: BLE001 — a dead backend resolves later
            self._device_kind = ""
        explicit = int(getattr(self.serve, "capacity_ceiling_bytes", 0) or 0)
        if explicit > 0:
            self._capacity_ceiling = explicit
            return
        limit = heap_profiler.memory_summary().get("bytes_limit")
        if limit:
            # bytes_limit is already the allocator's usable pool (XLA's
            # reservation is taken off the top) — applying the planner's
            # headroom again would double-discount vs the device-kind
            # table path and HBM_BUDGET.md.
            self._capacity_ceiling = int(limit)
            return
        self._capacity_ceiling = capacity.device_ceiling_bytes(
            self._device_kind
        )

    def _run_preflight(self, graph, k: int) -> None:
        """The one capacity-preflight invocation (ISSUE 12): raises
        :class:`CapacityError` when the predicted watermark exceeds this
        engine's ceiling; returns silently when the preflight is off or
        no ceiling is knowable.  Shared by the counting admission path
        (:meth:`_capacity_preflight`) and the fleet router's non-counting
        steering probe (:meth:`capacity_verdict`) so the two can never
        diverge on what "fits" means."""
        mode = str(
            getattr(self.serve, "capacity_preflight", "auto")
        ).strip().lower()
        if mode == "off" or self._capacity_ceiling is None:
            return
        from ..telemetry import capacity
        from ..utils.timer import scoped_timer

        with scoped_timer("capacity_preflight"):
            capacity.preflight(
                graph, k,
                ceiling_bytes=self._capacity_ceiling,
                device_kind=self._device_kind,
                device_decode=(
                    self.ctx.compression.enabled
                    and str(self.ctx.compression.device_decode) != "off"
                ),
            )

    def _capacity_preflight(self, graph, k: int) -> None:
        """Reject a predicted-oversize request with :class:`CapacityError`
        BEFORE it is queued (and long before anything compiles) — pure
        host arithmetic over the graph's padded shape cell (ISSUE 12; the
        first piece of the ROADMAP serve-fleet SLO-aware admission)."""
        try:
            self._run_preflight(graph, k)
        except CapacityError:
            self.stats_.bump("rejected_capacity")
            from ..telemetry import trace as ttrace

            rec = ttrace.active()
            if rec is not None:
                rec.instant(
                    "serve.reject_capacity", k=int(k),
                    ceiling_bytes=self._capacity_ceiling,
                )
            raise

    def inherit_warmup(self, source: "PartitionEngine") -> None:
        """Import another replica's warm state (round 18 warm-cache
        inheritance): its warmup-report rows land here marked
        ``inherited=True`` with zero wall/compile cost, its warm cells /
        (n, k, tier) pairs / lane-stack layout keys seed this engine's
        warm-accounting sets, and its service-time EMA seeds the
        retry-after estimate.  A subsequent ``start(warmup=True)`` then
        skips every inherited cell — replica N+1 pays zero synthetic
        partitions for cells the fleet already traced (the compiled
        executables are shared in-process, and the shared persistent
        cache dir covers a fresh process).  Must be called before
        :meth:`start`; inherited-vs-local counts ride ``warmup_report``,
        ``stats()`` and the Prometheus exposition."""
        for row in source.warmup_report:
            inherited = dict(row)
            inherited["inherited"] = True
            # The cost was paid by the source replica, not this one.
            inherited["wall_s"] = 0.0
            inherited["backend_compile_s"] = 0.0
            inherited["trace_s"] = 0.0
            self.warmup_report.append(inherited)
        self._warm_cells |= source._warm_cells
        self._warm_nk |= source._warm_nk
        self._warm_stack_keys |= source._warm_stack_keys
        ema = source.stats_.service_time_estimate()
        if ema > 0.0:
            self.stats_.seed_service_time(ema)
        self._inherited = True

    def _warmup(self) -> None:
        """Trace/compile the executable set over warm_ladder x warm_ks by
        running one synthetic RMAT partition per cell; every padded bucket
        the hierarchy visits below each rung gets warmed too.  Per-cell
        wall + compile/trace seconds come from utils/compile_stats.
        Cells already imported via :meth:`inherit_warmup` are skipped —
        their inherited report rows are in place and the executables are
        warm from the source replica."""
        from ..graph.generators import rmat_graph
        from ..utils import compile_stats

        # ONE synthetic graph per rung, shared by every warm pass (the
        # rung-to-scale mapping lives here alone, so the passes cannot
        # drift).
        rung_graphs: dict = {}

        def rung_graph(n):
            if n not in rung_graphs:
                scale = max(2, int(np.ceil(np.log2(max(int(n), 4)))))
                rung_graphs[n] = (scale, rmat_graph(
                    scale, edge_factor=self.serve.warm_edge_factor, seed=1
                ))
            return rung_graphs[n]

        from ..resilience.errors import ResilienceError, classify
        from ..resilience.faults import maybe_inject

        try:
            # Named "warmup" injection point: a warmup-pass fault degrades
            # the engine to cold-start serving, never fails start().
            maybe_inject("warmup", site="engine_warmup")
        except ResilienceError as exc:
            self._warmup_fault(exc, "warmup pass")
            return
        for n in self.serve.warm_ladder:
            for k in self.serve.warm_ks:
                scale, g = rung_graph(n)
                if k > (1 << scale):
                    continue
                cell = shape_cell(g, k)
                if self._inherited and cell in self._warm_cells:
                    continue  # imported from the fleet — already traced
                before = compile_stats.compile_time_snapshot()
                t0 = time.perf_counter()
                try:
                    maybe_inject("compile", site=f"warmup_cell:{n}:{k}")
                    with self.watchdog.guard(
                        "warmup_compile", self.resilience.compile_timeout_s,
                        on_timeout=lambda d, c=cell: self._on_hang(c, d),
                    ):
                        self._solver.set_graph(g)
                        self._solver.compute_partition(int(k), 0.03)
                except Exception as exc:  # noqa: BLE001 — one poisoned warm
                    # cell must not abort the ladder; classify, count,
                    # keep warming the rest.
                    self._warmup_fault(
                        classify(exc, site=f"warmup_cell:{n}:{k}"),
                        f"warm cell (n={n}, k={k})",
                    )
                    continue
                wall = time.perf_counter() - t0
                after = compile_stats.compile_time_snapshot()
                row = {
                    "n": 1 << scale,
                    "k": int(k),
                    "n_bucket": cell.n_bucket,
                    "m_bucket": cell.m_bucket,
                    "wall_s": round(wall, 3),
                    "backend_compile_s": round(
                        after["backend_compile_s"] - before["backend_compile_s"], 3
                    ),
                    "trace_s": round(after["trace_s"] - before["trace_s"], 3),
                }
                if compile_stats.executable_census_armed():
                    # Executable census of the cell (ISSUE 12): what the
                    # warmed hot kernels WOULD do on silicon — flops/bytes
                    # from cost_analysis, arg/out/temp/peak bytes from
                    # memory_analysis — via shape-only lowering (no device
                    # data, zero transfers; armed-only so unarmed warmups
                    # pay nothing).
                    census_row = self._harvest_cell_census(cell)
                    if census_row:
                        row["census"] = census_row
                self.warmup_report.append(row)
                self._note_warm(cell)
        if not self._inherited:
            # Inherited engines skip the aux passes: the ip-pool /
            # lane-stack / compressed executables are process-warm from
            # the source replica's passes (and rode the inherited report
            # rows above); re-running them per replica would pay the
            # synthetic partitions N times for one executable set.
            self._warm_ip_pool(rung_graph)
            self._warm_lanestack(rung_graph)
            self._warm_compressed(rung_graph)
        # Seed the retry-after service-time EMA from the warm execution
        # cost (wall minus compile/trace — the steady-state share) so the
        # very first admission rejects carry a real estimate instead of
        # the blind floor (ISSUE 6 satellite).  Inherited rows carry zero
        # wall (the source paid it) and must not dilute the mean — the
        # inherit path seeds the EMA from the source's instead.
        execs = [
            max(r["wall_s"] - r["backend_compile_s"] - r["trace_s"], 1e-3)
            for r in self.warmup_report
            if "kind" not in r and not r.get("inherited")
        ]
        if execs:
            self.stats_.seed_service_time(float(np.mean(execs)))

    def _warmup_fault(self, err, what: str) -> None:
        """Count + surface one contained warmup failure (typed; the engine
        serves cold-start for whatever was not warmed)."""
        import warnings

        self.stats_.bump("warmup_faults")
        warnings.warn(
            f"kaminpar_tpu serve: {what} failed during warmup "
            f"({err.failure_class}: {err}) — continuing; unwarmed cells "
            "pay their compile on first request.",
            RuntimeWarning,
            stacklevel=3,
        )

    def _on_hang(self, cell: ShapeCell, dossier: dict,
                 live: Optional[List[ServeRequest]] = None) -> None:
        """Watchdog timeout callback (monitor thread): convert the hang
        into a breaker trip + typed future resolutions instead of a
        killed process (round 17 tentpole d).  The hung dispatch itself
        is abandoned — the idempotent futures discard its late result."""
        from ..resilience.errors import ExecuteFault

        self.stats_.bump("watchdog_timeouts")
        key = (cell.n_bucket, cell.m_bucket, cell.k)
        # Force the trip (not one counted failure): each further probe of
        # a hung cell wedges the single dispatcher thread for a full
        # deadline — one observed hang is conclusive, the next request
        # fast-fails with PoisonedCell until the cooldown's half-open
        # probe.
        self.breakers.get("cell", key).trip()
        for req in (live or []):
            if req.future._reject(ExecuteFault(
                f"request {req.id} abandoned: {dossier['phase']} exceeded "
                f"the {dossier['timeout_s']}s watchdog deadline in cell "
                f"{key} (dossier on engine.stats()['resilience'])",
                site="watchdog",
            )):
                # Watchdog faults are resteerable (site="watchdog") — the
                # trace chain continues if the fleet re-homes the request.
                self._trace_event(req, "error", final=False,
                                  failure_class="worker-hung",
                                  site="watchdog")
                self.stats_.record_request(
                    time.monotonic() - req.enqueue_t, 0.0, failed=True
                )

    def _harvest_cell_census(self, cell: ShapeCell) -> dict:
        """Harvest the executable census of one warm shape cell via the
        capacity planner's shared ``capacity_contraction|n,m`` registry key
        (telemetry/capacity.harvest_contraction_cell) — the transient
        dominator lowered + compiled from ``jax.ShapeDtypeStruct`` shapes,
        so the planner and the warmup reuse each other's rows and one
        executable is never compiled twice.  Pure host-side compiler
        introspection — no device arrays exist, so the armed census adds
        zero blocking transfers and zero collectives (asserted in
        tests/test_capacity.py)."""
        from ..telemetry import capacity

        with self.runtime.activate():
            row = capacity.harvest_contraction_cell(
                int(cell.n_bucket), int(cell.m_bucket)
            )
        if not row:
            return {}
        return {
            k: row[k]
            for k in ("flops", "bytes_accessed", "temp_bytes", "peak_bytes")
            if row.get(k) is not None
        }

    def _warm_lanestack(self, rung_graph) -> None:
        """Precompile the lane-stacked pipeline per (rung, k, lane-count)
        cell (``serve.warm_lanes``; kind="lanestack" report rows, printed
        by ``tools warmup``).  Runs L copies of the rung's synthetic graph
        (``rung_graph`` — _warmup's memoized per-rung generator) through
        serve/lanestack.py — identical hierarchies, so the whole stack
        stays one cohort and every vmapped kernel of the lockstep pipeline
        gets traced at lane count L."""
        if self._lane_stack_mode() == "off" or not self.serve.warm_lanes:
            return
        from ..utils import compile_stats
        from .lanestack import LaneStackUnsupported, run_lanestacked

        for n in self.serve.warm_ladder:
            scale, g = rung_graph(n)
            for k in self.serve.warm_ks:
                if k < 2 or k > (1 << scale):
                    continue  # per-cell envelope bound, not config-wide
                for lanes in self.serve.warm_lanes:
                    before = compile_stats.compile_time_snapshot()
                    t0 = time.perf_counter()
                    try:
                        with self.runtime.activate():
                            _, rep = run_lanestacked(
                                self._solver.ctx, [g] * int(lanes), int(k), 0.03
                            )
                    except LaneStackUnsupported:
                        return  # config outside the envelope: nothing to warm
                    self._warm_stack_keys.add(
                        (rep.layout_key, int(k), 0.03)
                    )
                    wall = time.perf_counter() - t0
                    after = compile_stats.compile_time_snapshot()
                    cell = shape_cell(g, int(k))
                    self.warmup_report.append({
                        "kind": "lanestack",
                        "n": 1 << scale,
                        "k": int(k),
                        "n_bucket": cell.n_bucket,
                        "m_bucket": cell.m_bucket,
                        "lanes": int(lanes),
                        "wall_s": round(wall, 3),
                        "backend_compile_s": round(
                            after["backend_compile_s"]
                            - before["backend_compile_s"], 3
                        ),
                        "trace_s": round(
                            after["trace_s"] - before["trace_s"], 3
                        ),
                    })

    def _warm_compressed(self, rung_graph) -> None:
        """Trace/compile the decode-fused compressed-stream kernels per
        warm rung (ISSUE 10 satellite; ``kind="compressed"`` report rows,
        printed by ``tools warmup``).  Engines serving a terapart-style
        context (compression enabled with device decode routed on) warm
        the compressed LP sweep cell of every rung so the first real
        compressed request starts backend-compile-warm; other engines
        skip the pass entirely."""
        from ..graph.compressed import compress
        from ..graph.device_compressed import (
            DeviceCompressedView,
            device_decode_eligible,
            resolve_device_decode,
        )

        if not self.ctx.compression.enabled:
            return
        if resolve_device_decode(self.ctx.compression) == "off":
            return
        from ..coarsening.lp_clusterer import LPClustering
        from ..utils import compile_stats

        for n in self.serve.warm_ladder:
            _, g = rung_graph(n)
            cg = compress(g)
            # Same envelope gate the pipeline applies: an engine whose
            # requests will be routed dense (64-bit build, HEM clusterer)
            # must not burn warmup compiles on kernels it can never use.
            if not device_decode_eligible(self.ctx, cg)[0]:
                return
            before = compile_stats.compile_time_snapshot()
            t0 = time.perf_counter()
            with self.runtime.activate():
                cv = DeviceCompressedView(
                    cg, layout_mode=self.ctx.parallel.device_layout_build,
                )
                clusterer = LPClustering(self.ctx.coarsening.lp, 1)
                labels = clusterer.compute_clustering(
                    cv, max_cluster_weight=1 << 20
                )
                # Force execution so wall_s covers compile + run: ONE tiny
                # counted readback (warmup is outside the pipeline spine;
                # device code must not block_until_ready).
                from ..utils import sync_stats

                sync_stats.pull(labels[:1])
            wall = time.perf_counter() - t0
            after = compile_stats.compile_time_snapshot()
            self.warmup_report.append({
                "kind": "compressed",
                "n": int(n),
                "k": 0,  # clustering cell — no block count
                "n_bucket": cv.n_pad,
                "m_bucket": cv.m_pad,
                "wall_s": round(wall, 3),
                "backend_compile_s": round(
                    after["backend_compile_s"] - before["backend_compile_s"], 3
                ),
                "trace_s": round(after["trace_s"] - before["trace_s"], 3),
            })

    def _warm_ip_pool(self, rung_graph) -> None:
        """Precompile the lane-vmapped initial-bipartitioning pool per
        (n-bucket, m-bucket, lane-count) cell (ISSUE 4 satellite).  The
        synthetic warmup partitions above already trace the cells they
        visit; this pass AOT-compiles the k=2 bisection cell of every rung
        bucket explicitly — including the lane counts the adaptive
        repetition rule picks for each warm k — so the first real bisection
        in a cell starts backend-compile-warm (``rung_graph`` is _warmup's
        memoized per-rung generator, so this pass warms the exact cell the
        pipeline pass used).  Device backend only: the host pool has
        nothing to compile."""
        from ..initial.bipartitioner import resolve_ip_backend
        from ..ops import bipartition as bip

        ipc = self.ctx.initial_partitioning
        if resolve_ip_backend(ipc) != "device":
            return
        from ..utils import compile_stats

        # Recursive bisection halves final_k per level (k, ceil(k/2), ...,
        # 2), and each final_k maps to its own lane layout through the
        # adaptive repetition rule — warm the whole chain, not just the top.
        lane_layouts = set()
        for k in (2, *self.serve.warm_ks):
            k = int(k)
            while k > 1:
                lane_layouts.add(bip.method_lane_counts(ipc, k)[0])
                k = (k + 1) // 2 if k > 2 else 1
        for n in self.serve.warm_ladder:
            # The cell a rung's first bisection actually hits: the padded
            # buckets of the same synthetic graph the warmup partitions
            # above use (an m-bucket estimated from the edge factor can
            # land one ladder rung off the real graph's).
            pv = rung_graph(n)[1].padded()
            n_pad, m_pad = pv.n_pad, pv.m_pad
            for methods in sorted(lane_layouts):
                before = compile_stats.compile_time_snapshot()
                # Activate the engine's runtime so these compiles land in
                # ITS persistent cache dir (like the pipeline warm pass and
                # _warm_lanestack), not whatever dir is currently applied
                # process-wide.
                with self.runtime.activate():
                    wall = bip.warm_pool_executable(
                        n_pad, m_pad, methods, ipc.fm_num_iterations
                    )
                after = compile_stats.compile_time_snapshot()
                self.warmup_report.append({
                    "kind": "ip_pool",
                    "n": int(n),
                    "k": 2,
                    "n_bucket": n_pad,
                    "m_bucket": m_pad,
                    "lanes": sum(cnt for _, cnt in methods),
                    "wall_s": round(wall, 3),
                    "backend_compile_s": round(
                        after["backend_compile_s"] - before["backend_compile_s"], 3
                    ),
                    "trace_s": round(after["trace_s"] - before["trace_s"], 3),
                })

    def _note_warm(self, cell: ShapeCell, tier: str = "strong") -> None:
        self._warm_cells.add(cell)
        self._warm_nk.add((cell.n_bucket, cell.k, tier))

    @property
    def running(self) -> bool:
        return self._running

    def pause(self) -> None:
        """Hold the dispatcher (maintenance window; queued work waits IN
        the queue — where a fleet drain can requeue it — and admission
        stays open up to the queue bound).  Takes effect before the next
        batch is *extracted*: the gate-aware pop leaves work queued, so a
        paused burst accumulates to full batches."""
        self._gate.clear()

    def resume(self) -> None:
        self._gate.set()
        self._queue.poke()

    def shutdown(self, drain: bool = True, timeout_s: Optional[float] = None) -> None:
        """Stop the engine.  ``drain=True`` serves everything already
        queued first; ``drain=False`` rejects queued work with
        :class:`EngineStoppedError`.  Idempotent.

        Round 17 satellite: the drain is BOUNDED — if the dispatcher
        thread dies or hangs mid-batch, everything still unresolved
        (queued + in-flight) is force-resolved with a typed
        :class:`~kaminpar_tpu.resilience.errors.WorkerHung` after
        ``timeout_s`` (default ``ServeContext.drain_timeout_s``) instead
        of blocking callers forever."""
        with self._lock:
            if not self._running:
                return
            self._queue.close()
            if not drain:
                for req in self._queue.drain_items():
                    self.stats_.bump("cancelled")
                    req.future._reject(
                        EngineStoppedError("engine shut down before execution")
                    )
            self._gate.set()
            thread = self._thread
        if thread is not None:
            # `is not None`, not truthiness: an explicit timeout_s=0.0
            # means "force-resolve immediately", not "use the default".
            budget = (
                timeout_s if timeout_s is not None
                else self.serve.drain_timeout_s
            )
            thread.join(budget)
            if thread.is_alive():
                # The worker is hung (or wedged on a poisoned batch): the
                # drain contract still holds — every outstanding future is
                # resolved, with a typed error naming the cause.
                from ..resilience.errors import WorkerHung

                stuck = list(self._queue.drain_items())
                with self._lock:
                    stuck.extend(self._inflight)
                hung = 0
                for req in stuck:
                    if req.future._reject(WorkerHung(
                        f"request {req.id} unresolved: the dispatcher "
                        "thread did not finish draining within "
                        f"{budget}s "
                        "(worker dead or hung mid-batch)",
                        site="shutdown",
                    )):
                        hung += 1
                        self.stats_.record_request(
                            time.monotonic() - req.enqueue_t, 0.0, failed=True
                        )
                if hung:
                    self.stats_.bump("worker_hung", hung)
        # Final warm-state record + journal close (fsynced): a clean
        # shutdown leaves zero unresolved entries — EngineStopped/
        # WorkerHung force-resolutions above deliberately stay
        # UNRESOLVED in the journal so a restart replays them.
        self._close_journal()
        self._disarm_faults()
        with self._lock:
            self._running = False

    def _disarm_faults(self) -> None:
        """Disarm the process-wide fault plan iff THIS engine armed it."""
        if self._armed_faults:
            from ..resilience import faults

            faults.disarm()
            self._armed_faults = False

    # -- crash-safe journal (round 19, serve/journal.py) -------------------

    def _journal_admit(self, req: ServeRequest) -> None:
        """Journal one accepted request (admit record: params + graph
        payload, ONE counted bulk pull under ``journal_write``).  The
        future's resolution hook is installed by the submit path BEFORE
        the queue insert — a dispatcher racing ahead of this append just
        writes the resolve record first, which read_journal tolerates."""
        from ..utils.timer import scoped_timer
        from . import journal as _journal

        with scoped_timer("journal_write"):
            record = {
                "t": "admit",
                "id": req.id,
                "k": req.k,
                "epsilon": req.epsilon,
                "quality": req.quality,
                "min_epsilon": req.min_epsilon,
                "max_block_weights": (
                    None if req.max_block_weights is None
                    else [int(x) for x in req.max_block_weights]
                ),
                "min_block_weights": (
                    None if req.min_block_weights is None
                    else [int(x) for x in req.min_block_weights]
                ),
                # Trace continuity across crashes (round 20): replay
                # re-binds the replayed request to this id, so the
                # restarted process extends the SAME event chain.
                "trace_id": req.trace_id,
                "graph": _journal.encode_graph(req.graph),
            }
            self._journal.append(record)

    def _journal_resolution(self, jid: int, result, error) -> None:
        """Append the terminal record of journal entry ``jid`` — except
        for "the engine gave it back" classes (EngineStoppedError /
        WorkerHung), which leave the entry unresolved so a restart
        replays it (losing accepted work is the one thing the journal
        exists to prevent)."""
        jr = self._journal
        if jr is None:
            return
        if error is not None:
            from ..resilience.errors import WorkerHung

            if isinstance(error, (EngineStoppedError, WorkerHung)):
                return
            record = {
                "t": "resolve", "id": jid, "ok": 0,
                "error": getattr(
                    error, "failure_class", type(error).__name__
                ),
            }
        else:
            record = {
                "t": "resolve", "id": jid, "ok": 1,
                "cut": int(result.cut), "feasible": int(result.feasible),
            }
        jr.append(record, force_fsync=True)
        self.stats_.bump("journal_resolutions")

    def _replay_journal(self, entries) -> None:
        """Re-enqueue the journal's unresolved admits idempotently: each
        replayed request keeps its ORIGINAL journal id for the resolution
        record (no second admit record is written), runs without a
        deadline (the original deadline died with its process), and
        bypasses the admission bound — the work was admitted once
        already.  Decode is host->device puts only (zero pulls)."""
        from . import journal as _journal

        now = time.monotonic()
        for entry in entries:
            try:
                graph = _journal.decode_graph(
                    entry["graph"],
                    use_64bit=bool(self.ctx.use_64bit_ids),
                    layout_mode=self.ctx.parallel.device_layout_build,
                )
            except (KeyError, ValueError) as exc:
                import warnings

                warnings.warn(
                    f"kaminpar_tpu serve: journal entry {entry.get('id')} "
                    f"unreplayable ({type(exc).__name__}: {exc}) — skipped",
                    RuntimeWarning,
                    stacklevel=2,
                )
                continue
            cell = shape_cell(graph, int(entry["k"]))
            quality = str(entry.get("quality", "strong"))
            req = ServeRequest(
                id=next(self._ids),
                graph=graph,
                k=int(entry["k"]),
                epsilon=float(entry["epsilon"]),
                cell=cell,
                future=ServeFuture(0),
                enqueue_t=now,
                deadline_t=None,
                warm_hit=(cell.n_bucket, int(entry["k"]), quality)
                in self._warm_nk,
                max_block_weights=entry.get("max_block_weights"),
                min_epsilon=float(entry.get("min_epsilon", 0.0) or 0.0),
                min_block_weights=entry.get("min_block_weights"),
                quality=quality,
                trace_id=str(entry.get("trace_id", "") or ""),
            )
            req.future.request_id = req.id
            req.future._on_done = (
                lambda result, error, _id=int(entry["id"]):
                    self._journal_resolution(_id, result, error)
            )
            # Trace continuity (round 20): re-bind the journaled trace id
            # (minting a fresh one only for pre-round-20 journals) under
            # BOTH the new engine id and the original journal id, record a
            # replayed admit + an explicit journal_replay hop — the
            # restarted process extends the same event chain the dead one
            # started, so explain() shows admit -> replay -> resolution
            # connected.
            if not req.trace_id:
                req.trace_id = self.reqtrace.mint()
            self.reqtrace.bind(req.id, req.trace_id)
            self.reqtrace.bind(int(entry["id"]), req.trace_id)
            self.reqtrace.record(
                req.trace_id, "admit", request_id=req.id,
                engine=self.name, k=req.k, quality=quality,
                replayed=True, journal_id=int(entry["id"]),
            )
            self.reqtrace.record(
                req.trace_id, "journal_replay", request_id=req.id,
                engine=self.name, journal_id=int(entry["id"]),
            )
            self.stats_.record_warm(req.warm_hit)
            self._queue.put(req, force=True)
            self.stats_.bump("journal_replayed")

    def journal_mark_resteered(self, request_id: int) -> None:
        """Resolve journal entry ``request_id`` as re-homed (round 19):
        the fleet drain successfully requeued this request on a sibling
        replica, whose own journal now owns it — leaving the entry
        unresolved here would make a later revival of this slot replay
        work that already completed elsewhere."""
        jr = self._journal
        if jr is None:
            return
        jr.append(
            {"t": "resolve", "id": int(request_id), "ok": 0,
             "error": "resteered"},
            force_fsync=True,
        )
        self.stats_.bump("journal_resolutions")

    def _close_journal(self) -> None:
        jr = self._journal
        if jr is None:
            return
        from . import journal as _journal

        try:
            jr.append(_journal.warm_state_record(self), force_fsync=True)
        finally:
            jr.close()
            self._journal = None
        try:
            # Clean shutdown compacts the history down to what recovery
            # needs (unresolved admits + the final warm state): an
            # append-only file would otherwise grow one graph payload
            # per request forever and tax every restart's parse.
            _journal.compact(jr.path)
        except OSError as exc:
            import warnings

            warnings.warn(
                f"kaminpar_tpu serve: journal compaction failed "
                f"({exc}); the full history remains valid",
                RuntimeWarning,
                stacklevel=2,
            )

    def __enter__(self) -> "PartitionEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown(drain=True)

    # -- request path ------------------------------------------------------

    def submit(
        self,
        graph,
        k: int,
        epsilon: float = 0.03,
        *,
        deadline_ms: Optional[float] = None,
        max_block_weights: Optional[Sequence[int]] = None,
        min_epsilon: float = 0.0,
        min_block_weights: Optional[Sequence[int]] = None,
        quality: str = "strong",
        trace_id: str = "",
    ) -> ServeFuture:
        """Enqueue one partition request; returns a :class:`ServeFuture`.

        Raises :class:`EngineStoppedError` when not running,
        :class:`QueueFullError` (with ``retry_after_s``) when admission
        control rejects the request, and
        :class:`~kaminpar_tpu.resilience.errors.PoisonedCell` (with
        ``retry_after_s``) when the request's shape cell tripped its
        circuit breaker — a deterministically failing cell fast-fails at
        admission instead of wedging the queue (round 17).

        ``quality``: "strong" (the engine's full pipeline) or "fast"
        (trimmed refinement — the tiered-SLO knob; strong requests can be
        demoted per cell by the quality_strong ladder rung under
        capacity-class failures).

        ``trace_id``: request-scoped trace id (round 20) — the fleet
        passes the id it minted at steer time so the engine extends the
        same event chain; direct callers leave it empty and the engine
        mints one (queryable via :meth:`explain`)."""
        if quality not in ("strong", "fast"):
            raise ValueError(
                f"quality must be 'strong' or 'fast', got {quality!r}"
            )
        if not self._running:
            raise EngineStoppedError("engine not started (call start())")
        self.stats_.bump("submitted")
        from ..resilience.errors import PoisonedCell
        from ..resilience.faults import maybe_inject

        tid = str(trace_id) or self.reqtrace.mint()
        maybe_inject("queue-admit", site="submit")
        try:
            self._capacity_preflight(graph, k)
        except CapacityError:
            self.reqtrace.record(tid, "reject", engine=self.name,
                                 reason="capacity")
            if self._slo is not None:
                self._slo.record_reject(capacity=True)
            raise
        cell = shape_cell(graph, k)
        cell_key = (cell.n_bucket, cell.m_bucket, cell.k)
        cell_breaker = self.breakers.get("cell", cell_key)
        if not cell_breaker.allow():
            # Poisoned cell: reject fast with the cooldown as the retry
            # hint; the post-cooldown half-open probe re-admits ONE
            # request, and its success restores the cell.
            self.stats_.bump("rejected_poisoned")
            self.reqtrace.record(tid, "reject", engine=self.name,
                                 reason="poisoned")
            raise PoisonedCell(
                cell_key, cell_breaker.retry_after_s(), site="submit"
            )
        warm = (cell.n_bucket, int(k), quality) in self._warm_nk
        self.stats_.record_warm(warm)
        if deadline_ms is None:
            deadline_ms = self.serve.default_deadline_ms
        now = time.monotonic()
        req = ServeRequest(
            id=next(self._ids),
            graph=graph,
            k=int(k),
            epsilon=float(epsilon),
            cell=cell,
            future=ServeFuture(0),
            enqueue_t=now,
            deadline_t=now + deadline_ms / 1e3 if deadline_ms else None,
            warm_hit=warm,
            max_block_weights=max_block_weights,
            min_epsilon=float(min_epsilon),
            min_block_weights=min_block_weights,
            quality=quality,
            trace_id=tid,
        )
        req.future.request_id = req.id
        from ..telemetry import trace as ttrace

        rec = ttrace.active()
        if self._journal is not None:
            # Install the resolution funnel BEFORE the queue insert: the
            # dispatcher may resolve the request the instant it is
            # queued, and a first-wins finalization racing ahead of the
            # hook would leave the entry unresolved forever (replayed as
            # duplicate work on every restart).  A resolve record landing
            # before its admit record is fine — read_journal matches by
            # id, not by order.
            req.future._on_done = (
                lambda result, error, _id=req.id:
                    self._journal_resolution(_id, result, error)
            )
        try:
            self._queue.put(req)
        except QueueFullError:
            if self._journal is not None:
                req.future._on_done = None  # never admitted: nothing to log
            self.stats_.bump("rejected_full")
            retry_after = self.stats_.retry_after_estimate(
                len(self._queue), self.serve.max_batch
            )
            self.reqtrace.record(tid, "reject", engine=self.name,
                                 reason="queue_full",
                                 retry_after_s=round(retry_after, 3))
            if self._slo is not None:
                self._slo.record_reject(capacity=False)
            if rec is not None:
                rec.instant("serve.reject", request_id=req.id,
                            retry_after_s=round(retry_after, 3))
            raise QueueFullError(retry_after) from None
        self.stats_.bump("admitted")
        self.reqtrace.bind(req.id, tid)
        self.reqtrace.record(
            tid, "admit", request_id=req.id, engine=self.name, k=req.k,
            n_bucket=cell.n_bucket, m_bucket=cell.m_bucket, warm_hit=warm,
            quality=quality, queue_position=req.queue_position,
        )
        if self._journal is not None:
            # Admitted => journaled: from here on, the only ways out of
            # the journal are a resolution record or a replay after
            # restart (serve/journal.py).
            self._journal_admit(req)
        if rec is not None:
            # Queue lifecycle point: admission (the matching dispatch/resolve
            # events come from the dispatcher thread's batch span).
            rec.instant("serve.admit", request_id=req.id, k=req.k,
                        n_bucket=cell.n_bucket, m_bucket=cell.m_bucket,
                        warm_hit=warm)
            rec.counter("serve.queue", {"depth": len(self._queue)})
        return req.future

    def partition(
        self,
        graph,
        k: int,
        epsilon: float = 0.03,
        *,
        deadline_ms: Optional[float] = None,
        max_block_weights: Optional[Sequence[int]] = None,
        min_epsilon: float = 0.0,
        min_block_weights: Optional[Sequence[int]] = None,
        quality: str = "strong",
    ) -> np.ndarray:
        """Synchronous convenience wrapper: submit + wait, returning the
        (n,) block array — the facade delegates here when constructed with
        an engine.  Auto-starts a not-yet-started engine *without* warmup
        (call :meth:`start` yourself to pay warmup at a chosen moment)."""
        if not self._running:
            self.start(warmup=False)
        fut = self.submit(
            graph, k, epsilon,
            deadline_ms=deadline_ms,
            max_block_weights=max_block_weights,
            min_epsilon=min_epsilon,
            min_block_weights=min_block_weights,
            quality=quality,
        )
        return fut.result().partition

    # -- request tracing (round 20, telemetry/reqtrace.py) -----------------

    def _final_error(self, error) -> bool:
        """Whether a typed failure terminates the request's trace chain.
        The "engine gave it back" classes (EngineStoppedError, WorkerHung,
        watchdog/shutdown ExecuteFault) are resteerable or replayable —
        the chain continues on a sibling replica or after restart."""
        from ..resilience.errors import ExecuteFault, WorkerHung

        if isinstance(error, (EngineStoppedError, WorkerHung)):
            return False
        return not (
            isinstance(error, ExecuteFault)
            and getattr(error, "site", "") in ("watchdog", "shutdown")
        )

    def _trace_event(self, req: ServeRequest, event: str,
                     final: bool = False, **fields) -> None:
        """Record one request-trace event (pure host dict append).  On a
        terminal event (``final=True``) the request's whole chain is
        rendered onto a per-request lane of the active Chrome trace."""
        tid = req.trace_id
        if not tid:
            return
        if event in ("resolve", "error"):
            fields["final"] = bool(final)
        self.reqtrace.record(tid, event, request_id=req.id,
                             engine=self.name, **fields)
        if final:
            from ..telemetry import trace as ttrace

            rec = ttrace.active()
            if rec is not None:
                from ..utils.timer import scoped_timer

                with scoped_timer("reqtrace_export"):
                    self.reqtrace.export_chrome(rec, tid)

    def explain(self, request_id: int) -> Optional[dict]:
        """Structured dossier for one request: its time-ordered trace
        event chain (admit, dispatch, lane-stack cohort, demotion,
        resolve/error, journal replay ...) plus a connectivity verdict —
        ``None`` for unknown/evicted ids.  Pure host work (counted under
        ``reqtrace_export``; a device pull here is a contract
        violation)."""
        from ..utils.timer import scoped_timer

        with scoped_timer("reqtrace_export"):
            return self.reqtrace.explain_request(int(request_id))

    # -- dispatcher --------------------------------------------------------

    def _loop(self) -> None:
        while True:
            self._gate.wait()
            batch = self._queue.pop_batch(
                self.serve.max_batch, self.serve.batch_window_ms / 1e3,
                gate=self._gate,
            )
            if batch is None:
                return  # closed + drained: graceful exit
            try:
                self._execute_batch(batch)
            except Exception as exc:  # noqa: BLE001 — a poisoned batch must
                # not kill the dispatcher; classify the failure (round 17
                # hierarchy) and reject its requests with the typed error.
                from ..resilience.errors import classify

                err = classify(exc, site="dispatch")
                if batch:
                    key = (
                        batch[0].cell.n_bucket, batch[0].cell.m_bucket,
                        batch[0].cell.k,
                    )
                    self.breakers.get("cell", key).record_failure()
                for req in batch:
                    if req.future._reject(err):
                        self._trace_event(
                            req, "error",
                            final=self._final_error(err),
                            failure_class=getattr(
                                err, "failure_class", type(err).__name__
                            ),
                            site="dispatch",
                        )
                        wait = time.monotonic() - req.enqueue_t
                        self.stats_.record_request(wait, 0.0, failed=True)
                        if self._slo is not None:
                            self._slo.record_request(
                                req.quality, wait, ok=False
                            )

    def _execute_batch(self, batch: List[ServeRequest]) -> None:
        now = time.monotonic()
        live: List[ServeRequest] = []
        for req in batch:
            if req.future.cancelled:
                self.stats_.bump("cancelled")
                self._trace_event(req, "error", final=True,
                                  failure_class="cancelled")
                req.future._reject(RequestCancelledError(f"request {req.id}"))
            elif req.expired(now):
                self.stats_.bump("timed_out")
                wait = now - req.enqueue_t
                self._trace_event(req, "error", final=True,
                                  failure_class="deadline",
                                  queue_wait_ms=round(wait * 1e3, 1))
                if self._slo is not None:
                    self._slo.record_request(req.quality, wait, ok=False)
                req.future._reject(DeadlineExceededError(
                    f"request {req.id} expired after "
                    f"{(now - req.enqueue_t) * 1e3:.1f}ms in queue"
                ))
            elif req.future._mark_started():
                live.append(req)
            else:
                self.stats_.bump("cancelled")
                self._trace_event(req, "error", final=True,
                                  failure_class="cancelled")
                req.future._reject(RequestCancelledError(f"request {req.id}"))
        if not live:
            return
        self.stats_.record_batch(len(live))
        for req in live:
            # Batch-join lifecycle point: this request dispatches as part
            # of a formed micro-batch (occupancy = the lane axis).
            self._trace_event(req, "dispatch", occupancy=len(live))
        from ..telemetry import trace as ttrace

        rec = ttrace.active()
        if rec is not None:
            cell = live[0].cell
            rec.begin("serve.batch", occupancy=len(live), k=cell.k,
                      n_bucket=cell.n_bucket, m_bucket=cell.m_bucket)

        with self._lock:
            self._inflight = list(live)
        try:
            # Execution watchdog (round 17): a hung compile/execute inside
            # this batch has its futures force-resolved with a typed
            # ExecuteFault and its cell breaker tripped after
            # resilience.execute_timeout_s (0 disarms) — the dispatch is
            # abandoned, not cancelled, and its late result discarded by
            # the idempotent futures.
            with self.watchdog.guard(
                "serve_execute", self.resilience.execute_timeout_s,
                on_timeout=lambda d, c=live[0].cell, lv=list(live):
                    self._on_hang(c, d, lv),
            ):
                self._execute_live(live)
        finally:
            with self._lock:
                self._inflight = []
            if rec is not None:
                rec.end("serve.batch")
                rec.counter("serve.queue", {"depth": len(self._queue)})

    def _lane_stack_mode(self) -> str:
        """Effective lane-stack routing: env kill switch > serve context.
        Values are normalized (case/whitespace); an unrecognized value at
        dispatch time disables the stacked path (kill-switch-biased — a
        typo'd override must never silently keep the feature on), while
        an invalid *configured* value raises at engine construction."""
        import os

        mode = (
            os.environ.get("KAMINPAR_TPU_LANE_STACK", "")
            or getattr(self.serve, "lane_stack", "off")
        ).strip().lower()
        return mode if mode in ("auto", "on", "off") else "off"

    def _lanestack_fallback(self, reason: str, warn: bool) -> None:
        """Count one lane-stack fallback to the per-graph loop and, when
        ``warn``, surface the reason as a RuntimeWarning."""
        self.stats_.bump("lanestack_fallbacks")
        if warn:
            import warnings

            warnings.warn(
                f"kaminpar_tpu serve: {reason}; falling back to the "
                "per-graph loop.",
                RuntimeWarning,
                stacklevel=3,
            )

    def _try_lanestacked(
        self, live: List[ServeRequest]
    ) -> Optional[List[ServeRequest]]:
        """Run the whole batch as ONE vmapped lane-stacked program
        (serve/lanestack.py) when routing and eligibility allow; returns
        the fulfilled requests, or None to fall back to the per-graph loop
        (fallbacks are counted, and warned under ``lane_stack="on"``)."""
        mode = self._lane_stack_mode()
        if mode == "off" or (mode != "on" and len(live) < 2):
            return None
        cell_key = (
            live[0].cell.n_bucket, live[0].cell.m_bucket, live[0].cell.k
        )
        breaker = self.breakers.get("lanestack", cell_key)
        if not breaker.allow():
            # Breaker open (round 17, generalizing the round-11 latch):
            # skip the doomed stacked attempt — the demotion counter keeps
            # surfacing the lost parallelism, the trip itself already
            # warned, and the post-cooldown half-open probe re-arms the
            # stacked path without an engine restart.
            self.stats_.bump("lanestack_fallbacks")
            self.breakers.record_demotion(
                "lanestack", "circuit breaker open", warn=False
            )
            return None
        # Per-request constraint overrides (and non-strong quality tiers)
        # are outside the lockstep envelope: the stacked pipeline computes
        # every lane's caps from (k, epsilon), which the shape cell
        # already holds fixed, on the full-refinement chain.
        if any(
            r.max_block_weights is not None
            or r.min_block_weights is not None
            or r.min_epsilon
            or r.quality != "strong"
            for r in live
        ) or len({r.epsilon for r in live}) != 1:
            self._lanestack_fallback(
                "lane_stack=on but the batch carries per-request "
                "constraint overrides or mixed epsilons",
                warn=mode == "on",
            )
            return None
        from ..utils import compile_stats
        from .lanestack import LaneStackUnsupported, run_lanestacked

        pre_compiles = compile_stats.compile_time_snapshot()["compile_events"]
        t0 = time.perf_counter()
        try:
            with self.runtime.activate():
                parts, report = run_lanestacked(
                    self._solver.ctx, [r.graph for r in live],
                    live[0].k, live[0].epsilon,
                    trace_lane=self.name,
                )
        except LaneStackUnsupported as exc:
            self._lanestack_fallback(
                f"lane_stack=on but the batch is outside the lane-stack "
                f"envelope ({exc})",
                warn=mode == "on",
            )
            return None
        except Exception as exc:  # noqa: BLE001 — a lane-stack failure must
            # not reject a batch the per-graph loop can still serve; fall
            # back LOUDLY in every mode (the per-graph results remain
            # correct, the warning and counter surface the lost
            # parallelism).  The failure is classified and recorded on the
            # per-cell lanestack breaker; tripping it skips the doomed
            # attempt on later batches until the half-open probe recovers.
            from ..resilience.errors import classify

            err = classify(exc, site="lanestack")
            self._lanestack_fallback(
                f"lane-stacked execution failed "
                f"({err.failure_class}: {exc})",
                warn=True,
            )
            self.breakers.record_demotion(
                "lanestack", err.failure_class, warn=False
            )
            if breaker.record_failure():
                import warnings

                warnings.warn(
                    "kaminpar_tpu serve: lane-stacked execution failed on "
                    f"{breaker.threshold} consecutive batches in cell "
                    f"{cell_key} — disabling the stacked path for this "
                    "cell (the per-graph loop keeps serving; a half-open "
                    f"probe re-arms it after {breaker.cooldown_s}s).",
                    RuntimeWarning,
                    stacklevel=2,
                )
            return None
        wall = time.perf_counter() - t0
        if breaker.record_success():
            self.breakers.record_restoration("lanestack")
        # The stacked path serves these requests INSTEAD of the per-graph
        # loop, so it must also report the cell breaker's outcome — a
        # half-open cell probe served stacked would otherwise never close
        # the breaker and pin a healthy cell at one-probe-per-cooldown.
        cbr = self.breakers.get("cell", cell_key)
        if cbr.record_success():
            self.breakers.record_restoration("cell")
        # Key warm accounting on what this batch ACTUALLY dispatched: the
        # runner's recorded layout key (level-0 stack buckets + per-level
        # layout signatures x lane counts) with (k, epsilon) — the request
        # cell alone can't name the executables, because the isolated-node
        # strip moves work graphs across buckets and cohort splits change
        # lane counts.
        stack_key = (report.layout_key, live[0].k, live[0].epsilon)
        compiled = (
            stack_key not in self._warm_stack_keys
            and compile_stats.compile_time_snapshot()["compile_events"]
            > pre_compiles
        )
        # The submit-time warm flag covers the per-graph (bucket, k)
        # executable; a stacked batch's warmth is the lane-stack cell's —
        # correct the accounting in BOTH directions so warm_hit tracks
        # whether this request's dispatch actually avoided a compile
        # spike.  Gating the demotion on the engine-local key set keeps
        # compiles raised by OTHER engines/facades in the process (the
        # census is global) from demoting a batch whose stacked cell this
        # engine already ran.
        for req in live:
            if compiled and req.warm_hit:
                req.warm_hit = False
                self.stats_.bump("warm_hits", -1)
                self.stats_.bump("warm_misses")
            elif not compiled and not req.warm_hit:
                req.warm_hit = True
                self.stats_.bump("warm_hits")
                self.stats_.bump("warm_misses", -1)
        self._warm_stack_keys.add(stack_key)
        share = wall / len(live)
        self.stats_.bump("lanestacked_batches")
        self.stats_.bump("lanestacked_lanes", len(live))
        self.stats_.bump("lanestack_splits", report.splits)
        lane_cohorts = getattr(report, "lane_cohorts", ()) or ()
        for i, req in enumerate(live):
            # One stacked program serves all lanes; each request's execute
            # share is the batch wall over occupancy, and the rest of the
            # stacked wall counts as queue wait so queue_wait + execute
            # still covers the full submit->resolve wall (the per-graph
            # loop's percentile invariant).
            req.queue_wait_s = time.monotonic() - req.enqueue_t - share
            req.partition = parts[i]
            req.caps = report.caps[i]
            req.execute_s = share
            req.service_s = wall
            # Lane-stack lifecycle point: which cohort of the stacked
            # program this request's lane rode (cohort splits re-bucket
            # lanes whose work graphs left the request cell).
            self._trace_event(
                req, "lanestack", lane=i,
                cohort=(int(lane_cohorts[i])
                        if i < len(lane_cohorts) else 0),
                cohorts=report.cohorts, lanes=report.lanes,
                splits=report.splits,
            )
        return list(live)

    def _request_solver(self, req: ServeRequest):
        """The solver serving this request, after the quality ladder rung:
        explicit ``quality="fast"`` requests take the trimmed solver; a
        "strong" request is demoted to it when the cell's quality breaker
        is open (capacity-class failures tripped it) — counted, warned
        once, and restored by the half-open probe."""
        if req.quality == "fast":
            return self._get_fast_solver(), False
        key = (req.cell.n_bucket, req.cell.m_bucket, req.cell.k)
        qbreaker = self.breakers.get("quality_strong", key)
        if not qbreaker.allow():
            self.stats_.bump("demoted_quality")
            self.breakers.record_demotion(
                "quality_strong", "capacity pressure in this cell"
            )
            # Demotion-ladder lifecycle point: the quality_strong rung
            # served this strong request with the fast tier.
            self._trace_event(req, "demote", rung="quality_strong",
                              served="fast")
            return self._get_fast_solver(), False
        return self._solver, True

    def _get_fast_solver(self):
        """Lazily-built trimmed-refinement solver: the balancer+LP chain
        with halved LP sweeps and single-rep extension — the same
        deterministic pipeline shape, a lighter quality tier."""
        if self._fast_solver is None:
            from ..context import RefinementAlgorithm
            from ..kaminpar import KaMinPar

            fast = copy.deepcopy(self.ctx)
            keep = (
                RefinementAlgorithm.OVERLOAD_BALANCER,
                RefinementAlgorithm.LP,
                RefinementAlgorithm.UNDERLOAD_BALANCER,
                RefinementAlgorithm.GREEDY_BALANCER,
            )
            fast.refinement.algorithms = tuple(
                a for a in fast.refinement.algorithms if a in keep
            ) or (RefinementAlgorithm.OVERLOAD_BALANCER,
                  RefinementAlgorithm.LP)
            fast.refinement.lp.num_iterations = max(
                1, fast.refinement.lp.num_iterations // 2
            )
            fast.initial_partitioning.nested_extension_reps = 1
            fast.initial_partitioning.device_extension_reps = 1
            self._fast_solver = KaMinPar(fast)
        return self._fast_solver

    def _execute_live(self, live: List[ServeRequest]) -> None:
        from ..resilience.errors import classify
        from ..resilience.faults import maybe_inject

        ok = self._try_lanestacked(live)
        stacked = ok is not None
        if ok is None:
            ok = []
            for req in live:
                # Queue wait runs until THIS request's execution starts, so
                # a late batch member's wait includes in-batch serialization
                # — reported percentiles must cover the full submit->resolve
                # wall.
                req.queue_wait_s = time.monotonic() - req.enqueue_t
                t0 = time.perf_counter()
                key = (req.cell.n_bucket, req.cell.m_bucket, req.cell.k)
                # Provisional tier for the except path (a fault can fire
                # before _request_solver resolves the actual tier).
                strong = req.quality == "strong"
                try:
                    maybe_inject("execute", site="engine_request")
                    solver, strong = self._request_solver(req)
                    req.quality_served = "strong" if strong else "fast"
                    # The warm facade runs the *identical* code path a cold
                    # sequential KaMinPar.compute_partition runs (including
                    # its per-call RNG reseed), so per-graph results are
                    # bit-identical to single-graph runs by construction.
                    solver.set_graph(req.graph)
                    req.partition = solver.compute_partition(
                        req.k, req.epsilon, req.max_block_weights,
                        req.min_epsilon, req.min_block_weights,
                    )
                    req.caps = np.asarray(
                        solver.ctx.partition.max_block_weights,
                        dtype=np.int64,
                    ).copy()
                    req.execute_s = time.perf_counter() - t0
                    ok.append(req)
                    if not req.future.done():
                        # A done future means the watchdog already rejected
                        # this request as hung and TRIPPED the breaker —
                        # the late-returning dispatch must not record a
                        # success that would silently close it (the next
                        # request would re-enter the same hang).
                        cbr = self.breakers.get("cell", key)
                        if cbr.record_success():
                            self.breakers.record_restoration("cell")
                        if strong:
                            qbr = self.breakers.get("quality_strong", key)
                            if qbr.record_success():
                                self.breakers.record_restoration(
                                    "quality_strong"
                                )
                except Exception as exc:  # noqa: BLE001 — per-request isolation
                    # Route through the ONE classifier (round 17): callers
                    # get a typed failure, and the failure class picks the
                    # breaker — capacity pressure trips the quality rung
                    # (later strong requests demote to fast), everything
                    # else trips the cell breaker (enough repeats poison
                    # the cell at admission).  A False reject means the
                    # watchdog already force-resolved this future AND
                    # recorded the failure + breaker trip — don't
                    # double-count the late arrival.
                    err = classify(exc, site="engine_request")
                    if req.future._reject(err):
                        if err.failure_class == "capacity-exceeded" and strong:
                            self.breakers.get(
                                "quality_strong", key
                            ).record_failure()
                        else:
                            # Fast-tier capacity failures land here too:
                            # a cell that OOMs even under the trimmed
                            # solver has no further rung to demote to —
                            # it must poison at admission, not burn a
                            # doomed dispatch per request.
                            self.breakers.get("cell", key).record_failure()
                        exec_s = time.perf_counter() - t0
                        self._trace_event(
                            req, "error", final=self._final_error(err),
                            failure_class=err.failure_class,
                            site="engine_request",
                        )
                        self.stats_.record_request(
                            req.queue_wait_s, exec_s, failed=True,
                        )
                        if self._slo is not None:
                            self._slo.record_request(
                                req.quality_served or req.quality,
                                req.queue_wait_s + exec_s, ok=False,
                            )
        if not ok:
            return

        # Whole-batch quality metrics in ONE dispatch over the packed
        # disjoint-union buffer + one batched readback (serve/batching.py).
        t_metrics = time.perf_counter()
        cuts, bws = batched_metrics(
            pack_graphs([r.graph for r in ok]),
            [r.partition for r in ok],
            ok[0].k,
            pad_to=self.serve.max_batch,
        )
        metrics_share_s = (time.perf_counter() - t_metrics) / len(ok)
        from ..telemetry import trace as ttrace

        rec = ttrace.active()
        for i, req in enumerate(ok):
            req.execute_s += metrics_share_s
            if not stacked:
                # A stacked batch traces only lane-stack executables — it
                # does not warm the per-graph (bucket, k) cell, so marking
                # it here would report a later lone request in this cell
                # as a warm hit while it pays the full per-graph compile
                # (the stacked path tracks its own _warm_stack_keys).
                self._note_warm(
                    req.cell, req.quality_served or req.quality
                )
            feasible = bool(np.all(bws[i] <= req.caps))
            resolved = req.future._resolve(ServeResult(
                partition=req.partition,
                cut=int(cuts[i]),
                feasible=feasible,
                batch_size=len(ok),
                queue_wait_s=req.queue_wait_s,
                execute_s=req.execute_s,
                warm_hit=req.warm_hit,
                request_id=req.id,
            ))
            if not resolved:
                # The watchdog already force-resolved this future (the
                # dispatch was abandoned as hung and came back late): the
                # failure was recorded there — don't double-count.
                continue
            self.stats_.record_request(
                req.queue_wait_s, req.execute_s, service_s=req.service_s
            )
            self._trace_event(
                req, "resolve", final=True, cut=int(cuts[i]),
                feasible=feasible, batch=len(ok),
                quality=req.quality_served or req.quality,
                queue_wait_ms=round(req.queue_wait_s * 1e3, 2),
                execute_ms=round(req.execute_s * 1e3, 2),
            )
            if self._slo is not None:
                self._slo.record_request(
                    req.quality_served or req.quality,
                    req.queue_wait_s + req.execute_s, ok=True,
                )
            if rec is not None:
                rec.instant(
                    "serve.resolve", request_id=req.id, cut=int(cuts[i]),
                    feasible=feasible,
                    queue_wait_ms=round(req.queue_wait_s * 1e3, 2),
                    execute_ms=round(req.execute_s * 1e3, 2),
                )

    # -- observability -----------------------------------------------------

    def steer_signals(self) -> dict:
        """Cheap live serving signals for the fleet router (round 18) —
        queue depth, the unamortized service-time EMA, p99 execute
        seconds, open-breaker counts, watchdog fires — WITHOUT the full
        snapshot's compile/sync census cost.  Pure host reads."""
        return {
            "running": self._running,
            "queue_depth": len(self._queue),
            "ema_service_s": self.stats_.service_time_estimate(),
            "p99_execute_s": self.stats_.execute_p99_s(),
            "open_breakers": self.breakers.open_count(),
            "open_cell_breakers": self.breakers.open_count("cell"),
            "watchdog_timeouts": self.stats_.counter("watchdog_timeouts"),
            "max_batch": self.serve.max_batch,
            # SLO control pressure (round 20): max(0, worst_burn - 1),
            # briefly memoized — 0.0 whenever objectives are disarmed, so
            # the steering score is unchanged unless a deployment arms
            # them (bit-identity: control input only).
            "slo_pressure": (
                self._slo.pressure() if self._slo is not None else 0.0
            ),
        }

    def cell_depth(self, cell: ShapeCell) -> int:
        """Queued same-cell requests (the router's batch-join signal)."""
        return self._queue.cell_depth(cell)

    def capacity_verdict(self, graph, k: int) -> bool:
        """Would the admission preflight accept this request?  A pure
        non-raising, non-counting probe for the fleet router's steering
        score — per-replica ceilings can differ, so a request a small
        replica must reject may still be steerable to a bigger one.  True
        when no ceiling is knowable (preflight off).  Same invocation as
        the admission path (:meth:`_run_preflight`)."""
        try:
            self._run_preflight(graph, k)
        except CapacityError:
            return False
        return True

    def warmup_cell_counts(self) -> dict:
        """Inherited vs locally-compiled warmup cells (round 18 warm-cache
        inheritance; printed by ``tools warmup --fleet``)."""
        inherited = sum(
            1 for r in self.warmup_report if r.get("inherited")
        )
        return {
            "inherited": inherited,
            "local": len(self.warmup_report) - inherited,
        }

    def stats(self) -> dict:
        """Structured snapshot: queue depth, admission/reject/timeout
        counts, batch occupancy, warm-cache hit rate, latency percentiles,
        plus the compile-shape and blocking-transfer censuses."""
        snap = self.stats_.snapshot(queue_depth=len(self._queue))
        snap["running"] = self._running
        snap["warm_cells"] = len(self._warm_cells)
        snap["warmup"] = list(self.warmup_report)
        snap["warmup_cells"] = self.warmup_cell_counts()
        # Resilience surface (round 17): this engine's breaker registry
        # (lanestack/cell/quality rungs), the process-global pipeline
        # registry (the device_decode rung), the
        # watchdog's guard/fire census + dossier heads, and the chaos
        # harness's injection counters.
        from ..resilience import breakers as rbreakers
        from ..resilience import faults as rfaults

        snap["resilience"] = {
            "engine": self.breakers.snapshot(),
            "pipeline": rbreakers.global_registry().snapshot(),
            "watchdog": self.watchdog.snapshot(),
            "faults": rfaults.snapshot(),
        }
        # Crash-safe journal surface (round 19, serve/journal.py):
        # append/fsync counts of the live journal file — the replay and
        # resolution counters ride the standard counter block above.
        if self._journal is not None:
            snap["journal"] = self._journal.snapshot()
        # SLO burn surface (round 20, telemetry/slo.py): per-window
        # error-budget burn rates + the control pressure the fleet
        # steering/autoscale consume.  Pure host scan of the event ring,
        # counted under slo_eval.
        from ..utils.timer import scoped_timer

        with scoped_timer("slo_eval"):
            snap["slo"] = (
                self._slo.summary() if self._slo is not None
                else {"armed": False}
            )
        snap["reqtrace"] = self.reqtrace.snapshot()
        return snap

    def metrics_text(self) -> str:
        """Prometheus text exposition of the serving metrics (ISSUE 5):
        queue depth, admission/reject/timeout counts, batch occupancy,
        warm-cache hit rate, p50/p90/p99 latencies, and the compile-shape /
        blocking-transfer censuses.  The serve CLI's ``--metrics-port``
        serves this at ``/metrics``; scrape-friendly and dependency-free
        (telemetry/prometheus.py)."""
        from ..telemetry import prometheus
        from ..utils import compile_stats

        families = self.stats_.prometheus_families(
            queue_depth=len(self._queue),
            running=self._running,
            warm_cells=len(self._warm_cells),
        )
        # Executable census families (ISSUE 12): per-cell flops / peak /
        # temp bytes from XLA's own analyses, exported beside the serve
        # metrics so operators scrape what each executable WOULD do.
        families.extend(compile_stats.census_prometheus_families())
        # Resilience families (round 17): breaker states/trips, ladder
        # demotions + restorations, chaos injections — merged over this
        # engine's registry and the process-global pipeline registry.
        from ..resilience import breakers as rbreakers

        families.extend(rbreakers.prometheus_families(
            self.breakers, rbreakers.global_registry()
        ))
        families.append((
            "kaminpar_resilience_watchdog_fired_total", "counter",
            "Execution-watchdog deadline overruns converted into breaker "
            "trips + typed future resolutions",
            [({}, self.watchdog.fired)],
        ))
        # Warm-cache inheritance census (round 18): how many warmup cells
        # this replica inherited from the fleet vs compiled locally.
        cells = self.warmup_cell_counts()
        families.append((
            "kaminpar_serve_warmup_cells_total", "counter",
            "Warmup-report cells by source: inherited from the fleet's "
            "warm state vs locally traced/compiled",
            [({"source": "inherited"}, cells["inherited"]),
             ({"source": "local"}, cells["local"])],
        ))
        # SLO burn families (round 20, telemetry/slo.py) — empty unless
        # the ServeContext arms at least one objective.
        from ..telemetry import slo as slo_mod

        families.extend(slo_mod.prometheus_families(self._slo))
        return prometheus.render(families)
