"""Mesh-replicated serve fleet: a shape-cell router over per-device
engine replicas (round 18; the ROADMAP "millions of users" tier).

:class:`PartitionFleet` scales the single warm :class:`PartitionEngine`
to a fleet — one engine replica per mesh device (CPU dryrun: the forced
virtual host devices the ``shard_ab`` bench already uses), each pinned to
its device through the :class:`~kaminpar_tpu.context.EngineRuntime`
placement hook.  The front router classifies every request by its
existing :func:`~kaminpar_tpu.serve.batching.shape_cell` and steers it
with an **SLO-aware score** over the replicas' live serving signals —
queue drain estimate (the unamortized service-time EMA times depth over
batch width), p99 execute seconds, open breakers for the request's cell,
and the capacity-preflight verdict — instead of a single EMA:

* **lane x device 2D execution** — same-cell load fans *in* per replica
  up to the engine's ``max_batch`` (a score bonus for joining a forming
  batch fills the lane axis, where PR 6's vmapped lane-stacked dispatch
  runs the whole micro-batch as ONE program), then spills to the next
  device (the device axis).  Aggregate occupancy = replicas x lanes.
* **graph-id-sticky routing** — a request carrying ``graph_id`` keeps
  landing on the replica that first served it while that replica stays
  healthy, so a tenant's warm graph state stays device-local (the hook
  the incremental-repartitioning ROADMAP item composes with).
* **warm-cache inheritance** — replica N+1 shares the fleet's persistent
  compilation cache dir and imports the first replica's warmup report
  (:meth:`PartitionEngine.inherit_warmup`), skipping every cell already
  traced; inherited-vs-local counts ride ``warmup_report``/Prometheus.
* **drain + cross-replica resteer** — a replica whose watchdog trips or
  whose cell breakers latch open is drained: queued work is requeued on
  healthy replicas eagerly, in-flight work finishes (or is force-resolved
  typed by PR 13's bounded-drain machinery and resteered lazily), and
  nothing is lost or resolved twice (:class:`FleetFuture` rebinds with
  first-wins finalization).  The fleet-scoped ``replica`` breaker rung
  restores a drained replica through the standard half-open probe.

* **elastic scaling** (round 19) — :meth:`PartitionFleet.scale_to`
  resizes the fleet under live traffic: scale-up revives retired slots
  (warm state carries over) before spawning fresh inheriting replicas;
  scale-down retires the highest-index replicas through the drain/
  resteer machinery with conserved resolutions.  An optional
  ``autoscale`` policy (queue-drain watermarks with hysteresis, driven
  from the submit-path health sweep) sizes the fleet automatically, and
  a replica the health sweep takes out is *replaced*, not just drained
  (``replace_drained``).  Census in ``stats()`` ``fleet_scale_*``
  counters / ``kaminpar_fleet_scale_total``.

CPU-dryrun honesty: virtual host devices SERIALIZE — a CPU fleet number
is a router/occupancy claim, not a parallel-speedup claim; a
device-axis throughput claim needs a run on chips.
"""

from __future__ import annotations

import copy
import itertools
import threading
import time
import warnings
from typing import Dict, List, Optional, Sequence, Union

from ..context import Context, FleetContext
from ..resilience.breakers import BreakerRegistry
from ..resilience.errors import ExecuteFault, WorkerHung
from .batching import ShapeCell, shape_cell
from .engine import PartitionEngine, ServeFuture, ServeResult
from .errors import EngineStoppedError, QueueFullError


def _is_resteerable(exc: BaseException) -> bool:
    """Failures that mean "this replica gave the request back", not "this
    request is bad": a draining replica rejecting queued work, a hung
    dispatcher's bounded-drain force-resolution, and the watchdog's typed
    abandonment of an in-flight batch.  Everything else (deadline, cancel,
    a genuine pipeline fault) surfaces to the caller unchanged."""
    if isinstance(exc, (EngineStoppedError, WorkerHung)):
        return True
    return isinstance(exc, ExecuteFault) and getattr(exc, "site", "") in (
        "watchdog", "shutdown"
    )


class _FleetRecord:
    """Mutable routing state of one fleet request (internal)."""

    __slots__ = (
        "fleet_id", "graph", "k", "epsilon", "kwargs", "graph_id",
        "replica", "current", "attempts", "lock", "trace_id",
    )

    def __init__(self, fleet_id: int, graph, k: int, epsilon: float,
                 kwargs: dict, graph_id, trace_id: str = ""):
        self.fleet_id = fleet_id
        self.graph = graph
        self.k = int(k)
        self.epsilon = float(epsilon)
        self.kwargs = dict(kwargs)
        self.graph_id = graph_id
        self.replica: int = -1
        self.current: Optional[ServeFuture] = None
        self.attempts = 0
        self.lock = threading.Lock()
        # Request-scoped trace id (round 20): minted at steer time and
        # passed to every engine submit this record makes, so the whole
        # cross-replica life is one connected event chain.
        self.trace_id = str(trace_id)


class FleetFuture:
    """Completion handle for a fleet-routed request.

    Wraps the engine-level :class:`ServeFuture` the request is currently
    bound to; when that future resolves with a *resteerable* typed error
    (the bound replica drained or hung), the waiter triggers a
    cross-replica requeue and re-waits on the new binding.  Finalization
    is first-wins: exactly one result (or terminal error) per request,
    however many times the binding moved."""

    def __init__(self, fleet: "PartitionFleet", record: _FleetRecord):
        self._fleet = fleet
        self._record = record
        self._final_result: Optional[ServeResult] = None
        self._final_error: Optional[BaseException] = None
        self._finalized = threading.Event()
        self._lock = threading.Lock()

    @property
    def fleet_id(self) -> int:
        return self._record.fleet_id

    @property
    def replica(self) -> int:
        """Index of the replica currently (or finally) serving this
        request — may change across resteers."""
        return self._record.replica

    def cancel(self) -> bool:
        # Lock-free attribute read: ``current`` swaps atomically under the
        # record lock, and a resteer may hold that lock through bounded
        # backpressure waits — cancel/done must stay non-blocking (a
        # stale read here at worst cancels the abandoned binding, which
        # the resteer already gave up on).
        fut = self._record.current
        return fut.cancel() if fut is not None else False

    def done(self) -> bool:
        if self._finalized.is_set():
            return True
        fut = self._record.current  # lock-free: see cancel()
        return fut is not None and fut.done()

    def _finalize(self, result=None, error=None) -> None:
        with self._lock:
            if self._finalized.is_set():
                return
            self._final_result = result
            self._final_error = error
            self._finalized.set()

    def result(self, timeout: Optional[float] = None) -> ServeResult:
        deadline = None if timeout is None else time.monotonic() + timeout
        rec = self._record
        while True:
            if self._finalized.is_set():
                if self._final_error is not None:
                    raise self._final_error
                assert self._final_result is not None
                return self._final_result
            # Lock-free read (see cancel()): _resteer holds rec.lock
            # through bounded backpressure sleeps — taking it here would
            # block a result(timeout=...) caller past its deadline.  A
            # stale binding is safe: it resolves with the typed abandon
            # error and the loop re-reads after _maybe_resteer.
            fut = rec.current
            assert fut is not None
            remaining = (
                None if deadline is None
                else max(0.0, deadline - time.monotonic())
            )
            try:
                res = fut.result(remaining)
            except TimeoutError:
                if self._finalized.is_set():
                    continue  # another waiter finalized while we timed out
                raise
            except Exception as exc:
                if self._fleet._maybe_resteer(rec, fut, exc):
                    continue  # rebound to a healthy replica; re-wait
                self._finalize(error=exc)
                self._fleet._forget(rec)
                raise
            self._fleet._note_success(rec)
            self._finalize(result=res)
            self._fleet._forget(rec)
            return res


class PartitionFleet:
    """Front router over N per-device :class:`PartitionEngine` replicas.

    Usage::

        from kaminpar_tpu.serve import PartitionFleet
        with PartitionFleet("serve", replicas=8) as fleet:
            fut = fleet.submit(graph, k=8, graph_id="tenant-42")
            part = fut.result().partition

    Thread model: ``submit`` steers from any caller thread (pure host
    arithmetic under the registered ``fleet_steer`` phase); each replica
    keeps its own single dispatcher thread, so device work per replica
    stays serialized and per-request determinism is inherited from the
    engine contract (asserted across replicas in tests/test_fleet.py).
    """

    def __init__(
        self,
        ctx: Union[Context, str, None] = None,
        replicas: Optional[int] = None,
        **serve_overrides,
    ):
        from ..presets import create_context_by_preset_name

        if ctx is None:
            ctx = create_context_by_preset_name("serve")
        elif isinstance(ctx, str):
            ctx = create_context_by_preset_name(ctx)
        else:
            ctx = copy.deepcopy(ctx)
        self.ctx = ctx
        self.fleet_ctx: FleetContext = ctx.fleet
        n = int(replicas if replicas is not None else self.fleet_ctx.replicas)
        if n <= 0:
            import jax

            n = len(jax.devices())
        self._serve_overrides = dict(serve_overrides)
        self.replicas: List[PartitionEngine] = []
        for i in range(n):
            rctx = copy.deepcopy(ctx)
            rctx.parallel.placement_device = i
            if rctx.serve.journal_path:
                # Per-replica journal files (round 19): one shared path
                # would interleave N engines' records with colliding
                # request ids, making replay ambiguous.
                rctx.serve.journal_path += f".replica{i}"
            self.replicas.append(
                PartitionEngine(rctx, name=f"replica{i}", **serve_overrides)
            )
        # ONE request-trace registry for the whole fleet (round 20,
        # telemetry/reqtrace.py): replicas share it so a request resteered
        # off a draining replica keeps one connected event chain across
        # engines (each engine-private registry would fragment the
        # dossier).  _spawn_replica re-attaches it to fresh replicas.
        from ..telemetry.reqtrace import ReqTrace

        self.reqtrace = ReqTrace()
        for eng in self.replicas:
            eng.reqtrace = self.reqtrace
        # Fleet-scoped breaker registry (round 18): one "replica" breaker
        # per replica index — tripped by drain_replica, restored by the
        # half-open probe at steering time (which restarts the engine).
        self.breakers = BreakerRegistry(
            threshold=ctx.resilience.breaker_threshold,
            cooldown_s=self.fleet_ctx.replica_cooldown_s,
            scope="fleet",
        )
        self._draining = [False] * n
        self._drain_threads: List[Optional[threading.Thread]] = [None] * n
        self._watchdog_seen = [0] * n
        # Elastic membership (round 19): a RETIRED slot was scaled down
        # on purpose — unroutable, NOT probe-restorable (an intentional
        # drain is not a health verdict) — and is revived cheaply by the
        # next scale-up (warm state carries over engine restarts).
        self._retired = [False] * n
        self._sticky: Dict[object, int] = {}
        self._records: Dict[int, _FleetRecord] = {}  # id(engine future) ->
        self._counters: Dict[str, int] = {
            "submitted": 0, "resteers": 0, "sticky_hits": 0,
            "sticky_moves": 0, "drains": 0, "restores": 0,
            "rejected_full": 0, "rejected_unroutable": 0,
            "rejected_capacity": 0,
            "steer_retries": 0, "probe_steers": 0,
            # Elastic-scaling census (round 19, ISSUE 15): scale_to
            # calls by direction, how each slot changed (fresh spawn vs
            # retired-slot revival vs retirement), health-sweep
            # replacements, and autoscale decisions.
            "fleet_scale_ups": 0, "fleet_scale_downs": 0,
            "fleet_scale_spawns": 0, "fleet_scale_revives": 0,
            "fleet_scale_retires": 0, "fleet_scale_replacements": 0,
            "fleet_scale_auto_ups": 0, "fleet_scale_auto_downs": 0,
        }
        # Autoscale hysteresis state: consecutive health sweeps above the
        # high / below the low queue-drain watermark.
        self._above_high = 0
        self._below_low = 0
        self._scale_lock = threading.Lock()
        # Sweep-triggered scaling (autoscale / replacement) runs on this
        # single background thread — spawning + warming a replica can
        # take seconds and must never block the submit path that happened
        # to run the health sweep.  One action at a time: a pending
        # action absorbs further triggers (the next sweep re-evaluates).
        self._bg_scale: Optional[threading.Thread] = None
        self._warmup_flag = True
        # Submit-path health-check throttle: the auto-drain sweep reads
        # every replica's signals — once per interval, not per request.
        self._health_interval_s = 0.05
        self._last_health_check = 0.0
        # Record-map prune watermark (done futures whose waiter never
        # returned; pruning only drops the drain-lookup entry — live
        # waiters hold the record object itself).
        self._prune_watermark = max(
            64, 2 * sum(r.serve.queue_bound for r in self.replicas)
        )
        # Sticky-map bound: LRU eviction past the watermark (reads
        # refresh recency) — tenant cardinality must not grow router
        # memory without bound.
        self._sticky_watermark = max(4096, 8 * self._prune_watermark)
        self._steered = [0] * n
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._started = False
        self._stopping = False

    @property
    def serve(self):
        """The fleet's serve knobs (replica 0's resolved ServeContext —
        all replicas share it; keeps the CLI/demo code engine-agnostic)."""
        return self.replicas[0].serve

    # -- lifecycle ---------------------------------------------------------

    def start(self, warmup: bool = True) -> "PartitionFleet":
        """Start every replica.  With warmup, replica 0 pays the ladder
        precompile once; replicas 1..N-1 inherit its warm state (report,
        warm cells, lane-stack keys, EMA seed) and skip every inherited
        cell — the compile-count delta of an inheriting replica's start is
        asserted to be zero in tests/test_fleet.py."""
        with self._lock:
            if self._started:
                return self
            self._started = True
            self._stopping = False
            # Remembered for elastic scale-up: a spawned replica starts
            # the way the fleet itself was started.
            self._warmup_flag = bool(warmup)
        first = self.replicas[0]
        first.start(warmup=warmup)
        for eng in self.replicas[1:]:
            if warmup and self.fleet_ctx.inherit_warm_cache:
                eng.inherit_warmup(first)
            eng.start(warmup=warmup)
        return self

    def pause(self) -> None:
        for eng in self.replicas:
            eng.pause()

    def resume(self) -> None:
        for eng in self.replicas:
            eng.resume()

    def shutdown(self, drain: bool = True) -> None:
        """Stop every replica (bounded per-replica drain; a hung replica's
        in-flight work is force-resolved typed by the engine's bounded
        shutdown — the fleet does not resteer during its own shutdown)."""
        with self._lock:
            if not self._started:
                return
            self._stopping = True
            bg = self._bg_scale
        if bg is not None:
            bg.join(self.fleet_ctx.drain_timeout_s)
        for t in self._drain_threads:
            if t is not None:
                t.join(self.fleet_ctx.drain_timeout_s)
        for i, eng in enumerate(self.replicas):
            if not self._draining[i]:
                eng.shutdown(
                    drain=drain, timeout_s=self.fleet_ctx.drain_timeout_s
                )
        with self._lock:
            self._records.clear()
            self._started = False

    def __enter__(self) -> "PartitionFleet":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown(drain=True)

    # -- steering ----------------------------------------------------------

    def _service_floor(self, eng: PartitionEngine) -> float:
        ema = eng.stats_.service_time_estimate()
        return max(ema, self.fleet_ctx.steer_service_floor_s)

    def _replica_available(self, idx: int, probe_ok: bool = True,
                           consume: bool = True):
        """(available, is_probe) for replica ``idx``.  A closed fleet
        breaker on a non-draining replica is the normal case; an
        open/half-open breaker grants the single half-open probe slot
        (``probe_ok``), restarting a drained engine for it — probe
        replicas are routed FIRST by ``_pick_replica`` (a probe is
        traffic: consuming the slot without sending a request would
        leave the replica demoted for another cooldown).

        ``consume=False`` peeks: same decision, but the probe slot is
        not consumed and the replica not restored — the candidate scan
        peeks first so its cell-breaker/capacity filters cannot burn a
        probe on a replica they then drop."""
        if self._stopping:
            return False, False
        if self._retired[idx]:
            # Scaled down on purpose: not a health failure, so no probe
            # traffic — only scale_to revives a retired slot.
            return False, False
        br = self.breakers.get("replica", (idx,))
        if br.state == "closed":
            if self._draining[idx]:
                return False, False
            return self.replicas[idx].running, False
        t = self._drain_threads[idx]
        if t is not None and t.is_alive():
            # The drain is still in progress (bounded shutdown running):
            # do NOT consume the half-open probe slot for it — restoring
            # now would join the drain thread on the submit hot path,
            # stalling a caller for up to the drain budget.
            return False, False
        if not probe_ok:
            return False, False
        if not consume:
            return br.would_allow(), True
        if not br.allow():
            return False, False
        # Half-open probe granted: restore the replica for it.
        self._restore_replica(idx)
        with self._lock:
            self._counters["probe_steers"] += 1
        return True, True

    def _restore_replica(self, idx: int) -> None:
        """Restart a drained replica for a half-open probe (warm state —
        solver caches, warm cells, stats — carries over engine restarts)."""
        t = self._drain_threads[idx]
        if t is not None:
            t.join(self.fleet_ctx.drain_timeout_s)
            self._drain_threads[idx] = None
        eng = self.replicas[idx]
        if not eng.running:
            eng.start(warmup=False)
        if self._draining[idx]:
            self._draining[idx] = False
            with self._lock:
                self._counters["restores"] += 1

    def _score(self, idx: int, cell: ShapeCell) -> float:
        """SLO-aware steering score (lower = better).

        queue term: drain-time estimate of the replica's queued work
        (depth x unamortized EMA / batch width — the PR 6 rule keeps the
        EMA unamortized for lane-stacked batches, so depth/batch-width is
        the only occupancy division).  p99 term: tail execute latency.
        Batch-join bonus: a forming same-cell batch (0 < depth <
        max_batch) attracts the request so the lane axis fills before
        load spills to the next device.

        SLO pressure term (round 20, telemetry/slo.py): a replica burning
        its declared error budget (max(0, worst_burn - 1), in
        service-time units per unit of excess burn) looks slower to the
        router and sheds new load to healthier siblings.  0 whenever
        objectives are disarmed — a control input only, never a
        partition input."""
        eng = self.replicas[idx]
        sig = eng.steer_signals()
        per = self._service_floor(eng)
        max_batch = max(1, int(sig["max_batch"]))
        score = (
            self.fleet_ctx.steer_queue_weight
            * sig["queue_depth"] * per / max_batch
            + self.fleet_ctx.steer_p99_weight * sig["p99_execute_s"]
            + self.fleet_ctx.steer_slo_weight
            * sig.get("slo_pressure", 0.0) * per
        )
        cell_d = eng.cell_depth(cell)
        if 0 < cell_d < max_batch:
            score -= self.fleet_ctx.batch_join_bonus * per
        return score

    def _pick_replica(
        self, cell: ShapeCell, graph, k: int,
        exclude: Sequence[int] = (), meta: Optional[dict] = None,
    ) -> List[int]:
        """Candidate replica indices, best first.  Half-open probe
        replicas lead (a granted probe slot must carry this request or
        the replica stays demoted another cooldown).  Hard skips: an open
        cell breaker for THIS cell whose cooldown has not elapsed
        (poisoned there, maybe healthy elsewhere — once the cooldown
        passes the request routes through, so the ENGINE's admission
        ``allow()`` can grant the cell's own half-open probe), and a
        failing capacity-preflight verdict (per-replica ceilings
        differ).  ``meta`` reports considered/capacity-skip counts so
        the submit path can type an all-replicas-oversize rejection."""
        probes: List[int] = []
        scored = []
        considered = 0
        capacity_skips = 0
        cell_key = (cell.n_bucket, cell.m_bucket, cell.k)
        # One preflight per distinct (ceiling, device kind), not per
        # replica: a homogeneous fleet pays the host arithmetic once per
        # scan instead of N times (heterogeneous ceilings still each get
        # their own verdict).
        verdicts: Dict[tuple, bool] = {}
        for idx in range(len(self.replicas)):
            if idx in exclude:
                continue
            # Peek availability (no probe consumption): the filters
            # below may still drop this replica, and a consumed probe
            # that carries no request leaves it demoted another cooldown.
            ok, is_probe = self._replica_available(idx, consume=False)
            if not ok:
                continue
            considered += 1
            eng = self.replicas[idx]
            br = eng.breakers.get("cell", cell_key)
            if br.state != "closed" and br.retry_after_s() > 0.0:
                continue
            vkey = (eng._capacity_ceiling, eng._device_kind)
            verdict = verdicts.get(vkey)
            if verdict is None:
                verdict = verdicts[vkey] = eng.capacity_verdict(graph, k)
            if not verdict:
                capacity_skips += 1
                continue
            if is_probe:
                # The filters passed: consume the probe slot now (this
                # restores/restarts the replica) — a lost race on the
                # slot just drops the candidate.
                if self._replica_available(idx)[0]:
                    probes.append(idx)
            else:
                scored.append((self._score(idx, cell), idx))
        scored.sort()  # deterministic: (score, index)
        if meta is not None:
            meta["considered"] = considered
            meta["capacity_skips"] = capacity_skips
            # Per-replica score inputs for the request-trace steer event
            # (round 20): what the router saw when it ranked candidates.
            meta["probes"] = list(probes)
            meta["scores"] = [
                {"replica": idx, "score": round(score, 6)}
                for score, idx in scored
            ]
        return probes + [idx for _, idx in scored]

    def _check_auto_drain(self) -> None:
        """Lazily drain replicas whose watchdog fired or whose cell
        breakers latched open (the submit-path health check — no extra
        monitor thread; a fleet with no traffic has nothing to steer).
        Throttled to one sweep per ``_health_interval_s`` so a burst does
        not pay the per-replica signal reads per request."""
        now = time.monotonic()
        if now - self._last_health_check < self._health_interval_s:
            return
        self._last_health_check = now
        if self.fleet_ctx.auto_drain:
            # A health drain REPLACES the replica (round 19) when the
            # fleet is configured elastic: capacity must not dip for the
            # drain cooldown, so a fresh replica (inheriting the fleet's
            # warm state) takes the retired slot's place immediately.
            replace = (
                self.fleet_ctx.replace_drained or self.fleet_ctx.autoscale
            )
            for idx, eng in enumerate(self.replicas):
                if self._draining[idx] or not eng.running:
                    continue
                sig = eng.steer_signals()
                if sig["watchdog_timeouts"] < self._watchdog_seen[idx]:
                    # The engine's stats were reset under us (bench
                    # windows do): re-anchor the watermark or real fires
                    # after the reset would be silently swallowed by the
                    # stale delta.
                    self._watchdog_seen[idx] = sig["watchdog_timeouts"]
                fired = sig["watchdog_timeouts"] - self._watchdog_seen[idx]
                open_cells = sig["open_cell_breakers"]
                if fired > 0 or (
                    self.fleet_ctx.auto_drain_open_cells > 0
                    and open_cells >= self.fleet_ctx.auto_drain_open_cells
                ):
                    self._watchdog_seen[idx] = sig["watchdog_timeouts"]
                    reason = (
                        f"watchdog fired {fired}x" if fired > 0
                        else f"{open_cells} cell breakers latched open"
                    )
                    self.drain_replica(idx, reason=reason, retire=replace)
                    if replace:
                        self._replace_replica(idx, reason)
        self._autoscale_sweep()

    # -- elastic scaling (round 19, ISSUE 15) ------------------------------

    def _active_indices(self) -> List[int]:
        """Slots participating in the fleet's target size (everything not
        retired — a health-drained-but-not-retired replica still counts:
        it is expected back through the half-open probe)."""
        return [
            i for i in range(len(self.replicas)) if not self._retired[i]
        ]

    @property
    def active_replicas(self) -> int:
        return len(self._active_indices())

    def scale_to(self, n: int, reason: str = "") -> dict:
        """Elastically resize the fleet to ``n`` active replicas UNDER
        LIVE TRAFFIC (round 19 tentpole c).

        Scale-up first revives retired slots in index order (the engine
        object is kept across retirement, so its warm state — solver
        caches, warm cells, stats — carries over for free), then spawns
        fresh replicas that inherit the fleet's warm state + shared
        persistent cache dir (zero compile-event warmup delta, the PR 14
        inheritance argument) and journal nothing until started.
        Scale-down retires the highest-index active replicas through the
        PR 14 drain/resteer machinery — queued work requeues eagerly on
        the survivors, in-flight work finishes or is force-resolved typed
        and resteered lazily, so resolutions are conserved (zero lost,
        zero duplicated — asserted under an 8-thread live burst in
        tests/test_elastic.py) and sticky tenants re-home on their next
        request (counted in ``sticky_moves``).

        Returns an action summary ``{target, active, spawned, revived,
        retired}``.  Serialized against concurrent scaling; never goes
        below one active replica."""
        n = max(1, int(n))
        if not self._started or self._stopping:
            raise EngineStoppedError("fleet not started (call start())")
        with self._scale_lock:
            active = self._active_indices()
            delta = n - len(active)
            actions: dict = {
                "target": n, "spawned": [], "revived": [], "retired": [],
            }
            if delta > 0:
                with self._lock:
                    self._counters["fleet_scale_ups"] += 1
                for _ in range(delta):
                    revived = None
                    for i in range(len(self.replicas)):
                        if self._retired[i] and self._revive_replica(i):
                            revived = i
                            break
                    if revived is not None:
                        actions["revived"].append(revived)
                        with self._lock:
                            self._counters["fleet_scale_revives"] += 1
                    else:
                        # No retired slot (or every candidate's drain is
                        # still wedged in flight): spawn fresh.
                        actions["spawned"].append(self._spawn_replica())
                        with self._lock:
                            self._counters["fleet_scale_spawns"] += 1
            elif delta < 0:
                with self._lock:
                    self._counters["fleet_scale_downs"] += 1
                for idx in sorted(active, reverse=True)[:-delta]:
                    self.drain_replica(
                        idx,
                        reason=reason or f"scale_to({n})",
                        retire=True,
                    )
                    actions["retired"].append(idx)
                    with self._lock:
                        self._counters["fleet_scale_retires"] += 1
            actions["active"] = self.active_replicas
        from ..telemetry import trace as ttrace

        trec = ttrace.active()
        if trec is not None:
            trec.instant("fleet.scale", target=n, reason=reason,
                         spawned=len(actions["spawned"]),
                         revived=len(actions["revived"]),
                         retired=len(actions["retired"]))
        return actions

    def _spawn_replica(self) -> int:
        """Construct + start one fresh replica at the next index (caller
        holds ``_scale_lock``): same deepcopied base context, device
        placement wrapping the mesh, and warm-state inheritance from the first healthy replica —
        it joins the routable set only once started (``running`` gates
        ``_replica_available``), and journals nothing until then."""
        idx = len(self.replicas)
        try:
            import jax

            n_dev = max(1, len(jax.devices()))
        except Exception:  # noqa: BLE001 — placement is locality only
            n_dev = 1
        rctx = copy.deepcopy(self.ctx)
        rctx.parallel.placement_device = idx % n_dev
        if rctx.serve.journal_path:
            rctx.serve.journal_path += f".replica{idx}"
        eng = PartitionEngine(
            rctx, name=f"replica{idx}", **self._serve_overrides
        )
        # Fresh replicas join the fleet's shared request-trace registry
        # (round 20): a request resteered onto this replica extends its
        # original event chain.
        eng.reqtrace = self.reqtrace
        donor = next(
            (
                self.replicas[i] for i in self._active_indices()
                if self.replicas[i].running and not self._draining[i]
            ),
            None,
        )
        if donor is not None and self.fleet_ctx.inherit_warm_cache:
            eng.inherit_warmup(donor)
        eng.start(warmup=self._warmup_flag)
        # State arrays grow BEFORE the replicas list: every reader
        # indexes arrays by a position < len(self.replicas).
        with self._lock:
            self._draining.append(False)
            self._drain_threads.append(None)
            self._watchdog_seen.append(0)
            self._steered.append(0)
            self._retired.append(False)
        self.replicas.append(eng)
        return idx

    def _revive_replica(self, idx: int) -> bool:
        """Bring a retired slot back into rotation (caller holds
        ``_scale_lock``): join any straggling drain, restart the kept
        engine (warm state carries over restarts — no warmup pass), and
        administratively close its fleet breaker (the trip recorded an
        intentional retirement, not a health verdict).

        Returns False — slot NOT revived — when the drain thread is
        still alive after the join budget: its eventual ``shutdown``
        would stop the engine right after we marked it active, leaving a
        phantom slot that counts toward capacity but routes nothing.
        The caller spawns a fresh replica instead."""
        t = self._drain_threads[idx]
        if t is not None:
            t.join(self.fleet_ctx.drain_timeout_s)
            if t.is_alive():
                return False
            self._drain_threads[idx] = None
        eng = self.replicas[idx]
        if not eng.running:
            eng.start(warmup=False)
        with self._lock:
            self._retired[idx] = False
            self._draining[idx] = False
        self.breakers.get("replica", (idx,)).reset()
        return True

    def _scale_in_background(self, fn, label: str) -> None:
        """Run one sweep-triggered scaling action detached: replica
        spawn + warmup can take seconds, and the health sweep runs on a
        client's submit thread.  At most one action is in flight; extra
        triggers are absorbed (the next sweep re-evaluates the signal)."""
        with self._lock:
            if self._bg_scale is not None and self._bg_scale.is_alive():
                return
            thread = threading.Thread(
                target=self._run_bg_scale, args=(fn,),
                name=f"kaminpar-fleet-scale-{label}", daemon=True,
            )
            self._bg_scale = thread
        thread.start()

    def _run_bg_scale(self, fn) -> None:
        try:
            fn()
        except EngineStoppedError:
            pass  # fleet shut down under the action: nothing to scale
        except Exception as exc:  # noqa: BLE001 — a failed background
            # scale must be loud, not a silently dead thread.
            warnings.warn(
                f"kaminpar_tpu fleet: background scaling failed "
                f"({type(exc).__name__}: {exc})",
                RuntimeWarning,
                stacklevel=2,
            )

    def _replace_replica(self, idx: int, reason: str) -> None:
        """Health-sweep replacement: the watchdog/breaker drain retired
        slot ``idx``; spawn a FRESH substitute (never revive the sick
        slot — reviving would restart the engine the watchdog just
        condemned, while its drain is still in flight) so active
        capacity does not dip for the drain cooldown.  The spawn runs
        detached (see :meth:`_scale_in_background`)."""
        if self._stopping:
            return
        with self._lock:
            self._counters["fleet_scale_replacements"] += 1

        def _spawn():
            if self._stopping:
                return
            with self._scale_lock:
                self._spawn_replica()
                with self._lock:
                    self._counters["fleet_scale_spawns"] += 1

        self._scale_in_background(_spawn, f"replace{idx}")

    def _autoscale_sweep(self) -> None:
        """Watermark autoscaler (round 19): driven from the same
        submit-path health sweep as auto-drain — the mean per-replica
        queue-drain estimate (depth x unamortized EMA / max_batch, the
        PR 6 rule) crossing ``autoscale_high_s`` for
        ``autoscale_hysteresis`` CONSECUTIVE sweeps scales up one
        replica; staying under ``autoscale_low_s`` scales down one —
        never past the min/max bounds, and the counters reset whenever
        the signal leaves the band (hysteresis means sustained pressure,
        not one spike)."""
        fc = self.fleet_ctx
        if not fc.autoscale:
            return
        # RAW drain estimate (depth x unamortized EMA / max_batch), not
        # retry_after_estimate: that one floors at 0.05 s as an
        # anti-busy-spin backpressure hint, and a floor would read an
        # IDLE fleet as permanently above any smaller high watermark.
        estimates = []
        pressures = []
        for idx, eng in enumerate(self.replicas):
            if (
                self._draining[idx] or self._retired[idx]
                or not eng.running
            ):
                continue
            estimates.append(
                len(eng._queue)
                * eng.stats_.service_time_estimate()
                / max(1, eng.serve.max_batch)
            )
            pressures.append(
                eng._slo.pressure() if eng._slo is not None else 0.0
            )
        if not estimates:
            return
        mean = sum(estimates) / len(estimates)
        # SLO pressure boost (round 20, telemetry/slo.py): sustained
        # error-budget burn reads as extra seconds on the drain estimate
        # (autoscale_slo_boost seconds per unit of mean excess burn), so
        # a fleet missing its objectives scales up before raw queue depth
        # alone crosses the watermark.  0 whenever objectives are
        # disarmed — the watermark arithmetic is then unchanged.
        mean += (
            fc.autoscale_slo_boost * sum(pressures) / len(pressures)
        )
        active = len(self._active_indices())
        hysteresis = max(1, int(fc.autoscale_hysteresis))
        if mean > fc.autoscale_high_s and active < fc.autoscale_max_replicas:
            self._above_high += 1
            self._below_low = 0
            if self._above_high >= hysteresis:
                self._above_high = 0
                with self._lock:
                    self._counters["fleet_scale_auto_ups"] += 1
                reason = (f"autoscale: drain estimate {mean:.3f}s > "
                          f"{fc.autoscale_high_s}s")
                # Detached: a scale-up may spawn + warm a replica.
                self._scale_in_background(
                    lambda n=active + 1, r=reason: self.scale_to(n, r),
                    "auto-up",
                )
        elif mean < fc.autoscale_low_s and active > fc.autoscale_min_replicas:
            self._below_low += 1
            self._above_high = 0
            if self._below_low >= hysteresis:
                self._below_low = 0
                with self._lock:
                    self._counters["fleet_scale_auto_downs"] += 1
                reason = (f"autoscale: drain estimate {mean:.3f}s < "
                          f"{fc.autoscale_low_s}s")
                self._scale_in_background(
                    lambda n=active - 1, r=reason: self.scale_to(n, r),
                    "auto-down",
                )
        else:
            self._above_high = 0
            self._below_low = 0

    # -- request path ------------------------------------------------------

    def submit(
        self,
        graph,
        k: int,
        epsilon: float = 0.03,
        *,
        graph_id=None,
        replica: Optional[int] = None,
        **request_kwargs,
    ) -> FleetFuture:
        """Steer one request to a replica and enqueue it there.

        ``graph_id``: opaque tenant/graph key for sticky routing — repeat
        ids keep landing on their warm replica while it stays healthy.
        ``replica``: explicit pin (tests/operations), bypassing scoring.
        Raises :class:`QueueFullError` when every routable replica's queue
        is full — ``retry_after_s`` is the LEAST-LOADED replica's drain
        estimate (not the rejecting replica's EMA), and
        :class:`EngineStoppedError` when the fleet is not running."""
        if not self._started or self._stopping:
            raise EngineStoppedError("fleet not started (call start())")
        from ..telemetry import trace as ttrace
        from ..utils.timer import scoped_timer

        cell = shape_cell(graph, k)
        with scoped_timer("fleet_steer"):
            self._check_auto_drain()
            self._prune_records()
            with self._lock:
                self._counters["submitted"] += 1
            home = None
            if (
                replica is None and graph_id is not None
                and self.fleet_ctx.sticky_routing
            ):
                with self._lock:
                    home = self._sticky.get(graph_id)
                    if home is not None:
                        # LRU refresh: a live tenant's binding must not
                        # be the eviction victim.
                        self._sticky[graph_id] = self._sticky.pop(graph_id)
                if home is not None and not self._replica_available(
                    home, probe_ok=False
                )[0]:
                    home = None  # sticky replica drained: steer fresh
            meta: dict = {}
            if replica is not None:
                candidates = [int(replica)]
            elif home is not None:
                # Sticky preference, not a hard pin: a full warm replica
                # falls back to normal steering (locality optimization,
                # never an availability constraint).
                candidates = [home] + self._pick_replica(
                    cell, graph, k, exclude=(home,), meta=meta
                )
            else:
                candidates = self._pick_replica(cell, graph, k, meta=meta)
            if not candidates and replica is None:
                if meta.get("considered") and (
                    meta["capacity_skips"] == meta["considered"]
                ):
                    # Every routable replica's ceiling rejects this
                    # request: that is a deterministic oversize, not
                    # backpressure — surface the TYPED CapacityError
                    # (with its prediction payload) via the counting
                    # engine path instead of a retry-forever hint.
                    with self._lock:
                        self._counters["rejected_capacity"] += 1
                    for idx in range(len(self.replicas)):
                        if self._replica_available(idx, probe_ok=False)[0]:
                            self.replicas[idx]._capacity_preflight(graph, k)
                self._unroutable(cell)
            rec_id = next(self._ids)
            record = _FleetRecord(
                rec_id, graph, k, epsilon, request_kwargs, graph_id,
                trace_id=self.reqtrace.mint(),
            )
            self.reqtrace.bind_fleet(rec_id, record.trace_id)
            # Steer-decision trace event (round 20): the candidate ranking
            # and per-replica score inputs the router saw BEFORE the
            # admission attempts — the engine's admit event that follows
            # names the replica that actually took the request.
            self.reqtrace.record(
                record.trace_id, "steer", fleet_id=rec_id, k=int(k),
                n_bucket=cell.n_bucket, m_bucket=cell.m_bucket,
                candidates=list(candidates),
                sticky_home=(-1 if home is None else int(home)),
                pinned=(-1 if replica is None else int(replica)),
                considered=meta.get("considered", 0),
                capacity_skips=meta.get("capacity_skips", 0),
                probes=meta.get("probes", []),
                scores=meta.get("scores", []),
            )
            fut = self._submit_record(record, candidates, cell, graph, k)
        sticky_used = home is not None and record.replica == home
        if sticky_used:
            with self._lock:
                self._counters["sticky_hits"] += 1
        elif graph_id is not None and self.fleet_ctx.sticky_routing:
            with self._lock:
                moved = (
                    self._sticky.get(graph_id) not in (None, record.replica)
                )
                self._sticky_bind_locked(graph_id, record.replica)
                if moved:
                    self._counters["sticky_moves"] += 1
        rec = ttrace.active()
        if rec is not None:
            rec.instant(
                "fleet.steer", fleet_id=record.fleet_id,
                replica=record.replica, k=int(k),
                n_bucket=cell.n_bucket, m_bucket=cell.m_bucket,
                sticky=sticky_used,
            )
        return fut

    def _submit_record(
        self, record: _FleetRecord, candidates: List[int],
        cell: ShapeCell, graph, k: int,
    ) -> FleetFuture:
        """Try candidates best-first; a per-replica QueueFullError,
        PoisonedCell or CapacityError moves on to the next (counted as a
        steer retry) — sticky/pinned candidates bypass the scan's
        capacity filter, so a request oversize for its home replica must
        still reach a sibling with a larger ceiling."""
        from ..resilience.errors import PoisonedCell
        from .errors import CapacityError

        last_exc: Optional[BaseException] = None
        last_capacity: Optional[CapacityError] = None
        for idx in candidates:
            eng = self.replicas[idx]
            try:
                fut = eng.submit(
                    record.graph, record.k, record.epsilon,
                    trace_id=record.trace_id, **record.kwargs
                )
            except CapacityError as exc:
                last_capacity = exc
                with self._lock:
                    self._counters["steer_retries"] += 1
                continue
            except (QueueFullError, PoisonedCell, EngineStoppedError) as exc:
                last_exc = exc
                with self._lock:
                    self._counters["steer_retries"] += 1
                continue
            record.replica = idx
            record.current = fut
            with self._lock:
                self._steered[idx] += 1
                self._records[id(fut)] = record
            return FleetFuture(self, record)
        if isinstance(last_exc, QueueFullError):
            with self._lock:
                self._counters["rejected_full"] += 1
            raise QueueFullError(self._fleet_retry_after()) from None
        if last_capacity is not None:
            # Every tried replica rejected on capacity (and none on
            # backpressure): a deterministic oversize — surface the TYPED
            # error with its prediction payload, not a retry hint.
            with self._lock:
                self._counters["rejected_capacity"] += 1
            raise last_capacity
        self._unroutable(cell, last_exc)

    def _unroutable(self, cell: ShapeCell, cause=None):
        """No replica can take this request right now: reject with the
        fleet-wide retry hint (a draining fleet recovers; callers back
        off rather than error out)."""
        with self._lock:
            self._counters["rejected_unroutable"] += 1
        retry = self._fleet_retry_after()
        for idx in range(len(self.replicas)):
            br = self.breakers.get("replica", (idx,))
            if br.state != "closed":
                retry = max(retry, br.retry_after_s())
        raise QueueFullError(retry) from cause

    def _fleet_retry_after(self) -> float:
        """Backpressure hint on a fleet-level reject: the LEAST-LOADED
        routable replica's drain estimate — depth x unamortized EMA /
        batch width (ISSUE 14 satellite; the rejecting replica's own EMA
        can be arbitrarily pessimistic while a sibling is nearly idle).
        Falls back to the global floor when nothing is routable."""
        estimates = [
            eng.stats_.retry_after_estimate(
                len(eng._queue), eng.serve.max_batch
            )
            for idx, eng in enumerate(self.replicas)
            if not self._draining[idx] and eng.running
        ]
        return min(estimates) if estimates else 0.1

    def partition(self, graph, k: int, epsilon: float = 0.03, **kw):
        """Synchronous convenience wrapper: submit + wait, returning the
        (n,) block array."""
        return self.submit(graph, k, epsilon, **kw).result().partition

    # -- drain + cross-replica resteer -------------------------------------

    def drain_replica(self, idx: int, reason: str = "",
                      retire: bool = False) -> None:
        """Take replica ``idx`` out of rotation: trip its fleet breaker,
        requeue its queued work on healthy replicas eagerly, then shut it
        down with the bounded drain (in-flight work finishes normally, or
        a hung dispatcher's futures are force-resolved typed and resteered
        lazily by their waiters).  Zero lost, zero duplicated resolutions
        — asserted under concurrent overload in tests/test_fleet.py.

        ``retire`` (round 19): additionally mark the slot retired —
        ``scale_to`` scale-downs and health-sweep *replacements* use it;
        a retired slot is never probe-restored, only revived by a later
        scale-up."""
        idx = int(idx)
        with self._lock:
            already = self._draining[idx]
            self._draining[idx] = True
            if retire:
                self._retired[idx] = True
            if not already:
                self._counters["drains"] += 1
        if already:
            return
        eng = self.replicas[idx]
        self.breakers.get("replica", (idx,)).trip()
        self.breakers.record_demotion(
            "replica", reason or "drained", warn=True
        )
        from ..telemetry import trace as ttrace

        trec = ttrace.active()
        if trec is not None:
            trec.instant("fleet.drain", replica=idx, reason=reason,
                         queued=len(eng._queue))

        def _drain():
            # Eager leg: everything still queued (never started) moves
            # NOW — requeues honor sibling backpressure (bounded
            # retry-after waits inside _resteer), so a momentarily full
            # fleet loses nothing.
            for req in eng._queue.drain_items():
                with self._lock:
                    record = self._records.pop(id(req.future), None)
                if record is not None and self._resteer(record, req.future):
                    # Re-homed: resolve the entry in THIS replica's
                    # journal (round 19) — the sibling's journal owns the
                    # work now, and an unresolved entry here would replay
                    # already-completed work if the slot is later revived.
                    eng.journal_mark_resteered(req.id)
                # Resolve the abandoned engine future LAST: a waiter
                # waking on it re-reads record.current, which already
                # points elsewhere (or surfaces the typed error if the
                # resteer failed for good).
                req.future._reject(EngineStoppedError(
                    f"replica {idx} drained"
                    + (f": {reason}" if reason else "")
                ))
            # Bounded-drain leg: in-flight work finishes normally; a hung
            # dispatcher's futures are force-resolved typed (WorkerHung)
            # by the engine and resteered lazily by their waiters.
            try:
                eng.shutdown(
                    drain=True, timeout_s=self.fleet_ctx.drain_timeout_s
                )
            except Exception as exc:  # noqa: BLE001 — a failing drain must
                # not kill the drain thread silently; surface and carry on
                # (the replica breaker is already open).
                warnings.warn(
                    f"kaminpar_tpu fleet: draining replica {idx} failed "
                    f"({type(exc).__name__}: {exc}); its breaker stays "
                    "open until the half-open probe.",
                    RuntimeWarning,
                    stacklevel=2,
                )

        # The whole drain runs detached: the submit-path auto-drain check
        # (and operators) must never block on a replica's drain budget.
        t = threading.Thread(
            target=_drain, name=f"kaminpar-fleet-drain-{idx}", daemon=True
        )
        self._drain_threads[idx] = t
        t.start()

    def _maybe_resteer(
        self, record: _FleetRecord, failed: ServeFuture, exc: BaseException
    ) -> bool:
        """Waiter-side resteer hook: rebind a request its replica gave
        back (resteerable typed errors only).  Returns True when the
        waiter should re-wait on the (possibly already rebound) binding."""
        with record.lock:
            if record.current is not failed:
                return True  # the eager drain leg already rebound it
        if not _is_resteerable(exc):
            return False
        if record.replica >= 0:
            # The replica failed this request's dispatch: feed the fleet
            # breaker (a hung replica trips toward drain even when the
            # auto-drain check has not run yet).
            self.breakers.get("replica", (record.replica,)).record_failure()
        return self._resteer(record, failed)

    def _resteer(
        self, record: _FleetRecord, failed: ServeFuture
    ) -> bool:
        """Cross-replica requeue (idempotent per failed binding): submit
        the request on the best healthy replica excluding the failed one,
        swap the binding, and count it.  Sibling backpressure
        (QueueFullError) is waited out with bounded retry-after sleeps up
        to the drain budget — a momentarily saturated fleet must not LOSE
        a drained replica's work.  False = no resteer budget, the fleet is
        stopping, or every path stayed closed (the caller surfaces the
        typed error)."""
        if self._stopping:
            return False
        with record.lock:
            if record.current is not failed:
                return True  # lost the race to another resteer path
            if record.attempts >= self.fleet_ctx.max_resteers:
                return False
            from ..resilience.errors import PoisonedCell

            cell = shape_cell(record.graph, record.k)
            exclude = (record.replica,) if record.replica >= 0 else ()
            deadline = time.monotonic() + self.fleet_ctx.drain_timeout_s
            while True:
                backpressure: Optional[QueueFullError] = None
                for idx in self._pick_replica(
                    cell, record.graph, record.k, exclude=exclude,
                ):
                    try:
                        fut = self.replicas[idx].submit(
                            record.graph, record.k, record.epsilon,
                            trace_id=record.trace_id, **record.kwargs,
                        )
                    except QueueFullError as exc:
                        backpressure = exc
                        continue
                    except (PoisonedCell, EngineStoppedError):
                        continue
                    old = record.current
                    # Resteer-hop trace event (round 20): which replica
                    # gave the request back and where it re-homed — the
                    # new replica's admit event (same trace id) follows.
                    self.reqtrace.record(
                        record.trace_id, "resteer",
                        fleet_id=record.fleet_id,
                        from_replica=int(record.replica), replica=int(idx),
                        attempt=record.attempts + 1,
                    )
                    record.replica = idx
                    record.current = fut
                    record.attempts += 1
                    with self._lock:
                        self._records.pop(id(old), None)
                        self._records[id(fut)] = record
                        self._counters["resteers"] += 1
                        self._steered[idx] += 1
                        if (
                            record.graph_id is not None
                            and self.fleet_ctx.sticky_routing
                        ):
                            self._sticky_bind_locked(record.graph_id, idx)
                            self._counters["sticky_moves"] += 1
                    return True
                if (
                    backpressure is None  # nothing routable at any load
                    or self._stopping
                    or time.monotonic() >= deadline
                ):
                    return False
                time.sleep(min(backpressure.retry_after_s, 0.25))

    def _note_success(self, record: _FleetRecord) -> None:
        """A fleet-routed request completed on its replica: close the
        replica's fleet breaker (restoring a half-open probe).

        A success delivered by a DRAINING replica (in-flight work
        finishing inside the bounded drain) must NOT close its tripped
        breaker: a closed breaker on a draining replica is unroutable
        forever — only the half-open probe path clears ``_draining``."""
        if record.replica >= 0 and not self._draining[record.replica]:
            br = self.breakers.get("replica", (record.replica,))
            if br.record_success():
                self.breakers.record_restoration("replica")

    def _sticky_bind_locked(self, graph_id, idx: int) -> None:
        """Insert/refresh one sticky binding (caller holds ``_lock``),
        evicting least-recently-used bindings past the watermark — an
        evicted tenant just re-steers fresh on its next request."""
        self._sticky.pop(graph_id, None)
        self._sticky[graph_id] = idx
        while len(self._sticky) > self._sticky_watermark:
            self._sticky.pop(next(iter(self._sticky)))

    def _forget(self, record: _FleetRecord) -> None:
        fut = record.current  # lock-free: see FleetFuture.cancel()
        with self._lock:
            if fut is not None:
                self._records.pop(id(fut), None)

    def _prune_records(self) -> None:
        """Drop drain-lookup entries of DONE engine futures whose waiter
        never came back (timed-out or fire-and-forget callers) — the map
        would otherwise grow unboundedly, pinning every such request's
        graph.  Safe: the map is only the drain's queued-work lookup
        (done futures are past it) and ``_forget``'s target; a late
        waiter still holds the record object itself and resolves from
        the bound future."""
        with self._lock:
            if len(self._records) <= self._prune_watermark:
                return
            for key in [
                key for key, rec in self._records.items()
                if rec.current is not None and rec.current.done()
            ]:
                del self._records[key]

    # -- observability -----------------------------------------------------

    def stats(self) -> dict:
        """Fleet-level snapshot: router counters, per-replica serving
        signals + occupancy, the aggregate lane x device occupancy, and
        the fleet-scoped breaker registry."""
        with self._lock:
            counters = dict(self._counters)
            steered = list(self._steered)
            draining = list(self._draining)
            retired = list(self._retired)
        per_replica = []
        agg_lanes = 0
        agg_occupancy = 0.0
        for idx, eng in enumerate(self.replicas):
            snap = eng.stats_.snapshot(queue_depth=len(eng._queue))
            cells = eng.warmup_cell_counts()
            per_replica.append({
                "replica": idx,
                "running": eng.running,
                "draining": draining[idx],
                "retired": retired[idx],
                "steered": steered[idx],
                "queue_depth": snap["queue_depth"],
                "completed": snap["completed"],
                "failed": snap["failed"],
                "batches": snap["batches"],
                "batch_occupancy_mean": snap["batch_occupancy_mean"],
                "batch_occupancy_max": snap["batch_occupancy_max"],
                "lanestacked_batches": snap["lanestacked_batches"],
                "lanestacked_lanes": snap["lanestacked_lanes"],
                "p99_execute_ms": snap["latency_ms"]["execute_ms"].get(
                    "p99", 0.0
                ),
                "ema_service_s": snap["ema_service_s"],
                "warmup_inherited_cells": cells["inherited"],
                "warmup_local_cells": cells["local"],
                "slo_pressure": (
                    eng._slo.pressure() if eng._slo is not None else 0.0
                ),
            })
            agg_lanes += snap["lanestacked_lanes"]
            agg_occupancy += snap["batch_occupancy_max"]
        return {
            "replicas": len(self.replicas),
            "active_replicas": len(self.replicas) - sum(retired),
            "running": self._started,
            **counters,
            "per_replica": per_replica,
            # Peak concurrent lane x device occupancy: the sum over
            # replicas of the widest batch each dispatched (8 replicas x
            # 8 lanes = 64, the ROADMAP "millions of users" figure).  On
            # the CPU dryrun this is an occupancy claim, not a speedup
            # claim (virtual devices serialize; TPU_NOTES round 18).
            "aggregate_occupancy": agg_occupancy,
            "aggregate_lanestacked_lanes": agg_lanes,
            # Worst replica SLO pressure (round 20): the autoscale boost
            # uses the mean; the dashboard headline wants the worst.
            "slo_pressure": max(
                (r["slo_pressure"] for r in per_replica), default=0.0
            ),
            "reqtrace": self.reqtrace.snapshot(),
            "breakers": self.breakers.snapshot(),
        }

    def explain(self, request) -> Optional[dict]:
        """Structured request dossier by :class:`FleetFuture` (or fleet
        id, or raw trace id): the whole cross-replica event chain — steer
        decision with score inputs, per-replica admits/dispatches,
        resteer hops, journal replays, resolution — with a connectivity
        verdict (telemetry/reqtrace.py).  ``None`` for unknown/evicted
        requests."""
        from ..utils.timer import scoped_timer

        with scoped_timer("reqtrace_export"):
            if isinstance(request, FleetFuture):
                return self.reqtrace.explain_fleet(request.fleet_id)
            if isinstance(request, str):
                return self.reqtrace.dossier(request)
            return self.reqtrace.explain_fleet(int(request))

    def metrics_text(self) -> str:
        """Prometheus text exposition of the fleet router (per-replica
        engine expositions stay available via each replica's
        ``metrics_text``; the fleet adds the routing layer)."""
        from ..resilience import breakers as rbreakers
        from ..telemetry import prometheus

        snap = self.stats()
        steer_samples = [
            ({"replica": str(r["replica"])}, r["steered"])
            for r in snap["per_replica"]
        ]
        depth_samples = [
            ({"replica": str(r["replica"])}, r["queue_depth"])
            for r in snap["per_replica"]
        ]
        inherit_samples = []
        for r in snap["per_replica"]:
            lbl = {"replica": str(r["replica"])}
            inherit_samples.append(
                ({**lbl, "source": "inherited"}, r["warmup_inherited_cells"])
            )
            inherit_samples.append(
                ({**lbl, "source": "local"}, r["warmup_local_cells"])
            )
        families = [
            ("kaminpar_fleet_replicas", "gauge",
             "Engine replicas owned by the fleet router",
             [({}, snap["replicas"])]),
            ("kaminpar_fleet_replicas_draining", "gauge",
             "Replicas currently drained out of rotation",
             [({}, sum(1 for r in snap["per_replica"] if r["draining"]))]),
            ("kaminpar_fleet_steered_total", "counter",
             "Requests steered per replica (SLO-aware scoring)",
             steer_samples),
            ("kaminpar_fleet_queue_depth", "gauge",
             "Per-replica bounded-queue depth",
             depth_samples),
            ("kaminpar_fleet_requests_total", "counter",
             "Fleet-level request outcomes at the router",
             [({"outcome": "submitted"}, snap["submitted"]),
              ({"outcome": "rejected_full"}, snap["rejected_full"]),
              ({"outcome": "rejected_unroutable"},
               snap["rejected_unroutable"]),
              ({"outcome": "rejected_capacity"},
               snap["rejected_capacity"])]),
            ("kaminpar_fleet_resteers_total", "counter",
             "Cross-replica requeues of work a draining/hung replica "
             "gave back (zero lost/duplicated resolutions)",
             [({}, snap["resteers"])]),
            ("kaminpar_fleet_sticky_total", "counter",
             "Graph-id-sticky routing decisions",
             [({"result": "hit"}, snap["sticky_hits"]),
              ({"result": "moved"}, snap["sticky_moves"])]),
            ("kaminpar_fleet_drains_total", "counter",
             "Replicas drained out of rotation (watchdog/breaker health)",
             [({}, snap["drains"])]),
            ("kaminpar_fleet_restores_total", "counter",
             "Drained replicas restored by the half-open probe",
             [({}, snap["restores"])]),
            ("kaminpar_fleet_active_replicas", "gauge",
             "Replicas participating in the fleet's elastic target size "
             "(total minus retired slots)",
             [({}, snap["active_replicas"])]),
            ("kaminpar_fleet_scale_total", "counter",
             "Elastic scaling events (round 19): scale_to calls by "
             "direction, slot transitions (spawn/revive/retire), "
             "health-sweep replacements, autoscale decisions",
             [({"op": "up"}, snap["fleet_scale_ups"]),
              ({"op": "down"}, snap["fleet_scale_downs"]),
              ({"op": "spawn"}, snap["fleet_scale_spawns"]),
              ({"op": "revive"}, snap["fleet_scale_revives"]),
              ({"op": "retire"}, snap["fleet_scale_retires"]),
              ({"op": "replacement"}, snap["fleet_scale_replacements"]),
              ({"op": "auto_up"}, snap["fleet_scale_auto_ups"]),
              ({"op": "auto_down"}, snap["fleet_scale_auto_downs"])]),
            ("kaminpar_fleet_warmup_cells_total", "counter",
             "Per-replica warmup cells by source: inherited from the "
             "fleet's warm state vs locally traced/compiled",
             inherit_samples or [({}, 0)]),
            ("kaminpar_fleet_aggregate_occupancy", "gauge",
             "Sum over replicas of the widest dispatched batch — the "
             "lane x device occupancy figure (device claim on real "
             "meshes; virtual CPU devices serialize)",
             [({}, snap["aggregate_occupancy"])]),
            ("kaminpar_slo_fleet_pressure", "gauge",
             "Worst per-replica SLO error-budget pressure "
             "(max(0, worst_burn - 1); 0 unless objectives are armed)",
             [({}, snap["slo_pressure"])]),
        ]
        families.extend(rbreakers.prometheus_families(self.breakers))
        return prometheus.render(families)
