"""Unified resilience layer tests: the typed hierarchy + the
ONE classifier, the seed-keyed fault-injection harness, the circuit
breaker state machine, and the chaos matrix the acceptance criteria
name — for every fault class x injection point, the engine recovers
without wedging (drain completes), deterministic demotions are
BIT-IDENTICAL to the healthy fallback path, breaker/demotion/injection
counters match the armed plan exactly, and a post-cooldown half-open
probe restores the primary path.

Plus the round-17 satellites: bounded shutdown drain with a hung worker,
facade-boundary CSR validation rejections, and queue admission under
concurrent overload.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from kaminpar_tpu.graph import generators
from kaminpar_tpu.kaminpar import KaMinPar
from kaminpar_tpu.presets import create_context_by_preset_name
from kaminpar_tpu.resilience import breakers as rbreakers
from kaminpar_tpu.resilience import faults as rfaults
from kaminpar_tpu.resilience.breakers import BreakerRegistry, CircuitBreaker
from kaminpar_tpu.resilience.errors import (
    BackendUnavailable,
    CapacityExceeded,
    CompileTimeout,
    ExecuteFault,
    GraphValidationError,
    PoisonedCell,
    ResilienceError,
    WorkerHung,
    classify,
    is_control_flow,
)
from kaminpar_tpu.resilience.faults import FaultPlan, injected_faults
from kaminpar_tpu.serve.engine import PartitionEngine
from kaminpar_tpu.serve.errors import QueueFullError


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    """Every test starts with a disarmed harness and fresh registries —
    the process-global breaker registry must not leak trips between
    tests (the same reason sync_stats budgets reset per pipeline)."""
    rfaults.reset()
    rbreakers.reset_global_registry()
    yield
    rfaults.reset()
    rbreakers.reset_global_registry()


def _rmat(seed, scale=7):
    return generators.rmat_graph(scale, edge_factor=4, seed=seed)


def _engine(threshold=3, cooldown=30.0, execute_timeout=0.0, **serve):
    ctx = create_context_by_preset_name("serve")
    ctx.resilience.breaker_threshold = threshold
    ctx.resilience.breaker_cooldown_s = cooldown
    ctx.resilience.execute_timeout_s = execute_timeout
    serve.setdefault("warm_ladder", ())
    serve.setdefault("warm_ks", ())
    serve.setdefault("max_batch", 4)
    serve.setdefault("queue_bound", 16)
    return PartitionEngine(ctx, **serve)


# ---------------------------------------------------------------------------
# Hierarchy + classifier
# ---------------------------------------------------------------------------


def test_classify_maps_adhoc_exceptions_to_failure_classes():
    assert isinstance(classify(MemoryError("oom")), CapacityExceeded)
    assert isinstance(
        classify(RuntimeError("RESOURCE_EXHAUSTED: out of memory")),
        CapacityExceeded,
    )
    assert isinstance(
        classify(RuntimeError("UNAVAILABLE: failed to initialize backend")),
        BackendUnavailable,
    )
    assert isinstance(
        classify(TimeoutError("x"), site="warmup_compile"), CompileTimeout
    )
    assert isinstance(classify(TimeoutError("x"), site="engine"), ExecuteFault)
    generic = classify(ZeroDivisionError("kernel bug"), site="engine")
    assert isinstance(generic, ExecuteFault)
    assert generic.__cause__.__class__ is ZeroDivisionError
    assert generic.failure_class == "execute-fault"


def test_classify_idempotent_and_control_flow_passthrough():
    typed = ExecuteFault("already typed", site="x")
    assert classify(typed) is typed
    full = QueueFullError(0.5)
    assert is_control_flow(full)
    assert not is_control_flow(RuntimeError("boom"))
    # The serve CapacityError (capacity preflight) wraps into the hierarchy.
    from kaminpar_tpu.serve.errors import CapacityError

    wrapped = classify(CapacityError(100, 10))
    assert isinstance(wrapped, CapacityExceeded)


def test_graph_validation_error_is_valueerror():
    err = GraphValidationError("bad input")
    assert isinstance(err, ValueError) and isinstance(err, ResilienceError)
    assert err.failure_class == "graph-validation"


# ---------------------------------------------------------------------------
# Fault plan: parsing + seed-keyed replayability
# ---------------------------------------------------------------------------


def test_fault_plan_parse():
    plan = FaultPlan.parse(
        "execute@lanestack:execute-fault:n=2,"
        "queue-admit:capacity-exceeded:after=1,"
        "readback:execute-fault:p=0.5:delay=0.1",
        seed=7,
    )
    assert len(plan.specs) == 3
    a, b, c = plan.specs
    assert (a.point, a.site, a.error, a.count) == (
        "execute", "lanestack", "execute-fault", 2
    )
    assert (b.point, b.after, b.count) == ("queue-admit", 1, 1)
    assert (c.p, c.delay_s) == (0.5, 0.1)
    with pytest.raises(ValueError, match="injection point"):
        FaultPlan.parse("bogus:execute-fault")
    with pytest.raises(ValueError, match="failure class"):
        FaultPlan.parse("execute:bogus-class")


def test_fault_plan_parse_rejects_malformed_values():
    """Round-19 satellite: malformed plans raise a typed ValueError
    NAMING the offending spec at arm time — silent partial arming would
    let a chaos run claim coverage its plan never delivered."""
    # Non-numeric values, each naming the key and the spec.
    with pytest.raises(ValueError, match=r"malformed n=.*'abc'"):
        FaultPlan.parse("execute:execute-fault:n=abc")
    with pytest.raises(ValueError, match=r"malformed after="):
        FaultPlan.parse("execute:execute-fault:after=1.5x")
    with pytest.raises(ValueError, match=r"malformed p="):
        FaultPlan.parse("execute:execute-fault:p=lots")
    with pytest.raises(ValueError, match=r"malformed delay="):
        FaultPlan.parse("execute:execute-fault:delay=soon")
    # Out-of-range values.
    with pytest.raises(ValueError, match=r"p=1\.5 outside"):
        FaultPlan.parse("execute:execute-fault:p=1.5")
    with pytest.raises(ValueError, match=r"n=-1 must be >= 0"):
        FaultPlan.parse("execute:execute-fault:n=-1")
    with pytest.raises(ValueError, match=r"after=-2 must be >= 0"):
        FaultPlan.parse("execute:execute-fault:after=-2")
    with pytest.raises(ValueError, match="unknown fault-spec key"):
        FaultPlan.parse("execute:execute-fault:bogus=1")
    # The offending SPEC rides the message (a multi-spec plan must name
    # which entry is broken).
    with pytest.raises(ValueError, match=r"execute:execute-fault:n=zz"):
        FaultPlan.parse(
            "readback:execute-fault:n=1,execute:execute-fault:n=zz"
        )


def test_fault_plan_parse_rejects_duplicate_specs():
    """An EXACT copy of a spec could never add a firing — rejected at
    arm time, not silently carried.  Same-(point, site, error) specs
    with different firing parameters are legal STAGED plans (the
    matcher falls through exhausted/after-gated specs)."""
    with pytest.raises(ValueError, match="duplicate fault spec"):
        FaultPlan.parse(
            "execute:execute-fault:n=1,execute:execute-fault:n=1"
        )
    with pytest.raises(ValueError, match="duplicate fault spec"):
        FaultPlan.parse(
            "execute@site:execute-fault,execute@site:execute-fault"
        )
    # Staged plan: fire at hit 1 and again at hit 11 — NOT a duplicate.
    plan = FaultPlan.parse(
        "execute:execute-fault:n=1,execute:execute-fault:after=10:n=1"
    )
    assert len(plan.specs) == 2
    # Different site or error class: NOT duplicates either.
    plan = FaultPlan.parse(
        "execute@a:execute-fault,execute@b:execute-fault,"
        "execute@a:capacity-exceeded"
    )
    assert len(plan.specs) == 3


def test_fault_injection_counts_and_site_filter():
    with injected_faults("execute@right:execute-fault:n=2") as plan:
        rfaults.maybe_inject("execute", site="wrong-site")  # filtered
        with pytest.raises(ExecuteFault) as ei:
            rfaults.maybe_inject("execute", site="right-site")
        assert ei.value.injected and ei.value.site == "right-site"
        with pytest.raises(ExecuteFault):
            rfaults.maybe_inject("execute", site="right-site")
        rfaults.maybe_inject("execute", site="right-site")  # n=2 exhausted
        assert plan.specs[0].injected == 2
    snap = rfaults.snapshot()
    assert snap["points"]["execute"] == {"hits": 4, "injected": 2}


def test_seeded_coin_is_replayable():
    def decisions(seed):
        plan = FaultPlan.parse("readback:execute-fault:p=0.4:n=0", seed=seed)
        out = []
        with injected_faults(plan):
            for _ in range(64):
                try:
                    rfaults.maybe_inject("readback")
                    out.append(0)
                except ExecuteFault:
                    out.append(1)
        return out

    a, b = decisions(7), decisions(7)
    assert a == b, "same seed must replay the same injection sequence"
    c = decisions(8)
    assert a != c, "a different seed must reshuffle the sequence"
    assert 5 < sum(a) < 60  # the coin is actually probabilistic


# ---------------------------------------------------------------------------
# Circuit breaker state machine
# ---------------------------------------------------------------------------


def test_breaker_trip_cooldown_halfopen_close():
    br = CircuitBreaker(("x", ()), threshold=2, cooldown_s=0.15)
    assert br.allow() and br.state == "closed"
    assert not br.record_failure()
    assert br.record_failure(), "threshold-th failure must trip"
    assert br.state == "open" and not br.allow()
    assert br.retry_after_s() > 0
    time.sleep(0.16)
    assert br.allow(), "post-cooldown: the half-open probe is admitted"
    assert br.state == "half-open"
    assert not br.allow(), "only ONE probe while half-open"
    assert br.record_success(), "probe success closes (reports restoration)"
    assert br.state == "closed" and br.allow()


def test_breaker_halfopen_failure_reopens():
    br = CircuitBreaker(("x", ()), threshold=1, cooldown_s=0.1)
    br.record_failure()
    time.sleep(0.11)
    assert br.allow()
    assert br.record_failure(), "probe failure re-trips"
    assert br.state == "open" and not br.allow()


def test_breaker_retry_after_in_half_open():
    """While a half-open probe is in flight, retry_after_s hints the
    probe deadline instead of 0 — a 0 would make rejected clients
    hot-spin against repeated rejections until the probe resolves."""
    br = CircuitBreaker(("x", ()), threshold=1, cooldown_s=0.2)
    br.record_failure()
    time.sleep(0.21)
    assert br.allow()  # the probe
    assert br.state == "half-open"
    assert br.retry_after_s() > 0


def test_breaker_stale_probe_renewal():
    """A probe whose caller never reports back must not pin the path
    demoted forever — a new probe is granted after one more cooldown."""
    br = CircuitBreaker(("x", ()), threshold=1, cooldown_s=0.1)
    br.record_failure()
    time.sleep(0.11)
    assert br.allow()  # probe 1, never reported
    assert not br.allow()
    time.sleep(0.11)
    assert br.allow()  # stale -> probe 2
    assert br.probes == 2


def test_breaker_halfopen_probe_race_burns_one_slot():
    """Round-19 satellite: N threads racing a cooled-down breaker must
    burn exactly ONE probe slot — the open->half-open transition and the
    probe claim are one locked step (a barrier lines the threads up on
    the same instant)."""
    br = CircuitBreaker(("x", ()), threshold=1, cooldown_s=0.05)
    br.record_failure()
    time.sleep(0.06)  # cooldown elapsed: the next allow() opens the race
    n = 8
    barrier = threading.Barrier(n)
    grants: list = []
    lock = threading.Lock()

    def racer():
        barrier.wait()
        ok = br.allow()
        with lock:
            grants.append(ok)

    threads = [threading.Thread(target=racer) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert grants.count(True) == 1, grants
    assert br.probes == 1
    # The claimed probe stays exclusive until an outcome is recorded...
    assert not br.allow()
    assert not br.would_allow()
    # ...and its success releases the claim by closing the breaker.
    assert br.record_success()
    assert br.allow()


def test_breaker_would_allow_peek_vs_claim():
    """would_allow() must stay a pure peek while a claimed probe is in
    flight (False — the slot is taken), and would_allow(claim=True) is
    the consuming twin of allow()."""
    br = CircuitBreaker(("x", ()), threshold=1, cooldown_s=0.05)
    br.record_failure()
    time.sleep(0.06)
    # Peek does not consume: repeated peeks all say "available".
    assert br.would_allow() and br.would_allow()
    assert br.probes == 0
    # The claiming form consumes the one slot.
    assert br.would_allow(claim=True)
    assert br.probes == 1
    assert not br.would_allow()
    assert not br.would_allow(claim=True)
    # A recorded outcome (failure) re-opens; after cooldown the cycle
    # restarts with a fresh slot.
    br.record_failure()
    time.sleep(0.06)
    assert br.would_allow()
    assert br.allow()
    assert br.probes == 2


def test_engine_shutdown_disarms_its_fault_plan():
    """A fault plan armed from the engine's context must not outlive the
    engine — injections leaking into unrelated pipelines in the process
    would be a chaos harness attacking production."""
    ctx = create_context_by_preset_name("serve")
    ctx.resilience.fault_plan = "queue-admit:capacity-exceeded:n=0"
    eng = PartitionEngine(ctx, warm_ladder=(), warm_ks=(), queue_bound=8)
    eng.start(warmup=False)
    assert rfaults.active_plan() is not None
    try:
        with pytest.raises(CapacityExceeded):
            eng.submit(_rmat(seed=1), 4)
    finally:
        eng.shutdown(drain=True)
    assert rfaults.active_plan() is None
    rfaults.maybe_inject("queue-admit", site="post-shutdown")  # no raise


def test_registry_demotion_warns_once():
    reg = BreakerRegistry(threshold=1, cooldown_s=0.1)
    import warnings

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        reg.record_demotion("lanestack", "test")
        reg.record_demotion("lanestack", "test")
    assert len([w for w in caught if "degrading" in str(w.message)]) == 1
    assert reg.demotions() == {"lanestack": 2}


# ---------------------------------------------------------------------------
# Chaos matrix: engine recovery per fault class x injection point
# ---------------------------------------------------------------------------


def test_chaos_execute_fault_typed_rejection_and_recovery():
    """Injected execute faults reject exactly the planned requests with
    the typed error; the engine keeps serving, drain completes, and the
    injection/breaker counters match the plan exactly."""
    eng = _engine().start(warmup=False)
    outcomes = []
    try:
        with injected_faults("execute@engine_request:execute-fault:n=2"):
            for i in range(4):
                try:
                    eng.partition(_rmat(seed=10 + i), 4)
                    outcomes.append("ok")
                except ExecuteFault as exc:
                    assert exc.injected
                    outcomes.append("fault")
            snap = rfaults.snapshot()
    finally:
        eng.shutdown(drain=True)
    assert outcomes == ["fault", "fault", "ok", "ok"]
    assert snap["points"]["execute"]["injected"] == 2
    stats = eng.stats()
    assert stats["failed"] == 2 and stats["completed"] == 2
    cell = [
        br for name, br in
        stats["resilience"]["engine"]["breakers"].items()
        if name.startswith("cell|")
    ]
    assert len(cell) == 1
    assert cell[0]["failures"] == 2 and cell[0]["state"] == "closed"


def test_chaos_poisoned_cell_fast_fail_and_halfopen_restore():
    """Enough execute faults in one cell open its breaker: new submits
    fast-fail with typed PoisonedCell (+ retry_after) instead of wedging
    the queue, and the post-cooldown half-open probe restores the cell."""
    eng = _engine(threshold=2, cooldown=0.3).start(warmup=False)
    try:
        with injected_faults("execute@engine_request:execute-fault:n=2"):
            for i in range(2):
                with pytest.raises(ExecuteFault):
                    eng.partition(_rmat(seed=20 + i), 4)
        with pytest.raises(PoisonedCell) as ei:
            eng.partition(_rmat(seed=30), 4)
        assert ei.value.retry_after_s > 0
        assert eng.stats_.counter("rejected_poisoned") == 1
        time.sleep(0.35)
        # Half-open probe (injection plan exhausted): succeeds, restores.
        p = eng.partition(_rmat(seed=31), 4)
        assert p.size > 0
        breakers = eng.stats()["resilience"]["engine"]["breakers"]
        cell = next(v for k, v in breakers.items() if k.startswith("cell|"))
        assert cell["state"] == "closed" and cell["trips"] == 1
        assert cell["probes"] == 1
        # And the cell serves normally again.
        eng.partition(_rmat(seed=32), 4)
    finally:
        eng.shutdown(drain=True)


def test_chaos_lanestack_demotion_bit_identical_and_restore():
    """A lanestack execute fault demotes the batch to the per-graph loop
    — BIT-IDENTICAL to sequential runs (the deterministic-demotion
    acceptance bar) — trips the per-cell breaker at threshold 1, skips
    the doomed stacked attempt while open, and the post-cooldown
    half-open probe restores the stacked path."""
    import warnings

    # Cooldown far above the test's wall so the open window is actually
    # observable; the restore round rewinds _open_until instead of
    # sleeping (CPU solves take seconds — real time is not controllable).
    eng = _engine(threshold=1, cooldown=300.0, lane_stack="on")
    eng.pause()
    eng.start(warmup=False)
    try:
        # Same seed -> same shape cell (the test_lanestack idiom): every
        # round below must land in the SAME cell as the tripped breaker.
        solver = KaMinPar(ctx="serve")
        solver.set_graph(_rmat(100, scale=8))
        seq = solver.compute_partition(4, 0.03)
        with injected_faults("execute@lanestack:execute-fault:n=1"):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                futs = [eng.submit(_rmat(100, scale=8), 4) for _ in range(2)]
                eng.resume()
                parts = [f.result(timeout=600).partition for f in futs]
        # Demoted batch == healthy per-graph path == sequential runs.
        for part in parts:
            assert np.array_equal(part, seq)
        # Batch formation may split a round into 1-request batches (the
        # 2 ms batch window races submit timing under load), so batch-
        # granular counters are lower-bounded; breaker STATE transitions
        # are the deterministic contract.
        stats = eng.stats()
        assert stats["lanestacked_batches"] == 0
        assert stats["lanestack_fallbacks"] >= 1
        ls = next(
            v for k, v in
            stats["resilience"]["engine"]["breakers"].items()
            if k.startswith("lanestack|")
        )
        assert ls["state"] == "open" and ls["trips"] == 1
        assert stats["resilience"]["engine"]["demotions"]["lanestack"] >= 1
        fallbacks_after_trip = stats["lanestack_fallbacks"]

        # While open: the stacked attempt is skipped (demotion, no probe).
        eng.pause()
        futs = [eng.submit(_rmat(100, scale=8), 4) for _ in range(2)]
        eng.resume()
        for f in futs:
            f.result(timeout=600)
        assert eng.stats_.counter("lanestacked_batches") == 0
        assert eng.stats_.counter("lanestack_fallbacks") > fallbacks_after_trip

        # "Post-cooldown": rewind the open window, then the half-open
        # probe runs stacked and restores the primary path.
        br_obj = next(
            v for k, v in eng.breakers._breakers.items()
            if k[0] == "lanestack"
        )
        with br_obj._lock:
            br_obj._open_until = time.monotonic() - 1.0
        eng.pause()
        futs = [eng.submit(_rmat(100, scale=8), 4) for _ in range(2)]
        eng.resume()
        for f in futs:
            f.result(timeout=600)
        stats = eng.stats()
        assert stats["lanestacked_batches"] >= 1
        ls = next(
            v for k, v in
            stats["resilience"]["engine"]["breakers"].items()
            if k.startswith("lanestack|")
        )
        assert ls["state"] == "closed"
        assert stats["resilience"]["engine"]["restorations"][
            "lanestack"
        ] == 1
    finally:
        eng.shutdown(drain=True)


def test_chaos_ip_device_demotion_bit_identical():
    """A faulted device-pool dispatch is not rescued by the host pool:
    the facade raises the typed error and records no demotion — a run
    that returns used the backend it resolved."""
    ctx = create_context_by_preset_name("default")
    ctx.initial_partitioning.ip_backend = "device"
    solver = KaMinPar(ctx)
    solver.set_graph(_rmat(seed=5, scale=7))
    with injected_faults("execute@ip_device:execute-fault:n=0"):
        with pytest.raises(ExecuteFault) as ei:
            solver.compute_partition(4, 0.03)
    assert ei.value.injected and ei.value.site == "ip_device"
    demos = rbreakers.global_registry().snapshot()["demotions"]
    assert "ip_device" not in demos


def test_chaos_device_decode_demotion_bit_identical():
    """A faulted compressed-view build demotes the run to the dense
    path — bit-identical by the round-14 contract — and opens the
    device_decode breaker after enough repeats."""
    import warnings

    def run(device_decode, inject):
        ctx = create_context_by_preset_name("default")
        ctx.compression.enabled = True
        ctx.compression.device_decode = device_decode
        solver = KaMinPar(ctx)
        solver.set_graph(_rmat(seed=6, scale=7))
        if inject:
            with injected_faults("execute@device_decode:execute-fault:n=0"):
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    return solver.compute_partition(4, 0.03)
        return solver.compute_partition(4, 0.03)

    dense = run("off", inject=False)
    demoted = run("finest", inject=True)
    assert np.array_equal(dense, demoted)
    demos = rbreakers.global_registry().snapshot()["demotions"]
    assert demos.get("device_decode", 0) >= 1


def test_chaos_pallas_demotion_bit_identical():
    """The healthy Pallas path (interpret mode off-TPU) is bit-identical
    to XLA, and a faulted Pallas LP dispatch raises the typed error
    instead of rerunning on the XLA twin — no demotion is recorded."""

    def solver(kernel):
        ctx = create_context_by_preset_name("default")
        ctx.coarsening.lp.lp_kernel = kernel
        ctx.refinement.lp.lp_kernel = kernel
        # Engage coarsening at small n (the clusterer owns the pallas
        # dispatch); the default C=2000 would skip LP clustering entirely
        # at this scale.
        ctx.coarsening.contraction_limit = 10
        s = KaMinPar(ctx)
        s.set_graph(_rmat(seed=8, scale=6))
        return s

    xla = solver("xla").compute_partition(2, 0.03)
    assert np.array_equal(xla, solver("pallas").compute_partition(2, 0.03))
    with injected_faults("execute@lp_pallas:execute-fault:n=1"):
        with pytest.raises(ExecuteFault) as ei:
            solver("pallas").compute_partition(2, 0.03)
    assert ei.value.injected and ei.value.site == "lp_pallas"
    assert "lp_pallas" not in rbreakers.global_registry().snapshot()[
        "demotions"
    ]


def test_pallas_dispatch_failure_raises_classified():
    """A Pallas failure after dispatch surfaces classified, with the
    kernel's own exception chained as the cause."""
    import jax.numpy as jnp

    from kaminpar_tpu.coarsening.lp_clusterer import LPClustering
    from kaminpar_tpu.context import LabelPropagationContext

    clus = LPClustering(LabelPropagationContext(lp_kernel="pallas"))

    def xla_it(state, inc):
        return state + inc

    def pallas_it(state, inc):
        raise RuntimeError("pallas died after dispatch")

    with pytest.raises(ExecuteFault) as ei:
        clus._run_iterate(pallas_it, xla_it, jnp.arange(4), jnp.int32(1))
    assert isinstance(ei.value.__cause__, RuntimeError)
    assert ei.value.site == "lp_pallas"
    out = clus._run_iterate(xla_it, xla_it, jnp.arange(4), jnp.int32(1))
    assert np.array_equal(np.asarray(out), np.arange(4) + 1)


def test_halfopen_cell_probe_served_stacked_closes_breaker():
    """A half-open cell probe served by the lane-stacked path must close
    the cell breaker — otherwise a healthy cell whose probes always
    succeed stays pinned at one request per cooldown."""
    eng = _engine(threshold=1, cooldown=300.0, lane_stack="on")
    eng.start(warmup=False)
    try:
        cell_key = None
        cbr = None
        # Trip the cell breaker directly (the state machine is unit-tested
        # above; this test is about WHO reports the probe outcome).
        from kaminpar_tpu.serve.batching import shape_cell

        g = _rmat(100, scale=8)
        cell = shape_cell(g, 4)
        cell_key = (cell.n_bucket, cell.m_bucket, cell.k)
        cbr = eng.breakers.get("cell", cell_key)
        cbr.record_failure()
        assert cbr.state == "open"
        with cbr._lock:
            cbr._open_until = time.monotonic() - 1.0
        p = eng.partition(g, 4)  # the half-open probe, served stacked
        assert p.size > 0
        assert eng.stats_.counter("lanestacked_batches") == 1
        assert cbr.state == "closed"
    finally:
        eng.shutdown(drain=True)


def test_fault_plan_disarmed_when_start_fails(monkeypatch):
    """start() failing after arming the context's fault plan must disarm
    it — shutdown's disarm is unreachable for a never-running engine."""
    ctx = create_context_by_preset_name("serve")
    ctx.resilience.fault_plan = "queue-admit:capacity-exceeded:n=0"
    eng = PartitionEngine(ctx, warm_ladder=(), warm_ks=(), queue_bound=8)
    monkeypatch.setattr(
        eng, "_resolve_capacity_ceiling",
        lambda: (_ for _ in ()).throw(RuntimeError("init died")),
    )
    with pytest.raises(RuntimeError, match="init died"):
        eng.start(warmup=False)
    assert rfaults.active_plan() is None
    rfaults.maybe_inject("queue-admit", site="post-failed-start")  # no raise


def test_chaos_queue_admit_fault_typed():
    eng = _engine().start(warmup=False)
    try:
        with injected_faults("queue-admit:capacity-exceeded:n=1"):
            with pytest.raises(CapacityExceeded) as ei:
                eng.submit(_rmat(seed=40), 4)
            assert ei.value.injected
            fut = eng.submit(_rmat(seed=41), 4)
            assert fut.result(timeout=600).partition.size > 0
    finally:
        eng.shutdown(drain=True)


def test_chaos_warmup_fault_contained():
    """A warmup-point fault degrades the engine to cold-start serving —
    start() completes, the fault is counted, requests still serve."""
    import warnings

    eng = _engine(warm_ladder=(64,), warm_ks=(2,))
    with injected_faults("warmup:backend-unavailable:n=1"):
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            eng.start(warmup=True)
    try:
        assert eng.running
        assert eng.stats_.counter("warmup_faults") == 1
        assert any("warmup" in str(w.message) for w in caught)
        p = eng.partition(_rmat(seed=50), 4)
        assert p.size > 0
    finally:
        eng.shutdown(drain=True)


def test_chaos_readback_fault_classified():
    """A readback-point fault inside the pipeline surfaces as the typed
    error through the engine's classifier and does not wedge drain."""
    eng = _engine().start(warmup=False)
    try:
        with injected_faults("readback:execute-fault:n=1:after=2"):
            with pytest.raises(ResilienceError):
                eng.partition(_rmat(seed=60), 4)
        p = eng.partition(_rmat(seed=61), 4)
        assert p.size > 0
    finally:
        eng.shutdown(drain=True)


def test_watchdog_times_out_hung_execute():
    """An execute overrunning the watchdog deadline has its future
    force-resolved with a typed ExecuteFault naming the watchdog, its
    cell breaker records the failure, and a dossier with the stack tail
    is captured; the engine keeps serving afterwards."""
    eng = _engine(execute_timeout=0.15).start(warmup=False)
    try:
        with injected_faults(
            "execute@engine_request:execute-fault:n=1:delay=0.8"
        ):
            fut = eng.submit(_rmat(seed=70), 4)
            with pytest.raises(ExecuteFault, match="watchdog"):
                fut.result(timeout=600)
        deadline = time.monotonic() + 5
        while eng.stats_.counter("watchdog_timeouts") == 0:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert eng.stats_.counter("watchdog_timeouts") == 1
        wd = eng.watchdog.snapshot()
        assert wd["fired"] == 1
        assert eng.watchdog.dossiers[0]["stack_tail"]
        # One observed hang TRIPS the cell breaker outright (each further
        # probe would wedge the dispatcher for a full deadline): the next
        # same-cell submit fast-fails with PoisonedCell.
        cbr = next(
            v for k, v in eng.breakers._breakers.items() if k[0] == "cell"
        )
        assert cbr.state == "open" and cbr.trips == 1
        with pytest.raises(PoisonedCell):
            eng.submit(_rmat(seed=70), 4)
        # Recovery: rewind the cooldown and serve the half-open probe.
        # The 0.15s deadline exists to catch the injected 0.8s hang
        # deterministically; a real CPU solve is slower than that, so
        # disarm it for the probe (deployments tune above their p99).
        eng.resilience.execute_timeout_s = 0.0
        with cbr._lock:
            cbr._open_until = time.monotonic() - 1.0
        p = eng.partition(_rmat(seed=70), 4)
        assert p.size > 0
        assert cbr.state == "closed"
    finally:
        eng.shutdown(drain=True)


def test_quality_fast_tier_and_capacity_demotion():
    """quality="fast" serves from the trimmed solver; capacity-class
    execute failures trip the per-cell quality breaker and demote later
    strong requests to the fast tier (counted + reversible)."""
    eng = _engine(threshold=2, cooldown=30.0).start(warmup=False)
    try:
        p = eng.partition(_rmat(seed=80), 4, quality="fast")
        assert p.size > 0
        with injected_faults("execute@engine_request:capacity-exceeded:n=2"):
            for i in range(2):
                with pytest.raises(CapacityExceeded):
                    eng.partition(_rmat(seed=81 + i), 4)
        import warnings

        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            p = eng.partition(_rmat(seed=83), 4)  # strong -> demoted
        assert p.size > 0
        assert eng.stats_.counter("demoted_quality") == 1
        assert any("quality_strong" in str(w.message) for w in caught)
        stats = eng.stats()
        assert stats["resilience"]["engine"]["demotions"][
            "quality_strong"
        ] == 1
        with pytest.raises(ValueError, match="quality"):
            eng.submit(_rmat(seed=84), 4, quality="bogus")
    finally:
        eng.shutdown(drain=True)


# ---------------------------------------------------------------------------
# Satellite: bounded shutdown drain with a dead/hung worker
# ---------------------------------------------------------------------------


def test_shutdown_bounded_drain_force_resolves_hung_worker():
    eng = _engine().start(warmup=False)
    release = threading.Event()
    started = threading.Event()
    original = eng._solver.compute_partition

    def _hang(*args, **kwargs):
        started.set()
        release.wait(30.0)
        return original(*args, **kwargs)

    eng._solver.compute_partition = _hang
    try:
        fut = eng.submit(_rmat(seed=90), 4)
        fut2 = eng.submit(_rmat(seed=91), 8)  # different cell: stays queued
        assert started.wait(30.0)
        t0 = time.monotonic()
        eng.shutdown(drain=True, timeout_s=0.3)
        assert time.monotonic() - t0 < 5.0, "drain must be bounded"
        with pytest.raises(WorkerHung):
            fut.result(timeout=1.0)
        with pytest.raises(WorkerHung):
            fut2.result(timeout=1.0)
        assert eng.stats_.counter("worker_hung") == 2
        assert not eng.running
    finally:
        release.set()


# ---------------------------------------------------------------------------
# Satellite: CSR ingestion hardening at the facade boundary
# ---------------------------------------------------------------------------


class TestGraphValidation:
    def _solver(self):
        return KaMinPar(ctx="default")

    def test_valid_graph_accepted(self):
        s = self._solver()
        s.copy_graph(
            np.array([0, 1, 2]), np.array([1, 0]),
            np.array([1, 1]), np.array([1, 1]),
        )
        assert s.graph is not None and s.graph.n == 2

    def test_rejects_nonmonotone_row_ptr(self):
        with pytest.raises(GraphValidationError, match="non-monotone"):
            self._solver().copy_graph(np.array([0, 2, 1, 4]),
                                      np.array([1, 2, 0, 0]))

    def test_rejects_bad_row_ptr_origin(self):
        with pytest.raises(GraphValidationError, match=r"row_ptr\[0\]"):
            self._solver().copy_graph(np.array([1, 2]), np.array([0]))

    def test_rejects_row_ptr_tail_mismatch(self):
        with pytest.raises(GraphValidationError, match=r"row_ptr\[-1\]"):
            self._solver().copy_graph(np.array([0, 1, 3]), np.array([1, 0]))

    def test_rejects_out_of_range_columns(self):
        with pytest.raises(GraphValidationError, match="out of range"):
            self._solver().copy_graph(np.array([0, 1, 2]), np.array([1, 9]))
        with pytest.raises(GraphValidationError, match="out of range"):
            self._solver().copy_graph(np.array([0, 1, 2]), np.array([-1, 0]))

    def test_rejects_negative_weights(self):
        with pytest.raises(GraphValidationError, match="negative edge"):
            self._solver().copy_graph(
                np.array([0, 1, 2]), np.array([1, 0]),
                None, np.array([1, -3]),
            )
        with pytest.raises(GraphValidationError, match="negative node"):
            self._solver().copy_graph(
                np.array([0, 1, 2]), np.array([1, 0]),
                np.array([-1, 1]), None,
            )

    def test_rejects_weight_shape_mismatch(self):
        with pytest.raises(GraphValidationError, match="shape"):
            self._solver().copy_graph(
                np.array([0, 1, 2]), np.array([1, 0]), np.array([1, 1, 1]),
            )

    def test_rejects_overflowing_total_weight(self):
        big = np.array([np.iinfo(np.int32).max, 2], dtype=np.int64)
        with pytest.raises(GraphValidationError, match="overflows int32"):
            self._solver().copy_graph(
                np.array([0, 1, 2]), np.array([1, 0]), big, None,
            )

    def test_rejects_overflow_on_64bit_build_exactly(self):
        """The total-weight sum must be exact: an int64 accumulator wraps
        modulo 2**64 and can NEVER exceed the 64-bit id_max, making the
        check dead for 64-bit builds (and wrapped totals pass 32-bit)."""
        from kaminpar_tpu.graph.csr import validate_csr_input

        huge = np.array([1 << 62, 1 << 62, 1 << 62, 1 << 62],
                        dtype=np.int64)
        with pytest.raises(GraphValidationError, match="overflows int64"):
            validate_csr_input(
                np.array([0, 1, 2, 3, 4]), np.array([1, 0, 3, 2]),
                huge, None, use_64bit=True,
            )

    def test_rejects_float_weights(self):
        """Float weights would be silently truncated by the index-typed
        cast — a different weighted problem, not a rounding detail."""
        with pytest.raises(GraphValidationError, match="integer"):
            self._solver().copy_graph(
                np.array([0, 1, 2]), np.array([1, 0]),
                np.array([1.9, 2.9]), None,
            )

    def test_rejects_nonmonotone_unsigned_row_ptr(self):
        """np.diff on an unsigned row_ptr WRAPS instead of going negative
        — the validation must diff in a signed dtype or the exact
        malformed input it exists for passes."""
        with pytest.raises(GraphValidationError, match="non-monotone"):
            self._solver().copy_graph(
                np.array([0, 2, 1, 4], dtype=np.uint32),
                np.array([1, 2, 0, 0]),
            )

    def test_rejects_float_indices(self):
        with pytest.raises(GraphValidationError, match="integer"):
            self._solver().copy_graph(
                np.array([0.0, 1.0, 2.0]), np.array([1, 0]),
            )

    def test_internal_construction_not_taxed(self):
        """from_numpy_csr without validate_input skips the checks —
        coarse-level construction inside the pipeline pays nothing."""
        from kaminpar_tpu.graph.csr import from_numpy_csr

        g = from_numpy_csr(np.array([0, 1, 2]), np.array([1, 0]))
        assert g.n == 2


# ---------------------------------------------------------------------------
# Satellite: queue admission under concurrent overload
# ---------------------------------------------------------------------------


def test_queue_admission_concurrent_overload():
    """N threads submitting past capacity: every submit either yields a
    future that resolves exactly once or a QueueFullError with a
    positive, sane retry_after estimate; nothing is lost or duplicated."""
    eng = _engine(queue_bound=4, max_batch=2)
    eng.pause()  # hold dispatch so the bound actually fills
    eng.start(warmup=False)
    graphs = [_rmat(seed=200 + i) for i in range(4)]
    futures, rejects, errors = [], [], []
    lock = threading.Lock()
    barrier = threading.Barrier(8)

    def submit(i):
        barrier.wait()
        try:
            fut = eng.submit(graphs[i % 4], 4)
            with lock:
                futures.append(fut)
        except QueueFullError as exc:
            with lock:
                rejects.append(exc.retry_after_s)
        except Exception as exc:  # noqa: BLE001 — the test records strays
            with lock:
                errors.append(exc)

    threads = [
        threading.Thread(target=submit, args=(i,)) for i in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        assert not errors, f"unexpected submit errors: {errors}"
        assert len(futures) + len(rejects) == 8, "no submission lost"
        assert len(futures) == 4, "admissions must respect the bound"
        assert len(rejects) == 4
        for retry in rejects:
            assert 0.0 < retry < 60.0, f"insane retry_after {retry}"
        eng.resume()
        ids = [f.result(timeout=600).request_id for f in futures]
        assert len(set(ids)) == len(ids), "duplicated resolution"
        stats = eng.stats()
        assert stats["submitted"] == 8
        assert stats["admitted"] == 4
        assert stats["rejected_full"] == 4
        assert stats["completed"] == 4
    finally:
        eng.shutdown(drain=True)


# ---------------------------------------------------------------------------
# tools chaos smoke (the soak the CI/tooling satellite wires)
# ---------------------------------------------------------------------------


def test_tools_chaos_soak(tmp_path):
    from kaminpar_tpu.tools.tools import chaos

    runs = tmp_path / "RUNS.jsonl"
    rc = chaos([
        "--plan", "execute@engine_request:execute-fault:n=1",
        "--requests", "3", "--scale", "6", "-k", "2",
        "--runs", str(runs), "--json",
    ])
    assert rc == 0
    import json

    lines = runs.read_text().strip().splitlines()
    assert len(lines) == 1
    entry = json.loads(lines[0])
    assert entry["kind"] == "chaos"
    metrics = entry["metrics"]
    assert metrics["chaos_injected_count"] == 1
    assert metrics["chaos_faulted"] == 1
    assert metrics["chaos_recovered"] == 1
    assert "chaos_recover_s" in metrics
