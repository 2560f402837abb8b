"""Mesh-replicated serve fleet tests (ISSUE 14): SLO-aware shape-cell
steering over per-device engine replicas, lane x device 2D fill, warm-cache
inheritance (compile-delta asserted), graph-id stickiness, fleet-level
backpressure (least-loaded retry-after), and the drain + cross-replica
resteer path under concurrent overload — zero lost/duplicated resolutions
(extending the PR 13 queue-admission test to the fleet tier).

Determinism is the acceptance witness: the same (graph, seed, k) request
returns a bit-identical partition regardless of which replica serves it,
asserted across cells x replicas against sequential facade runs.

Tier-1 keeps small graphs and warmup-free engines; the 8-replica x
8-lane aggregate-occupancy sweep is @slow.
"""

import os
import threading
import time

import jax
import numpy as np
import pytest

from kaminpar_tpu import CHECKOUT_DIR

from kaminpar_tpu.graph import generators
from kaminpar_tpu.kaminpar import KaMinPar
from kaminpar_tpu.serve import (
    PartitionFleet,
    QueueFullError,
)
from kaminpar_tpu.serve.batching import shape_cell
from kaminpar_tpu.serve.stats import ServeStats

SMALL = dict(warm_ladder=(), warm_ks=(), max_batch=4, queue_bound=16,
             lane_stack="off")


def _expected_cache_dir():
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CHECKOUT_DIR, ".xla_cache"
    )


def _rmat(seed, scale=8):
    return generators.rmat_graph(scale, edge_factor=4, seed=seed)


def _fleet(replicas=2, **overrides):
    kw = dict(SMALL)
    kw.update(overrides)
    return PartitionFleet("serve", replicas=replicas, **kw)


def _same_cell_graphs(n, k, scale=8):
    pool = [_rmat(seed=50 + i, scale=scale) for i in range(3 * n)]
    cells = [shape_cell(g, k) for g in pool]
    head = max(set(cells), key=cells.count)
    graphs = [g for g, c in zip(pool, cells) if c == head][:n]
    assert len(graphs) == n, "could not build a same-cell workload"
    return graphs


# ---------------------------------------------------------------------------
# Steering: lane-axis fill before device-axis spill, poisoned-cell avoidance
# ---------------------------------------------------------------------------


def test_steering_fills_lanes_then_spills_to_next_device():
    fleet = _fleet(replicas=2, max_batch=4)
    fleet.pause()  # before start: hold dispatch until the burst is queued
    fleet.start(warmup=False)
    try:
        graphs = _same_cell_graphs(8, k=4)
        futs = [fleet.submit(g, 4) for g in graphs]
        routed = [f.replica for f in futs]
        # Batch-join fill policy: the first max_batch requests land on one
        # replica (the lane axis fills), the rest spill to the sibling.
        assert sorted(routed) == [0, 0, 0, 0, 1, 1, 1, 1]
        assert routed[:4] == [routed[0]] * 4
        fleet.resume()
        for f in futs:
            f.result(timeout=600)
        snap = fleet.stats()
        occ = [r["batch_occupancy_max"] for r in snap["per_replica"]]
        assert sorted(occ) == [4, 4]
        assert snap["aggregate_occupancy"] == 8.0
        assert snap["resteers"] == 0
    finally:
        fleet.shutdown(drain=True)


def test_steering_avoids_replica_with_open_cell_breaker():
    fleet = _fleet(replicas=2).start(warmup=False)
    try:
        g = _rmat(seed=1)
        cell = shape_cell(g, 4)
        key = (cell.n_bucket, cell.m_bucket, cell.k)
        fleet.replicas[0].breakers.get("cell", key).trip()
        futs = [fleet.submit(g, 4) for _ in range(3)]
        assert all(f.replica == 1 for f in futs)
        for f in futs:
            f.result(timeout=600)
    finally:
        fleet.shutdown(drain=True)


def test_sticky_routing_hits_and_moves_on_drain():
    fleet = _fleet(replicas=2).start(warmup=False)
    try:
        g = _rmat(seed=2)
        home = fleet.submit(g, 4, graph_id="tenant-a").replica
        # Load the OTHER replica lightly so pure load-based steering would
        # prefer it; stickiness must keep tenant-a on its home replica.
        futs = [fleet.submit(g, 4, graph_id="tenant-a") for _ in range(3)]
        assert all(f.replica == home for f in futs)
        assert fleet.stats()["sticky_hits"] == 3
        for f in futs:
            f.result(timeout=600)
        fleet.drain_replica(home, reason="test")
        fut = fleet.submit(g, 4, graph_id="tenant-a")
        assert fut.replica != home
        fut.result(timeout=600)
        assert fleet.stats()["sticky_moves"] >= 1
    finally:
        fleet.shutdown(drain=True)


# ---------------------------------------------------------------------------
# Determinism: bit-identity across cells x replicas (acceptance witness)
# ---------------------------------------------------------------------------


def test_partition_bit_identical_across_replicas_and_cells():
    fleet = _fleet(replicas=2).start(warmup=False)
    try:
        for scale, k in ((7, 2), (9, 4)):  # two distinct shape cells
            g = _rmat(seed=3, scale=scale)
            solver = KaMinPar("serve")
            solver.set_graph(g)
            ref = solver.compute_partition(k, 0.03)
            for replica in range(2):
                part = fleet.submit(
                    g, k, replica=replica
                ).result(timeout=600).partition
                assert np.array_equal(part, ref), (
                    f"replica {replica} diverged at scale={scale} k={k}"
                )
    finally:
        fleet.shutdown(drain=True)


# ---------------------------------------------------------------------------
# Warm-cache inheritance: replica N+1 skips every cell already traced
# ---------------------------------------------------------------------------


def test_warm_inheritance_zero_compile_delta():
    from kaminpar_tpu.utils import compile_stats

    fleet = _fleet(
        replicas=2, warm_ladder=(256,), warm_ks=(4,),
    )
    try:
        fleet.replicas[0].start(warmup=True)
        assert fleet.replicas[0].warmup_cell_counts()["local"] >= 1
        before = compile_stats.compile_time_snapshot()["compile_events"]
        fleet.replicas[1].inherit_warmup(fleet.replicas[0])
        fleet.replicas[1].start(warmup=True)
        after = compile_stats.compile_time_snapshot()["compile_events"]
        # The inheriting replica skips every cell already traced: its
        # start raises ZERO compile events (the acceptance delta).
        assert after - before == 0
        counts = fleet.replicas[1].warmup_cell_counts()
        assert counts["inherited"] >= 1
        assert counts["local"] == 0
        assert all(
            row.get("inherited") for row in fleet.replicas[1].warmup_report
        )
        # The warm EMA seed carries over so retry-after estimates are real
        # from the first reject on the new replica too.
        assert fleet.replicas[1].stats_.service_time_estimate() > 0.0
        # Inherited-vs-local counts ride the engine Prometheus exposition.
        text = fleet.replicas[1].metrics_text()
        assert 'kaminpar_serve_warmup_cells_total{source="inherited"}' in text
        # The warm-hit accounting inherited too: a request in the
        # inherited cell reports warm at submit time.
        fleet._started = True
        g = generators.rmat_graph(8, edge_factor=8, seed=1)
        fut = fleet.submit(g, 4, replica=1)
        res = fut.result(timeout=600)
        assert res.warm_hit
    finally:
        fleet._started = True
        fleet.shutdown(drain=True)


def test_fleet_start_inherits_and_shares_cache_dir():
    fleet = _fleet(replicas=3, warm_ladder=(256,), warm_ks=(4,))
    try:
        fleet.start(warmup=True)
        # The process has one cache dir, and no replica moves it.
        assert jax.config.jax_compilation_cache_dir == _expected_cache_dir()
        devices = [
            eng.runtime.device_index for eng in fleet.replicas
        ]
        assert devices == [0, 1, 2], "one replica per mesh device"
        counts = [r.warmup_cell_counts() for r in fleet.replicas]
        assert counts[0]["local"] >= 1 and counts[0]["inherited"] == 0
        for c in counts[1:]:
            assert c["inherited"] >= 1 and c["local"] == 0
    finally:
        fleet.shutdown(drain=True)


# ---------------------------------------------------------------------------
# Fleet-level backpressure: least-loaded drain estimate (ISSUE 14 satellite)
# ---------------------------------------------------------------------------


def test_queue_full_retry_after_from_least_loaded_replica():
    fleet = _fleet(replicas=2, queue_bound=2, max_batch=4).start(warmup=False)
    try:
        fleet.pause()
        # Distinct smoothed service times: replica 0 slow, replica 1 fast.
        fleet.replicas[0].stats_.seed_service_time(10.0)
        fleet.replicas[1].stats_.seed_service_time(0.2)
        g = _rmat(seed=4)
        for _ in range(4):  # fill both bounded queues (2 + 2)
            fleet.submit(g, 4)
        with pytest.raises(QueueFullError) as exc:
            fleet.submit(g, 4)
        # The hint must be the LEAST-LOADED replica's drain estimate
        # (depth x EMA / max_batch = 2 x 0.2 / 4), not the rejecting (or
        # slowest) replica's 2 x 10 / 4 = 5 s.
        expected = fleet.replicas[1].stats_.retry_after_estimate(2, 4)
        assert abs(exc.value.retry_after_s - expected) < 1e-9
        assert exc.value.retry_after_s < 1.0
        assert fleet.stats()["rejected_full"] == 1
    finally:
        fleet.resume()
        fleet.shutdown(drain=True)


def test_retry_after_stays_unamortized_for_lanestacked_batches():
    # The PR 6 rule feeding the fleet estimate: the EMA takes the
    # UNAMORTIZED batch wall (service_s), not the per-lane execute share,
    # because retry_after_estimate divides by the batch width itself.
    stats = ServeStats()
    stats.record_request(0.0, 0.1, service_s=0.8)  # share 0.1s of a 0.8s stack
    assert abs(stats.service_time_estimate() - 0.8) < 1e-9
    assert abs(stats.retry_after_estimate(4, 8) - 4 * 0.8 / 8) < 1e-9


def test_unroutable_fleet_rejects_with_retry_hint():
    fleet = _fleet(replicas=2).start(warmup=False)
    try:
        fleet.drain_replica(0, reason="test")
        fleet.drain_replica(1, reason="test")
        with pytest.raises(QueueFullError) as exc:
            fleet.submit(_rmat(seed=5), 4)
        assert exc.value.retry_after_s > 0.0
        assert fleet.stats()["rejected_unroutable"] == 1
    finally:
        fleet.shutdown(drain=True)


# ---------------------------------------------------------------------------
# Drain + cross-replica resteer (extends the PR 13 queue-admission test)
# ---------------------------------------------------------------------------


def test_drain_resteer_concurrent_overload_no_lost_no_duplicated():
    """8 threads submit past one replica's batch capacity while that
    replica is drained mid-burst: every admitted request resolves exactly
    once (on a healthy replica), every reject carries a sane retry_after,
    and fleet ids stay unique — the PR 13 force-resolve machinery extended
    to cross-replica requeue."""
    fleet = _fleet(replicas=2, queue_bound=8, max_batch=2)
    # Pause BEFORE the dispatchers start: a post-start pause only takes
    # effect before the *next* batch (the dispatcher may already be inside
    # pop_batch), which would let the victim serve a batch pre-drain.
    fleet.pause()
    fleet.start(warmup=False)
    graphs = _same_cell_graphs(4, k=4)
    futures, rejects, errors = [], [], []
    lock = threading.Lock()
    barrier = threading.Barrier(8)

    def submit(i):
        barrier.wait()
        try:
            fut = fleet.submit(graphs[i % 4], 4)
            with lock:
                futures.append(fut)
        except QueueFullError as exc:
            with lock:
                rejects.append(exc.retry_after_s)
        except Exception as exc:  # noqa: BLE001 — the test records strays
            with lock:
                errors.append(exc)

    try:
        threads = [
            threading.Thread(target=submit, args=(i,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, f"unexpected submit errors: {errors}"
        assert len(futures) + len(rejects) == 8, "no submission lost"
        for retry in rejects:
            assert 0.0 < retry < 60.0, f"insane retry_after {retry}"
        # Drain the replica holding the most queued work while every
        # request is still queued (dispatch is held) — the eager leg
        # requeues all of them on the sibling, honoring its bound.
        routed = [f.replica for f in futures]
        victim = max(set(routed), key=routed.count)
        fleet.drain_replica(victim, reason="test overload drain")
        deadline = time.monotonic() + 60
        while fleet.replicas[victim].running and time.monotonic() < deadline:
            time.sleep(0.02)
        fleet.resume()
        results = [f.result(timeout=600) for f in futures]
        ids = [f.fleet_id for f in futures]
        assert len(set(ids)) == len(ids), "duplicated resolution"
        assert all(r.partition is not None for r in results)
        # Every drained request moved off the victim.
        assert all(f.replica != victim for f in futures)
        snap = fleet.stats()
        assert snap["drains"] == 1
        assert snap["resteers"] >= routed.count(victim)
        # A second result() call returns the SAME resolution (first-wins
        # finalization).
        again = futures[0].result(timeout=5)
        assert again is results[0]
    finally:
        fleet.shutdown(drain=True)


def test_drained_replica_restored_by_half_open_probe():
    fleet = _fleet(replicas=2).start(warmup=False)
    fleet.fleet_ctx.replica_cooldown_s = 0.2
    fleet.breakers.cooldown_s = 0.2
    # Score on queue depth alone: both replicas carry noisy warm-up p99
    # samples, and this test is about probe admission, not tail steering.
    fleet.fleet_ctx.steer_p99_weight = 0.0
    try:
        g = _rmat(seed=6)
        fleet.submit(g, 4, replica=0).result(timeout=600)
        fleet.drain_replica(0, reason="test")
        # Tripped breaker: replica 0 is out of rotation.
        assert fleet.submit(g, 4).replica == 1
        # Recreate the breaker with the short cooldown (the registry's
        # default cooldown applied when the breaker was first created).
        br = fleet.breakers.get("replica", (0,))
        br.cooldown_s = 0.2
        br.trip()
        time.sleep(0.3)
        # Load replica 1 so the score prefers the probe-restored replica 0.
        fleet.pause()
        futs = [fleet.submit(g, 4) for _ in range(6)]
        fleet.resume()
        for f in futs:
            f.result(timeout=600)
        assert any(f.replica == 0 for f in futs), (
            "half-open probe should have restored + used replica 0"
        )
        assert fleet.stats()["restores"] >= 1
        assert fleet.replicas[0].running
    finally:
        fleet.shutdown(drain=True)


def test_sticky_home_capacity_reject_steers_to_bigger_sibling():
    """Sticky/pinned candidates bypass the scan's capacity filter, so a
    request oversize for its home replica must fall through to a sibling
    with a larger ceiling instead of surfacing CapacityError — ceilings
    are per-replica (heterogeneous fleets)."""
    from kaminpar_tpu.serve.errors import CapacityError

    fleet = _fleet(replicas=2).start(warmup=False)
    try:
        g = _rmat(seed=9)
        home = fleet.submit(g, 4, graph_id="tenant-c").replica
        # Shrink the home replica's ceiling so its admission preflight
        # now rejects this cell; the sibling keeps the real ceiling.
        fleet.replicas[home]._capacity_ceiling = 1
        fut = fleet.submit(g, 4, graph_id="tenant-c")
        assert fut.replica != home
        fut.result(timeout=600)
        # When EVERY replica is too small the typed error surfaces (with
        # the router counter bumped), not a retry-forever hint.
        for eng in fleet.replicas:
            eng._capacity_ceiling = 1
        with pytest.raises(CapacityError):
            fleet.submit(g, 4)
        assert fleet.stats()["rejected_capacity"] >= 1
    finally:
        fleet.shutdown(drain=True)


def test_inflight_success_during_drain_keeps_breaker_open():
    """A success delivered by a DRAINING replica (in-flight work finishing
    inside the bounded drain) must NOT close its tripped fleet breaker:
    closed + draining is unroutable forever — only the half-open probe
    path clears the draining flag, and it requires a non-closed breaker."""
    fleet = _fleet(replicas=2)
    fleet.breakers.cooldown_s = 0.2
    fleet.start(warmup=False)
    try:
        g = _rmat(seed=8)
        fleet.submit(g, 4, replica=0).result(timeout=600)
        fleet.drain_replica(0, reason="test")
        t = fleet._drain_threads[0]
        assert t is not None
        t.join(60)
        assert not t.is_alive()
        # The in-flight success arrives after the trip: the waiter-side
        # hook must leave the tripped breaker open while draining.
        from kaminpar_tpu.serve.fleet import _FleetRecord

        rec = _FleetRecord(999, g, 4, 0.03, {}, None)
        rec.replica = 0
        fleet._note_success(rec)
        br = fleet.breakers.get("replica", (0,))
        assert br.state == "open", (
            "success during drain must not close the replica breaker"
        )
        # The half-open probe still restores the replica afterwards.
        time.sleep(0.3)
        ok, is_probe = fleet._replica_available(0)
        assert ok and is_probe
        assert fleet.replicas[0].running
        assert not fleet._draining[0]
    finally:
        fleet.shutdown(drain=True)


# ---------------------------------------------------------------------------
# Observability: Prometheus exposition, phase registry, trace instants
# ---------------------------------------------------------------------------


def test_fleet_prometheus_exposition_validates():
    from kaminpar_tpu.telemetry import prometheus

    fleet = _fleet(replicas=2).start(warmup=False)
    try:
        fleet.submit(_rmat(seed=7), 4).result(timeout=600)
        text = fleet.metrics_text()
        prometheus.validate(text)
        assert "kaminpar_fleet_replicas 2" in text
        assert "kaminpar_fleet_steered_total" in text
        assert "kaminpar_fleet_warmup_cells_total" in text
        assert 'scope="fleet"' in text
        snap = fleet.stats()
        assert snap["breakers"]["scope"] == "fleet"
    finally:
        fleet.shutdown(drain=True)


def test_fleet_steer_phase_registered():
    from kaminpar_tpu.telemetry import phases

    assert phases.is_known("fleet_steer")


def test_replica_rung_in_ladder():
    from kaminpar_tpu.resilience.breakers import LADDER

    assert LADDER["replica"] == "resteer"


# ---------------------------------------------------------------------------
# Lane x device 2D: per-replica lane-stacked batches
# ---------------------------------------------------------------------------


def test_lanestacked_batches_across_replicas():
    fleet = _fleet(replicas=2, max_batch=2, lane_stack="auto")
    fleet.pause()  # before start: hold dispatch until the burst is queued
    fleet.start(warmup=False)
    try:
        graphs = _same_cell_graphs(4, k=2, scale=7)
        futs = [fleet.submit(g, 2) for g in graphs]
        assert sorted(f.replica for f in futs) == [0, 0, 1, 1]
        fleet.resume()
        for f in futs:
            f.result(timeout=600)
        snap = fleet.stats()
        stacked = [r["lanestacked_batches"] for r in snap["per_replica"]]
        lanes = [r["lanestacked_lanes"] for r in snap["per_replica"]]
        # Each replica ran its micro-batch as ONE vmapped stack (the lane
        # axis) on its own device (the device axis).
        assert all(s >= 1 for s in stacked)
        assert snap["aggregate_lanestacked_lanes"] == sum(lanes) == 4
    finally:
        fleet.shutdown(drain=True)


@pytest.mark.slow
def test_aggregate_occupancy_64_on_8_replica_mesh():
    """The ROADMAP "millions of users" configuration on the CPU dryrun:
    64 same-cell requests over 8 replicas x max_batch 8 fill the full
    lane x device plane (aggregate occupancy >= 64), with per-replica
    results bit-identical to a sequential facade run."""
    fleet = _fleet(replicas=8, max_batch=8, queue_bound=64)
    fleet.pause()  # before start: hold dispatch until the burst is queued
    fleet.start(warmup=False)
    try:
        graphs = _same_cell_graphs(64, k=4)
        solver = KaMinPar("serve")
        solver.set_graph(graphs[0])
        ref = solver.compute_partition(4, 0.03)
        futs = [fleet.submit(g, 4) for g in graphs]
        fleet.resume()
        results = [f.result(timeout=1800) for f in futs]
        assert np.array_equal(results[0].partition, ref)
        snap = fleet.stats()
        assert snap["aggregate_occupancy"] >= 64.0
        used = {f.replica for f in futs}
        assert used == set(range(8))
    finally:
        fleet.shutdown(drain=True)
