"""Request-scoped distributed tracing + SLO burn accounting (ISSUE 20).

Tentpole contracts under test:

* every submit mints a trace id whose event chain reads steer → admit →
  dispatch → resolve as one CONNECTED dossier (``engine.explain`` /
  ``fleet.explain``), even across a replica kill (resteer) or an engine
  crash + journal replay — zero orphan spans;
* tracing is host-only: arming it adds ZERO blocking transfers;
* SLO burn-rate pressure is a control input only — partitions are
  bit-identical with the SLO layer armed or off;
* terminal events export the request's life onto a per-request lane of
  the active Chrome trace.
"""

import threading
import time

import numpy as np
import pytest

from kaminpar_tpu.graph import generators
from kaminpar_tpu.serve import journal as J
from kaminpar_tpu.serve.engine import PartitionEngine
from kaminpar_tpu.serve.fleet import PartitionFleet
from kaminpar_tpu.telemetry import trace as ttrace
from kaminpar_tpu.telemetry.reqtrace import ReqTrace
from kaminpar_tpu.telemetry.slo import BurnTracker, prometheus_families

SMALL = dict(warm_ladder=(), warm_ks=(), max_batch=4, queue_bound=16)


def _rmat(seed, scale=7):
    return generators.rmat_graph(scale, edge_factor=4, seed=seed)


def _events(dossier):
    return [ev["event"] for ev in dossier["events"]]


# -- registry unit tests ------------------------------------------------------


def test_mint_bind_and_bounds():
    rt = ReqTrace(capacity=4, max_events=3)
    assert len({rt.mint() for _ in range(16)}) == 16
    tid = rt.mint()
    rt.bind(7, tid)
    rt.bind_fleet(70, tid)
    assert rt.trace_for_request(7) == tid
    assert rt.trace_for_fleet(70) == tid
    for i in range(5):
        rt.record(tid, "admit", request_id=7, seq=i)
    assert len(rt.events(tid)) == 3  # max_events cap
    assert rt.dropped_events == 2
    for i in range(10):
        rt.record(f"stray-{i}", "admit")
    snap = rt.snapshot()
    assert snap["traces"] <= 4  # capacity eviction
    assert snap["evicted_traces"] > 0
    rt.record("", "admit")  # empty trace id is a no-op
    assert rt.dossier("no-such-trace") is None
    assert rt.explain_request(12345) is None


def test_dossier_connectivity_and_orphans():
    rt = ReqTrace()
    tid = rt.mint()
    rt.record(tid, "steer", fleet_id=1)
    rt.record(tid, "admit", request_id=11, engine="replica0")
    rt.record(tid, "dispatch", request_id=11, engine="replica0")
    rt.record(tid, "resolve", request_id=11, final=True, engine="replica0")
    d = rt.dossier(tid)
    assert _events(d) == ["steer", "admit", "dispatch", "resolve"]
    s = d["summary"]
    assert s["connected"] and s["resolved"] and s["outcome"] == "resolve"
    assert s["roots"] == 2 and s["orphan_events"] == 0
    assert s["engines"] == ["replica0"]

    # a request-scoped event with no matching admit in the trace is an
    # orphan and breaks connectivity — the replay/resteer tripwire
    tid2 = rt.mint()
    rt.record(tid2, "steer")
    rt.record(tid2, "resolve", request_id=99, final=True)
    s2 = rt.dossier(tid2)["summary"]
    assert s2["orphan_events"] == 1 and not s2["connected"]

    # a non-final (resteerable) error is NOT a terminal resolution
    tid3 = rt.mint()
    rt.record(tid3, "admit", request_id=5)
    rt.record(tid3, "error", request_id=5, final=False,
              failure_class="worker-hung")
    s3 = rt.dossier(tid3)["summary"]
    assert not s3["resolved"] and s3["outcome"] is None
    rt.record(tid3, "admit", request_id=6, engine="replica1")
    rt.record(tid3, "resolve", request_id=6, final=True)
    s3 = rt.dossier(tid3)["summary"]
    assert s3["resolved"] and s3["connected"] and s3["outcome"] == "resolve"


def test_reqtrace_is_host_only():
    """Arming request tracing must add ZERO blocking transfers: every
    ReqTrace operation is dict bookkeeping under a lock."""
    from kaminpar_tpu.utils import sync_stats

    sync_stats.reset()
    rt = ReqTrace()
    with sync_stats.scoped("reqtrace_export"):
        tid = rt.mint()
        rt.bind(1, tid)
        rt.record(tid, "admit", request_id=1)
        rt.record(tid, "resolve", request_id=1, final=True, cut=42)
        rt.dossier(tid)
        rec = ttrace.TraceRecorder()
        rt.export_chrome(rec, tid)
    sync_stats.enable_budget_checks(True)
    try:
        sync_stats.assert_phase_budget("reqtrace_export", 0)
    finally:
        sync_stats.enable_budget_checks(False)
        sync_stats.reset()


def test_new_phases_registered():
    from kaminpar_tpu.telemetry import phases

    assert "reqtrace_export" in phases.KNOWN_PHASES
    assert "slo_eval" in phases.KNOWN_PHASES


# -- SLO burn accounting ------------------------------------------------------


def test_burn_tracker_math_and_pressure():
    bt = BurnTracker(strong_ms=100.0, availability=0.9,
                     capacity_reject_rate=0.5, windows_s=(60.0,))
    for _ in range(8):
        bt.record_request("strong", 0.01, ok=True)
    bt.record_request("strong", 0.5, ok=True)   # misses the 100 ms target
    bt.record_request("strong", 0.01, ok=False)  # availability failure
    bt.record_reject(capacity=True)
    bt.record_reject(capacity=False)  # queue-full: NOT a capacity reject
    s = bt.summary()
    assert s["armed"]
    burns = s["windows"][0]["burn"]
    # 1 of 9 ok-requests missed latency, against a 10% budget (1 - 0.9)
    assert burns["latency_strong"] == pytest.approx((1 / 9) / 0.1)
    # 1 of 10 finished failed, against the same 10% budget
    assert burns["availability"] == pytest.approx(0.1 / 0.1)
    # 1 capacity reject of 11 submitted, against a 50% reject budget
    assert burns["capacity_reject"] == pytest.approx((1 / 11) / 0.5)
    assert s["worst_burn"] == pytest.approx(max(burns.values()))
    assert s["pressure"] == pytest.approx(max(0.0, s["worst_burn"] - 1.0))
    assert bt.pressure() == pytest.approx(s["pressure"], abs=1e-6)
    fams = {f[0] for f in prometheus_families(bt)}
    assert {"kaminpar_slo_burn_rate", "kaminpar_slo_worst_burn",
            "kaminpar_slo_pressure"} <= fams


def test_burn_tracker_disarmed_is_none():
    class Serve:
        slo_strong_ms = 0.0
        slo_fast_ms = 0.0
        slo_availability = 0.0
        slo_capacity_reject_rate = 0.0

    assert BurnTracker.from_serve(Serve()) is None
    assert prometheus_families(None) == []


# -- engine integration -------------------------------------------------------


def test_engine_explain_request_lifecycle_and_chrome_lane():
    # One engine drive covers the explain() lifecycle AND the Chrome
    # per-request lane export (the trace is armed for the whole run).
    rec = ttrace.start()
    try:
        eng = PartitionEngine("serve", **SMALL)
        eng.start(warmup=False)
        try:
            fut = eng.submit(_rmat(1), 4)
            fut.result(timeout=300)
            d = eng.explain(fut.request_id)
            assert d is not None
            evs = _events(d)
            assert evs[0] == "admit" and evs[-1] == "resolve"
            assert "dispatch" in evs
            s = d["summary"]
            assert s["connected"] and s["resolved"]
            assert s["outcome"] == "resolve"
            assert s["orphan_events"] == 0
            admit = d["events"][0]
            assert admit["request_id"] == fut.request_id
            assert admit["queue_position"] >= 1
            resolve = d["events"][-1]
            assert resolve["final"] is True and "cut" in resolve

            # a caller-supplied trace id extends the SAME chain
            rt_tid = eng.reqtrace.mint()
            eng.reqtrace.record(rt_tid, "steer", fleet_id=123)
            fut2 = eng.submit(_rmat(2), 4, trace_id=rt_tid)
            fut2.result(timeout=300)
            d2 = eng.reqtrace.dossier(rt_tid)
            assert _events(d2)[0] == "steer"
            assert _events(d2)[-1] == "resolve"
            assert d2["summary"]["connected"]

            snap = eng.stats()
            assert snap["reqtrace"]["minted"] >= 2
            assert snap["reqtrace"]["recorded_events"] >= 6
            assert snap["slo"] == {"armed": False}
        finally:
            eng.shutdown(drain=True)
    finally:
        ttrace.stop()
    chrome = rec.chrome_trace()
    req_spans = [ev for ev in chrome["traceEvents"]
                 if str(ev.get("name", "")).startswith("req.")
                 and ev.get("ph") == "B"]
    assert req_spans, "terminal resolve must export a per-request lane"
    assert any(ev["name"] == "req.admit" for ev in req_spans)
    assert all("trace_id" in ev.get("args", {}) for ev in req_spans)
    # the exported lane validates as part of the whole chrome trace
    from kaminpar_tpu.telemetry.trace import validate_chrome_trace

    validate_chrome_trace(chrome)


def test_slo_armed_bit_identical_partitions():
    """The bit-identity acceptance gate: burn-rate feedback is a control
    input only — an engine with objectives armed must produce the exact
    same partition as one with the SLO layer off."""
    g = _rmat(4)

    def run(**slo):
        eng = PartitionEngine("serve", **SMALL, **slo)
        eng.start(warmup=False)
        try:
            return np.asarray(
                eng.submit(g, 4).result(timeout=300).partition
            )
        finally:
            eng.shutdown(drain=True)

    off = run()
    armed = run(slo_strong_ms=0.001, slo_availability=0.999,
                slo_capacity_reject_rate=0.01)
    assert np.array_equal(off, armed)


def test_engine_slo_summary_and_metrics():
    eng = PartitionEngine("serve", slo_strong_ms=0.001, **SMALL)
    eng.start(warmup=False)
    try:
        eng.submit(_rmat(5), 4).result(timeout=300)
        slo = eng.stats()["slo"]
        assert slo["armed"]
        # a sub-millisecond target cannot be met: the burn saturates
        assert slo["worst_burn"] > 1.0 and slo["pressure"] > 0.0
        assert eng.steer_signals()["slo_pressure"] > 0.0
        text = eng.metrics_text()
        assert "kaminpar_slo_burn_rate" in text
        assert "kaminpar_slo_pressure" in text
    finally:
        eng.shutdown(drain=True)


# -- crash / resteer continuity (the satellite-4 acceptance tests) -----------


def test_journal_replay_trace_continuity(tmp_path):
    """Kill an engine with admitted-but-unserved work; the restarted
    engine replays the journal and every replayed request's dossier
    reads admit → journal_replay → resolve under the ORIGINAL trace id,
    connected with zero orphan spans."""
    path = tmp_path / "serve.jsonl"

    def engine():
        from kaminpar_tpu.presets import create_context_by_preset_name

        ctx = create_context_by_preset_name("serve")
        ctx.serve.journal_path = str(path)
        ctx.serve.journal_fsync_every = 1
        return PartitionEngine(ctx, **SMALL)

    e1 = engine()
    e1.start(warmup=False)
    e1.pause()
    futs = [e1.submit(_rmat(10 + i, scale=7), 4) for i in range(3)]
    tids = [e1.reqtrace.trace_for_request(f.request_id) for f in futs]
    assert all(tids)
    e1.shutdown(drain=False)  # dies with 3 unresolved admits

    e2 = engine()
    e2.start(warmup=False)
    try:
        deadline = time.monotonic() + 180

        def settled():
            # The journal's resolution record is written before the trace
            # records the resolve event: wait for both.
            if J.read_journal(str(path))["unresolved"]:
                return False
            dossiers = [e2.reqtrace.dossier(tid) for tid in tids]
            return all(d is not None and d["summary"]["resolved"]
                       for d in dossiers)

        while not settled() and time.monotonic() < deadline:
            time.sleep(0.1)
        assert not J.read_journal(str(path))["unresolved"]
        for tid in tids:
            d = e2.reqtrace.dossier(tid)
            assert d is not None, "replay must rebind the journaled id"
            evs = _events(d)
            assert "admit" in evs and "journal_replay" in evs
            s = d["summary"]
            assert s["replays"] == 1
            assert s["resolved"] and s["outcome"] == "resolve"
            assert s["connected"] and s["orphan_events"] == 0
            admit = next(ev for ev in d["events"]
                         if ev["event"] == "admit")
            assert admit.get("replayed") is True
    finally:
        e2.shutdown(drain=True)


def test_resteer_trace_continuity():
    """Kill (drain) the replica holding a queued burst: every resteered
    request's dossier shows the steer root, the first admit, the resteer
    hop, the second admit on the surviving replica, and the final
    resolve — one connected span tree, zero orphans."""
    fleet = PartitionFleet("serve", replicas=2, **SMALL)
    fleet.pause()
    fleet.start(warmup=False)
    try:
        graphs = [_rmat(20, scale=7)] * 4  # same cell: one home replica
        futs = [fleet.submit(g, 4) for g in graphs]
        routed = [f.replica for f in futs]
        victim = max(set(routed), key=routed.count)
        fleet.drain_replica(victim, reason="trace continuity test")
        deadline = time.monotonic() + 60
        while (fleet.replicas[victim].running
               and time.monotonic() < deadline):
            time.sleep(0.02)
        fleet.resume()
        for f in futs:
            f.result(timeout=600)
        moved = [f for f in futs if routed[futs.index(f)] == victim]
        assert moved, "the drain must have resteered at least one request"
        for f in futs:
            d = fleet.explain(f)
            assert d is not None
            s = d["summary"]
            assert s["connected"], f"orphans: {d['orphans']}"
            assert s["orphan_events"] == 0
            assert s["resolved"] and s["outcome"] == "resolve"
            assert _events(d)[0] == "steer"
        for f in moved:
            d = fleet.explain(f)
            s = d["summary"]
            assert s["resteers"] >= 1
            assert s["admits"] >= 2  # one per replica the request visited
            assert len(s["engines"]) >= 1
            resteer = next(ev for ev in d["events"]
                           if ev["event"] == "resteer")
            assert resteer["from_replica"] == victim
    finally:
        fleet.shutdown(drain=True)


# -- fleet integration --------------------------------------------------------


def test_fleet_steer_event_and_explain():
    fleet = PartitionFleet("serve", replicas=2, **SMALL)
    fleet.start(warmup=False)
    try:
        fut = fleet.submit(_rmat(30, scale=7), 4)
        fut.result(timeout=300)
        # explain by future, by fleet id, and by raw trace id agree
        d = fleet.explain(fut)
        assert d == fleet.explain(fut.fleet_id)
        assert d == fleet.explain(d["trace_id"])
        steer = d["events"][0]
        assert steer["event"] == "steer"
        assert len(steer["candidates"]) >= 1
        # the per-replica score inputs that chose the winner are recorded
        assert {s["replica"] for s in steer["scores"]} \
            == set(steer["candidates"])
        assert sum(1 for ev in d["events"] if ev["event"] == "steer") == 1
        s = d["summary"]
        assert s["connected"] and s["resolved"]
        assert s["engines"], "the admit event names the landing replica"
        snap = fleet.stats()
        assert snap["reqtrace"]["minted"] >= 1
        assert "slo_pressure" in snap
        assert "kaminpar_slo_fleet_pressure" in fleet.metrics_text()
    finally:
        fleet.shutdown(drain=True)


@pytest.mark.slow
def test_fleet_trace_matrix_burst():
    """Heavy fleet-trace matrix: a concurrent multi-cell burst across 2
    replicas under SLO steering with an active Chrome trace — every
    request's dossier stays connected, lanes are budgeted, and the
    combined trace still validates."""
    rec = ttrace.start()
    try:
        fleet = PartitionFleet(
            "serve", replicas=2, slo_strong_ms=250.0,
            slo_availability=0.99, **SMALL,
        )
        fleet.start(warmup=False)
        try:
            graphs = [_rmat(40 + i, scale=7 + (i % 2)) for i in range(12)]
            futs, lock = [], threading.Lock()

            def submit(g):
                fut = fleet.submit(g, 4)
                with lock:
                    futs.append(fut)

            threads = [threading.Thread(target=submit, args=(g,))
                       for g in graphs]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for f in futs:
                f.result(timeout=600)
            for f in futs:
                s = fleet.explain(f)["summary"]
                assert s["connected"] and s["resolved"]
                assert s["orphan_events"] == 0
            snap = fleet.reqtrace.snapshot()
            assert snap["minted"] >= len(graphs)
            assert snap["chrome_lanes_exported"] <= 64
        finally:
            fleet.shutdown(drain=True)
    finally:
        ttrace.stop()
    from kaminpar_tpu.telemetry.trace import validate_chrome_trace

    validate_chrome_trace(rec.chrome_trace())
