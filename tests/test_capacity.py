"""Executable-grade observability (ISSUE 12): capacity planner
predicted-vs-measured validation, executable-census neutrality, serve
admission preflight, and the hang-forensics flight recorder's dossier
round trip."""

import json
import os
import sys

import numpy as np
import pytest

from kaminpar_tpu.serve import CapacityError, PartitionEngine
from kaminpar_tpu.telemetry import capacity, flight_recorder
from kaminpar_tpu.utils import collective_stats, compile_stats, sync_stats
from kaminpar_tpu.utils import heap_profiler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarm_census():
    """Every test starts and ends with the census disarmed (it is
    process-global, like the compile-shape census)."""
    compile_stats.arm_executable_census(False)
    yield
    compile_stats.arm_executable_census(False)


# -- capacity model vs measured residency (acceptance) -----------------------


def test_predicted_vs_measured_watermark_cpu_scale12():
    """The resident-buffer model must land within the stated tolerance of
    the constructed views' live-array bytes on CPU, for BOTH the dense and
    the device_decode arms (ISSUE 12 acceptance)."""
    out = capacity.validate_cpu(scale=12)
    assert out["watermark_backend"] == "cpu_rss_proxy"
    for arm in ("dense", "device_decode"):
        rel = out[arm]["rel_err"]
        assert rel <= capacity.VALIDATION_TOLERANCE, (
            f"{arm}: predicted {out[arm]['predicted_bytes']} vs measured "
            f"{out[arm]['measured_bytes']} (rel err {rel} > "
            f"{capacity.VALIDATION_TOLERANCE})"
        )
        assert out[arm]["measured_bytes"] > 0


def test_watermark_report_labels_backend():
    """ISSUE 12 satellite: CPU-measured watermarks carry an explicit
    backend label (+ the RSS/live-array proxy numbers) so they can never be
    silently compared against HBM ceilings."""
    rep = heap_profiler.watermark_report()
    assert rep["backend"] in ("cpu_rss_proxy", "cpu_allocator", "tpu_hbm")
    if rep["backend"] == "cpu_rss_proxy":
        assert rep["rss_bytes"] > 0
        assert rep["peak_rss_bytes"] > 0
        assert rep["live_array_bytes"] >= 0


def test_capacity_prediction_and_ladder():
    pred = capacity.predict("rmat", 16, 64, device_kind="v5e")
    assert pred.predicted_peak_bytes > pred.resident_bytes > 0
    assert pred.ceiling_bytes is not None and pred.fits is True
    # Unknown device kind: no ceiling, fits is unknowable, never a crash.
    unk = capacity.predict("rmat", 16, 64, device_kind="weird")
    assert unk.ceiling_bytes is None and unk.fits is None
    lad = capacity.ladder(
        "rmat", 64, device_kind="v5e", scales=range(16, 29, 4)
    )
    fits = [row["dense"].fits for row in lad["rows"]]
    # Monotone: once a scale stops fitting, larger scales don't fit either.
    assert fits == sorted(fits, reverse=True)
    assert lad["max_feasible_scale"]["dense"] is not None
    # The compressed arm fits at least as far as the dense arm.
    assert (lad["max_feasible_scale"]["device_decode"]
            >= lad["max_feasible_scale"]["dense"])


def test_capacity_census_temp_harvest():
    """Armed, the planner reads the cell's temp bytes from XLA's own
    memory_analysis (shape-only lowering — no device arrays exist)."""
    compile_stats.arm_executable_census()
    pred = capacity.predict("rmat", 10, 8, device_kind="v5e")
    assert pred.temp_source == "xla_memory_analysis"
    assert pred.temp_bytes > 0
    snap = compile_stats.executable_census_snapshot()
    rows = {k: v for k, v in snap.items()
            if k.startswith("capacity_contraction|")}
    assert rows, f"census rows missing: {sorted(snap)}"
    row = next(iter(rows.values()))
    assert row["peak_bytes"] >= row["temp_bytes"] > 0
    assert row["flops"] is not None
    # A second predict for the same cell reuses the cached row — no
    # second compile of the identical executable.
    before = compile_stats.compile_time_snapshot()["compile_events"]
    capacity.predict("rmat", 10, 8, device_kind="v5e")
    assert compile_stats.compile_time_snapshot()["compile_events"] == before


def test_harvest_failure_not_retried(monkeypatch):
    """A failed lower/compile is negative-cached: the ladder must not pay
    the failing compile once per row (code-review finding)."""
    compile_stats.arm_executable_census()
    calls = {"n": 0}
    real = compile_stats.harvest_fn

    def counting(*a, **kw):
        calls["n"] += 1
        return None  # simulate a compile failure

    monkeypatch.setattr(compile_stats, "harvest_fn", counting)
    capacity._harvest_attempted.discard((333, 4444))
    assert capacity.harvest_temp_bytes(333, 4444) is None
    assert capacity.harvest_temp_bytes(333, 4444) is None
    assert calls["n"] == 1
    monkeypatch.setattr(compile_stats, "harvest_fn", real)
    capacity._harvest_attempted.discard((333, 4444))


# -- census neutrality (acceptance) ------------------------------------------


def _partition_with_census(arm: bool):
    from kaminpar_tpu.graph.generators import rmat_graph
    from kaminpar_tpu.kaminpar import KaMinPar
    from kaminpar_tpu.utils import RandomState

    RandomState.reseed(7)
    sync_stats.reset()
    collective_stats.reset()
    compile_stats.arm_executable_census(arm)
    g = rmat_graph(9, edge_factor=8, seed=3)
    solver = KaMinPar(ctx="default")
    solver.set_graph(g)
    part = solver.compute_partition(8, 0.03)
    snap = sync_stats.snapshot()
    pulls = {ph: row["count"] for ph, row in snap["phases"].items()}
    colls = collective_stats.snapshot()["count"]
    return np.asarray(part), pulls, colls


def test_census_neutrality_bit_identical_and_pull_counts():
    """Armed vs off must be bit-identical with equal per-phase pull counts
    and zero added collectives (ISSUE 12 acceptance — the census is pure
    host-side compiler introspection)."""
    part_off, pulls_off, colls_off = _partition_with_census(False)
    part_on, pulls_on, colls_on = _partition_with_census(True)
    assert np.array_equal(part_off, part_on)
    assert pulls_on == pulls_off
    assert colls_on == colls_off


def test_census_harvest_adds_no_transfers():
    import jax
    import jax.numpy as jnp

    compile_stats.arm_executable_census()
    sync_stats.reset()
    before = collective_stats.snapshot()["count"]
    from kaminpar_tpu.ops.contraction import _contract_device

    nn = jax.ShapeDtypeStruct((256,), jnp.int32)
    mm = jax.ShapeDtypeStruct((1024,), jnp.int32)
    row = compile_stats.harvest_fn(
        "capacity_contraction", _contract_device, nn, mm, mm, mm, nn,
        cell=(256, 1024),
    )
    assert row is not None and row["temp_bytes"] is not None
    snap = sync_stats.snapshot()
    assert snap["count"] == 0 and snap["implicit"] == 0
    assert collective_stats.snapshot()["count"] == before


def test_census_prometheus_families_render():
    from kaminpar_tpu.telemetry import prometheus

    compile_stats.arm_executable_census()
    capacity.harvest_temp_bytes(512, 2048)
    text = prometheus.render(compile_stats.census_prometheus_families())
    families = prometheus.validate(text)
    assert prometheus.get_sample(
        families, "kaminpar_executable_census_total"
    ) >= 1


# -- serve admission preflight (acceptance) ----------------------------------


def test_preflight_rejects_predicted_oversize():
    from kaminpar_tpu.graph.generators import rmat_graph

    g = rmat_graph(10, edge_factor=8, seed=1)
    engine = PartitionEngine(
        "serve", capacity_ceiling_bytes=64 * 1024
    ).start(warmup=False)
    try:
        # Preflight contract: the submit path NEVER lowers or compiles,
        # even with the census armed — it reads cached rows only.
        compile_stats.arm_executable_census()
        compiles_before = compile_stats.compile_time_snapshot()["compile_events"]
        with pytest.raises(CapacityError) as ei:
            engine.submit(g, 8)
        assert (compile_stats.compile_time_snapshot()["compile_events"]
                == compiles_before)
        err = ei.value
        assert err.predicted_bytes > err.ceiling_bytes == 64 * 1024
        assert len(err.cell) == 3 and err.cell[2] == 8
        assert engine.stats_.counter("rejected_capacity") == 1
        # The reject happened before queueing: nothing admitted, queue empty.
        assert engine.stats_.counter("admitted") == 0
        snap = engine.stats_.snapshot(queue_depth=0)
        assert snap["rejected_capacity"] == 1
    finally:
        engine.shutdown(drain=False)


def test_preflight_passes_within_ceiling_and_off_mode():
    from kaminpar_tpu.graph.generators import rmat_graph

    g = rmat_graph(7, edge_factor=4, seed=1)
    # Huge explicit ceiling: the request must sail through admission.
    engine = PartitionEngine(
        "serve", capacity_ceiling_bytes=1 << 40
    ).start(warmup=False)
    try:
        assert engine.partition(g, 4).shape == (g.n,)
    finally:
        engine.shutdown(drain=True)
    # preflight=off ignores even an absurd ceiling.
    engine = PartitionEngine(
        "serve", capacity_ceiling_bytes=1, capacity_preflight="off"
    ).start(warmup=False)
    try:
        assert engine.partition(g, 4).shape == (g.n,)
    finally:
        engine.shutdown(drain=True)


def test_preflight_default_cpu_passes():
    """On CPU without allocator stats no ceiling is derivable: auto mode
    must not reject anything (the honest no-ceiling reading)."""
    from kaminpar_tpu.graph.generators import rmat_graph

    engine = PartitionEngine("serve").start(warmup=False)
    try:
        if engine._capacity_ceiling is None:
            assert engine.partition(
                rmat_graph(7, edge_factor=4, seed=1), 4
            ).shape == (128,)
    finally:
        engine.shutdown(drain=True)


# -- flight recorder + dossier (acceptance) ----------------------------------


def test_flight_recorder_heartbeats_and_dossier(tmp_path):
    hb = str(tmp_path / "hb.jsonl")
    rec = flight_recorder.FlightRecorder(hb, interval_s=0.05)
    rec.start()
    rec.note("backend_init")
    import time as _time

    _time.sleep(0.3)
    rec.stop()
    dossier = flight_recorder.read_dossier(hb)
    assert dossier is not None
    assert dossier["heartbeats"] >= 3
    assert dossier["phase"] == "backend_init"
    assert dossier["phase_class"] == "init"
    assert dossier["last_heartbeat"]["rss_bytes"] > 0


def test_flight_recorder_reads_phase_board(tmp_path):
    from kaminpar_tpu.utils.timer import scoped_timer

    hb = str(tmp_path / "hb.jsonl")
    rec = flight_recorder.FlightRecorder(hb, interval_s=5.0)
    with scoped_timer("coarsening"):
        rec.beat()
    dossier = flight_recorder.read_dossier(hb)
    assert dossier["phase"] == "coarsening"
    assert dossier["phase_class"] == "execute"


def test_classify_phase():
    assert flight_recorder.classify_phase(None) == "init"
    assert flight_recorder.classify_phase("backend_init") == "init"
    assert flight_recorder.classify_phase("serve_warmup") == "compile"
    assert flight_recorder.classify_phase("lp_refinement") == "execute"


# -- tools CLI ----------------------------------------------------------------


def test_tools_capacity_cli(capsys):
    from kaminpar_tpu.tools.tools import capacity as capacity_tool

    rc = capacity_tool([
        "--device-kind", "v5e", "--scales", "16:20", "-k", "8", "--no-census",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "max feasible scale" in out and "dense" in out


def test_tools_capacity_cli_json(capsys):
    from kaminpar_tpu.tools.tools import capacity as capacity_tool

    rc = capacity_tool([
        "--scales", "16:18", "--json", "--no-census",
    ])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["max_feasible_scale"]["dense"] is not None
    assert payload["rows"][0]["dense"]["predicted_peak_bytes"] > 0


# -- ledger integration -------------------------------------------------------


def test_ledger_entry_carries_executable_census(tmp_path):
    from kaminpar_tpu.telemetry import ledger

    compile_stats.arm_executable_census()
    capacity.harvest_temp_bytes(512, 2048)
    entry = ledger.build_entry({"backend": "cpu", "value": 1.0}, kind="bench")
    census = entry["executable_census"]
    assert census["executables"] >= 1
    assert census["peak_bytes_max"] > 0
    path = str(tmp_path / "runs.jsonl")
    ledger.append(entry, path)
    assert ledger.read(path)[0]["executable_census"]["executables"] >= 1
