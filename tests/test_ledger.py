"""Run ledger + regression sentinel (ISSUE 8): RUNS.jsonl append/read,
noise-aware baseline comparison, the tools ledger/regress CLI contract
(regress exits nonzero on an injected 2x phase regression and zero on an
identical replay), and the bench salvage compression satellite."""

import json

import pytest

from kaminpar_tpu.telemetry import ledger


def _record(**overrides):
    rec = {
        "value": 2.5e6,
        "vs_baseline": 0.003,
        "backend": "cpu-fallback",
        "partition_wall_s": 120.0,
        "partition_cut": 60000,
        "host_sync_count": 48,
        "host_sync_bytes": 12345,
        "phase_walls_s": {"partitioning": 110.0, "lp_bench_fence": 4.0},
        "collectives": {"count": 30, "logical_bytes": 4096,
                        "by_op": {"psum": {"count": 25, "logical_bytes": 1024},
                                  "all_to_all": {"count": 5,
                                                 "logical_bytes": 3072}}},
        "compiled_shape_count": {"total": 40},
        "lint": {"fresh": 0},
    }
    rec.update(overrides)
    return rec


def test_entry_build_append_read_roundtrip(tmp_path):
    path = str(tmp_path / "RUNS.jsonl")
    entry = ledger.build_entry(_record(), kind="bench", git_head="abc1234")
    assert entry["schema"] == ledger.SCHEMA
    assert entry["kind"] == "bench"
    assert entry["git_head"] == "abc1234"
    assert entry["backend"] == "cpu-fallback"
    assert entry["metrics"]["partition_wall_s"] == 120.0
    assert entry["metrics"]["partition_cut"] == 60000
    assert entry["sync"]["count"] == 48
    assert entry["collectives"]["count"] == 30
    assert entry["collectives"]["by_op"] == {"psum": 25, "all_to_all": 5}
    assert entry["compiled_shapes"] == 40
    assert entry["stale_vs_head"] is False

    ledger.append(entry, path)
    ledger.append(ledger.build_entry(_record(), kind="bench"), path)
    entries = ledger.read(path)
    assert len(entries) == 2
    assert entries[0]["git_head"] == "abc1234"
    assert ledger.tail(1, path) == entries[-1:]

    # a torn write must not poison the ledger
    with open(path, "a") as fh:
        fh.write('{"truncated": tru\n')
    assert len(ledger.read(path)) == 2


def test_metric_direction_classes():
    assert ledger.metric_direction("partition_wall_s") == "down"
    assert ledger.metric_direction("serve_p99_ms") == "down"
    assert ledger.metric_direction("partition_cut") == "down"
    assert ledger.metric_direction("host_sync_count") == "down"
    assert ledger.metric_direction("value") == "up"
    assert ledger.metric_direction("serve_throughput_gps") == "up"
    assert ledger.metric_direction("lanestack_vs_pergraph") == "up"
    assert ledger.metric_direction("vs_baseline") == "up"


def test_compare_quiet_on_identical_and_within_noise():
    base = [ledger.build_entry(_record(), kind="bench") for _ in range(3)]
    # identical replay: silent
    assert ledger.compare(ledger.build_entry(_record(), kind="bench"), base) == []
    # within the noise tolerance: silent
    near = ledger.build_entry(
        _record(partition_wall_s=140.0,
                phase_walls_s={"partitioning": 125.0}), kind="bench"
    )
    assert ledger.compare(near, base) == []


def test_compare_flags_wall_census_quality_and_throughput():
    base = [ledger.build_entry(_record(), kind="bench") for _ in range(3)]
    bad = ledger.build_entry(
        _record(
            partition_wall_s=240.0,       # 2x wall
            host_sync_count=49,           # one stray blocking transfer
            partition_cut=70000,          # ~17% worse cut
            value=1.0e6,                  # throughput collapse
            phase_walls_s={"partitioning": 110.0, "lp_bench_fence": 4.0},
            collectives={"count": 31, "logical_bytes": 4096, "by_op": {}},
        ),
        kind="bench",
    )
    regs = {r["metric"]: r for r in ledger.compare(bad, base)}
    assert "partition_wall_s" in regs and regs["partition_wall_s"]["class"] == "wall"
    assert "census.host_sync_count" in regs
    assert regs["census.host_sync_count"]["class"] == "census"
    assert "census.collective_count" in regs
    assert "partition_cut" in regs and regs["partition_cut"]["class"] == "quality"
    assert "value" in regs and regs["value"]["class"] == "throughput"


def test_baseline_window_filters_kind_backend_and_workload():
    entries = [
        ledger.build_entry(_record(backend="cpu-fallback"), kind="bench"),
        ledger.build_entry(_record(backend="tpu"), kind="bench"),
        ledger.build_entry(_record(backend="cpu-fallback"), kind="serve"),
        ledger.build_entry(_record(backend="cpu-fallback"), kind="bench"),
        # same kind/backend but a DIFFERENT workload scale: not a baseline
        ledger.build_entry(
            _record(backend="cpu-fallback", partition_scale=9), kind="bench"
        ),
    ]
    latest = ledger.build_entry(
        _record(backend="cpu-fallback", partition_scale=17), kind="bench"
    )
    window = ledger.baseline_window(entries, latest, window=5)
    # the two scale-free cpu-fallback bench entries match (absent config
    # keys are compatible); the scale-9 entry does not
    assert len(window) == 2
    assert all(e["backend"] == "cpu-fallback" and e["kind"] == "bench"
               for e in window)
    assert all(
        (e.get("metrics") or {}).get("partition_scale") is None
        for e in window
    )


def test_tools_regress_cli_exit_codes(tmp_path, capsys):
    """Acceptance (ISSUE 8): ``tools regress`` exits nonzero on a
    synthetically injected 2x phase regression and zero on a replayed
    identical entry."""
    from kaminpar_tpu.tools.__main__ import main as tools_main

    path = str(tmp_path / "RUNS.jsonl")
    for _ in range(3):
        ledger.append(ledger.build_entry(_record(), kind="bench"), path)
    # identical replay
    ledger.append(ledger.build_entry(_record(), kind="bench"), path)
    assert tools_main(["regress", "--runs", path]) == 0
    assert "no regressions" in capsys.readouterr().out

    # injected 2x regression in a phase wall
    ledger.append(
        ledger.build_entry(
            _record(phase_walls_s={"partitioning": 220.0,
                                   "lp_bench_fence": 4.0}),
            kind="bench",
        ),
        path,
    )
    assert tools_main(["regress", "--runs", path]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION phase.partitioning_s" in out

    # empty / no-baseline ledgers stay quiet (exit 0)
    empty = str(tmp_path / "EMPTY.jsonl")
    assert tools_main(["regress", "--runs", empty]) == 0
    lone = str(tmp_path / "LONE.jsonl")
    ledger.append(ledger.build_entry(_record(backend="tpu"), kind="bench"), lone)
    assert tools_main(["regress", "--runs", lone]) == 0
    capsys.readouterr()


def test_tools_ledger_cli(tmp_path, capsys):
    from kaminpar_tpu.tools.__main__ import main as tools_main

    path = str(tmp_path / "RUNS.jsonl")
    src = tmp_path / "record.json"
    src.write_text(json.dumps(_record()))
    assert tools_main(["ledger", "append", "--runs", path,
                       "--from-json", str(src), "--kind", "bench"]) == 0
    capsys.readouterr()
    assert tools_main(["ledger", "show", "--runs", path]) == 0
    out = capsys.readouterr().out
    assert "bench" in out and "partition_wall_s=120.0" in out
    assert tools_main(["ledger", "tail", "--runs", path, "-n", "1"]) == 0
    tail_out = capsys.readouterr().out
    assert json.loads(tail_out)["kind"] == "bench"
    # missing --from-json is an error, empty ledger is not
    assert tools_main(["ledger", "append", "--runs", path]) == 1
    capsys.readouterr()
    assert tools_main(["ledger", "show", "--runs",
                       str(tmp_path / "NONE.jsonl")]) == 0
    assert "no ledger entries" in capsys.readouterr().out


def test_record_run_kill_switch(tmp_path, monkeypatch):
    path = str(tmp_path / "RUNS.jsonl")
    monkeypatch.setenv("KPTPU_LEDGER", "0")
    assert ledger.record_run(_record(), kind="bench", path=path) is None
    assert ledger.read(path) == []
    monkeypatch.setenv("KPTPU_LEDGER", "1")
    assert ledger.record_run(_record(), kind="bench", path=path) == path
    assert len(ledger.read(path)) == 1


# -- git head resolution (round 20 satellite) --------------------------------


def test_resolve_git_head_fallback_chain(monkeypatch):
    """Env override -> subprocess rev-parse -> ""; cached once resolved,
    and build_entry falls back to it so tier-1/bench entries written with
    no explicit head stop recording git_head=""."""
    monkeypatch.setenv("KPTPU_GIT_HEAD", "feedc0de")
    assert ledger.resolve_git_head(force=True) == "feedc0de"
    # cached: later env changes are invisible without force
    monkeypatch.setenv("KPTPU_GIT_HEAD", "other")
    assert ledger.resolve_git_head() == "feedc0de"
    monkeypatch.delenv("KPTPU_GIT_HEAD")
    head = ledger.resolve_git_head(force=True)
    assert head, "this repo is a git checkout: rev-parse must resolve"
    assert head != "feedc0de"

    entry = ledger.build_entry(_record(), kind="tier1")
    assert entry["git_head"] == head
    # an explicit head (or one carried by the record) still wins
    assert ledger.build_entry(
        _record(), kind="tier1", git_head="abc1234")["git_head"] == "abc1234"
    assert ledger.build_entry(
        _record(git_head="def5678"), kind="tier1")["git_head"] == "def5678"


# -- ledger analytics (round 20 tentpole c) ----------------------------------


def _series(n=6, regress_last=False):
    """n chronological same-workload entries; optionally the last one
    carries an injected 2.5x wall regression living in one phase."""
    entries = []
    for i in range(n):
        bad = regress_last and i == n - 1
        entries.append(ledger.build_entry(_record(
            partition_wall_s=300.0 if bad else 120.0,
            phase_walls_s={"partitioning": 290.0 if bad else 110.0,
                           "lp_bench_fence": 4.0},
        ), kind="bench"))
    return entries


def test_metric_trends_verdicts():
    trends = ledger.metric_trends(_series(regress_last=True))
    wall = trends["partition_wall_s"]
    assert wall["n"] == 6
    assert wall["prior_median"] == 120.0 and wall["last"] == 300.0
    assert wall["verdict"] == "regressed"
    assert trends["phase.partitioning_s"]["verdict"] == "regressed"
    assert trends["partition_cut"]["verdict"] == "flat"
    # an improving higher-better metric reads as improved
    up = [ledger.build_entry(_record(value=1e6), kind="bench")
          for _ in range(3)]
    up.append(ledger.build_entry(_record(value=2e6), kind="bench"))
    assert ledger.metric_trends(up)["value"]["verdict"] == "improved"
    # single-entry groups have no trend
    assert ledger.metric_trends(_series(n=1)) == {}


def test_attribute_names_co_moving_phase():
    entries = _series(regress_last=True)
    latest, base = entries[-1], entries[:-1]
    regs = ledger.compare(latest, base)
    assert any(r["metric"] == "partition_wall_s" for r in regs)
    attr = {a["metric"]: a["suspects"]
            for a in ledger.attribute(latest, base, regs)}
    suspects = [s["metric"] for s in attr["partition_wall_s"]]
    assert "phase.partitioning_s" in suspects
    # the stable phase is NOT a suspect (below the movement floor)
    assert "phase.lp_bench_fence_s" not in suspects
    top = attr["partition_wall_s"][0]
    assert top["metric"] == "phase.partitioning_s"
    assert top["latest"] == 290.0 and top["baseline_median"] == 110.0
    # a quiet series produces no attribution at all
    assert ledger.attribute(_series()[-1], _series()[:-1]) == []


def test_build_report_groups_and_markdown(tmp_path):
    path = str(tmp_path / "RUNS.jsonl")
    for entry in _series(regress_last=True):
        ledger.append(entry, path)
    # a second, quiet group with a different kind
    for _ in range(3):
        ledger.append(ledger.build_entry(_record(), kind="serve"), path)

    rep = ledger.build_report(path=path)
    assert rep["summary"]["entries"] == 9
    assert rep["summary"]["groups"] == 2
    assert rep["summary"]["regressed_groups"] == 1
    assert rep["summary"]["total_regressions"] >= 1
    bench_row = next(r for r in rep["groups"] if r["kind"] == "bench")
    assert bench_row["regressions"] and bench_row["attribution"]
    serve_row = next(r for r in rep["groups"] if r["kind"] == "serve")
    assert not serve_row["regressions"]

    md = ledger.render_report_markdown(rep)
    assert "# Ledger report" in md
    assert "## bench" in md and "## serve" in md
    assert "partition_wall_s" in md
    assert "suspect phase.partitioning_s" in md

    # kind filter narrows the report
    only = ledger.build_report(path=path, kinds=["serve"])
    assert only["summary"]["groups"] == 1
    assert only["groups"][0]["kind"] == "serve"


def test_tools_report_cli_and_regress_summary(tmp_path, capsys):
    """Acceptance (ISSUE 20c): ``tools report`` renders the ledger
    jax-free, attributes the injected regression fixture, and its
    summary keys ride the ``tools regress`` sentinel."""
    from kaminpar_tpu.tools.__main__ import main as tools_main

    path = str(tmp_path / "RUNS.jsonl")
    for entry in _series(regress_last=True):
        ledger.append(entry, path)

    assert tools_main(["report", "--runs", path]) == 0
    md = capsys.readouterr().out
    assert "# Ledger report" in md
    assert "suspect phase.partitioning_s" in md

    assert tools_main(["report", "--runs", path, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["summary"]["regressed_groups"] == 1
    suspects = [s["metric"]
                for a in rep["groups"][0]["attribution"]
                for s in a["suspects"]]
    assert "phase.partitioning_s" in suspects

    out = tmp_path / "report.md"
    assert tools_main(["report", "--runs", path, "--out", str(out)]) == 0
    capsys.readouterr()
    assert "suspect phase.partitioning_s" in out.read_text()

    # a missing ledger is a typed failure
    assert tools_main(["report", "--runs",
                       str(tmp_path / "NONE.jsonl")]) == 2
    capsys.readouterr()

    # the regress sentinel carries the report summary keys
    assert tools_main(["regress", "--runs", path, "--json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["regressions"]
    summ = payload["report_summary"]
    assert summ["groups"] == 1 and summ["regressed_groups"] == 1
    assert summ["trend_regressed_metrics"] >= 1
