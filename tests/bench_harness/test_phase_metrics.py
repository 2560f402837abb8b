"""Tests of what the benchmark reads of the program's own tracing, on the
CPU: the phase reduction of a trace (``perfbench/phase_trace.py``), the
readers of the compile ledger and the LP round counter, and the ledger
against the harness's own count of the warm-up's compile events."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from perfbench import phase_trace, trace_reduce
from perfbench.metrics import (
    lp_rounds,
    setup_compile_s,
    setup_load_s,
    setup_programs_compiled,
    setup_trace_s,
)
from perfbench.run import Partition, Run
from perfbench.spec import ROOT

BIG_SEED = 2**31 + 54321

# Window 0..20 us.  Host line "python": bench/partition 0-20,
# bench/compute_partition 1-19, kp/partitioning 2-18 holding the phases
# kp/coarsening 2-8 (with kp/lp_clustering 3-5 inside), kp/initial_partitioning
# 8-12 and kp/lp_refinement 12-16.  Line "worker": kp/ip_pool 9-11, a span
# with no kp/partitioning around it on its line.  Device programs: 0-1,
# 3-5 and 4-5 (overlapping), 7-10, 13-14, 17-19.
_PHASE_TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 13000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 17000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_lp_iterate_bucketed(11)" } }
  event_metadata { key: 2 value { id: 2 name: "jit__pool_kernel(12)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 18000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 0 }
    events { metadata_id: 4 offset_ps: 20000000 duration_ps: 0 }
    events { metadata_id: 5 offset_ps: 2000000 duration_ps: 16000000 }
    events { metadata_id: 6 offset_ps: 2000000 duration_ps: 6000000 }
    events { metadata_id: 7 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 8 offset_ps: 8000000 duration_ps: 4000000 }
    events { metadata_id: 9 offset_ps: 12000000 duration_ps: 4000000 }
  }
  lines { id: 2 name: "worker" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 9000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/partition" } }
  event_metadata { key: 2 value { id: 2 name: "bench/compute_partition" } }
  event_metadata { key: 3 value { id: 3 name: "bench/trace_start" } }
  event_metadata { key: 4 value { id: 4 name: "bench/trace_end" } }
  event_metadata { key: 5 value { id: 5 name: "kp/partitioning" } }
  event_metadata { key: 6 value { id: 6 name: "kp/coarsening" } }
  event_metadata { key: 7 value { id: 7 name: "kp/lp_clustering" } }
  event_metadata { key: 8 value { id: 8 name: "kp/initial_partitioning" } }
  event_metadata { key: 9 value { id: 9 name: "kp/lp_refinement" } }
  event_metadata { key: 10 value { id: 10 name: "kp/ip_pool" } }
}
"""


def _phases(text=_PHASE_TRACE):
    from jax.profiler import ProfileData

    return phase_trace.phase_profile(ProfileData.from_text_proto(text))


def test_gaps_are_named_by_the_innermost_span_of_either_family():
    got = _phases()
    assert [name for name, _ in got["gaps"]] == [
        "kp/initial_partitioning",  # 10-13
        "kp/lp_refinement",  # 14-17
        "kp/coarsening",  # 1-3: bench/compute_partition holds kp/coarsening
        "kp/coarsening",  # 5-7, after kp/lp_clustering closed
        "bench/partition",  # 19-20
    ]
    assert [s for _, s in got["gaps"]] == pytest.approx([3e-6, 3e-6, 2e-6, 2e-6, 1e-6])


def test_phase_busy_is_the_device_union_inside_each_phase():
    got = _phases()
    # coarsening 3-5 (overlap counted once) and 7-8; initial partitioning
    # 8-10; refinement 13-14.  kp/lp_clustering sits inside a phase and
    # kp/ip_pool on a line with no partition span: neither is a phase.
    assert got["phase_busy"] == pytest.approx({
        "kp/coarsening": 3e-6,
        "kp/initial_partitioning": 2e-6,
        "kp/lp_refinement": 1e-6,
    })
    assert got["busy_s"] == pytest.approx(9e-6)


def test_device_time_outside_every_phase_stays_unattributed():
    from jax.profiler import ProfileData

    got = _phases()
    # 0-1 before kp/partitioning, 17-18 inside it between phases, 18-19 after.
    assert got["unattributed_s"] == pytest.approx(3e-6)
    assert got["events"] == {"/device:TPU:0": {"XLA Modules": 6}}
    profile = ProfileData.from_text_proto(_PHASE_TRACE)
    assert got["busy_s"] == pytest.approx(trace_reduce.reduce_profile(profile).busy_s)


def test_the_recorded_chip_trace_has_no_phases_and_keeps_its_gap_names():
    got = phase_trace.phase_file(
        ROOT / "perfbench" / "testdata" / "v5e_rmat10_k4.xplane.pb"
    )
    assert got["phase_busy"] == {}
    assert got["unattributed_s"] == pytest.approx(got["busy_s"])
    assert got["busy_s"] == pytest.approx(0.544660608, rel=1e-3)
    assert len(got["gaps"]) == 10
    assert all(name == "bench/compute_partition" for name, _ in got["gaps"])
    assert got["gaps"][0][1] == pytest.approx(0.0272, abs=1e-4)
    (lines,) = got["events"].values()
    assert list(lines) == ["XLA Modules"] and lines["XLA Modules"] > 0


def test_the_reduction_needs_the_window_markers():
    text = _PHASE_TRACE.replace('"bench/trace_start"', '"other"')
    with pytest.raises(ValueError, match="bench/trace_start"):
        _phases(text)


# -- metric readers on a hand-built run ---------------------------------------

_LEDGER = [
    {"program": "_pool_kernel", "phase": "initial_partitioning", "trace_s": 20.0,
     "lower_s": 8.0, "compile_s": 0.0, "compiles": 0, "load_s": 8.0, "loads": 1},
    {"program": "lp_iterate_bucketed", "phase": "lp_clustering", "trace_s": 1.5,
     "lower_s": 0.5, "compile_s": 17.0, "compiles": 2, "load_s": 0.0, "loads": 0},
    {"program": "lp_iterate_bucketed", "phase": "lp_refinement", "trace_s": 0.5,
     "lower_s": 0.25, "compile_s": 5.0, "compiles": 1, "load_s": 1.0, "loads": 1},
]


def _run(partitions=2, warm_up=True):
    return Run(cell=None, seed=1, n=32768, edges=441000, setup_s=240.0,
               window_s=10.0, partitions=[Partition(5.0) for _ in range(partitions)],
               peak_bytes=None, warmup={"wall_s": 200.0} if warm_up else {})


@pytest.fixture
def ledger(monkeypatch):
    from kaminpar_tpu.utils import compile_stats

    monkeypatch.setattr(compile_stats, "compile_ledger_snapshot",
                        lambda: [dict(r) for r in _LEDGER])


def test_setup_trace_s_reads_tracing_and_lowering(ledger):
    assert setup_trace_s.read(_run()) == pytest.approx(30.75)


def test_setup_compile_s_reads_the_cache_misses(ledger):
    assert setup_compile_s.read(_run()) == pytest.approx(22.0)


def test_setup_load_s_reads_the_cache_hits(ledger):
    assert setup_load_s.read(_run()) == pytest.approx(9.0)


def test_setup_programs_compiled_counts_and_lists_the_top_programs(ledger, capsys):
    assert setup_programs_compiled.read(_run()) == 3
    line = capsys.readouterr().out.strip()
    assert line.startswith("setup-programs: ")
    top = json.loads(line[len("setup-programs: "):])
    assert top == [
        ["_pool_kernel", 20.0, 8.0, 0.0, 8.0, 0, 1],
        ["lp_iterate_bucketed", 2.0, 0.75, 22.0, 1.0, 3, 1],
    ]


def test_lp_rounds_divides_the_counter_over_every_partition(monkeypatch):
    from kaminpar_tpu.ops import lp

    monkeypatch.setattr(lp, "lp_stats_snapshot", lambda: {"calls": 30, "rounds": 96})
    assert lp_rounds.read(_run(partitions=2)) == 32.0  # the warm-up and two
    assert lp_rounds.read(_run(partitions=3, warm_up=False)) == 32.0


@pytest.mark.parametrize("reader", [setup_trace_s, setup_compile_s, setup_load_s,
                                    setup_programs_compiled])
def test_a_program_without_a_ledger_reads_nothing(monkeypatch, reader):
    from kaminpar_tpu.utils import compile_stats

    monkeypatch.delattr(compile_stats, "compile_ledger_snapshot")
    assert reader.read(_run()) is None


def test_a_program_without_the_round_counter_reads_nothing(monkeypatch):
    from kaminpar_tpu.ops import lp

    monkeypatch.delattr(lp, "lp_stats_snapshot")
    assert lp_rounds.read(_run()) is None


# -- the ledger against the harness's own count -------------------------------

# Runs the harness in a child process, so that the test worker does not keep
# the partition's compiled programs.
_WARM_UP_SCRIPT = """
import json, sys
from pathlib import Path

from kaminpar_tpu.utils import compile_stats
from perfbench import run as bench_run
from perfbench.spec import load_cell

FIELDS = ("trace_s", "lower_s", "compile_s", "compiles", "load_s", "loads")


def totals():
    rows = compile_stats.compile_ledger_snapshot()
    return {f: sum(r[f] for r in rows) for f in FIELDS}


marks = []
timed = bench_run._timed_partition


def marked(*args, **kwargs):
    marks.append(totals())
    out = timed(*args, **kwargs)
    marks.append(totals())
    return out


bench_run._timed_partition = marked
cell = load_cell("rmat-k2", Path(sys.argv[1]))
devices = bench_run.chip_devices(1, allow_cpu=True)
run = bench_run.run_cell(cell, int(sys.argv[2]), 0.1, False, devices)
print(json.dumps({"marks": marks, "warmup": run.warmup,
                  "window_compiles": sum(p.compiles for p in run.partitions)}))
"""


def test_the_ledger_counts_the_warm_up_as_the_harness_does(tmp_path):
    """Over the warm-up partition the ledger's compiles are the harness's
    compile events less its cache hits, and its loads are the hits; the
    window adds nothing to the ledger."""
    from test_perfbench import _tiny_root

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    proc = subprocess.run(
        [sys.executable, "-c", _WARM_UP_SCRIPT, str(_tiny_root(tmp_path)),
         str(BIG_SEED)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    before, after_warm_up, end = got["marks"][0], got["marks"][1], got["marks"][-1]
    warm = got["warmup"]
    assert after_warm_up["compiles"] - before["compiles"] == (
        warm["compile_events"] - warm["cache_hits"])
    assert after_warm_up["loads"] - before["loads"] == warm["cache_hits"]
    assert got["window_compiles"] == 0
    assert end == after_warm_up
