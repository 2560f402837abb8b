"""Tests of the chip benchmark (``perfbench/``) on the CPU at tiny sizes:
the generators and the NumPy reference on their own, the trace reduction,
the harness's result line and refusal, cells found by name from new files,
and ``correct`` coming out false when the partition path is broken."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from perfbench import reference, trace_reduce
from perfbench.families import rmat_g500
from perfbench.peaks import peaks
from perfbench.spec import ROOT, SpecError, load_cell

RMAT = {"scale": 10, "edge_factor": 16, "A": 0.57, "B": 0.19, "C": 0.19}
BIG_SEED = 2**31 + 12345


def _edge_set(row_ptr, col_idx):
    src = np.repeat(np.arange(len(row_ptr) - 1), np.diff(row_ptr))
    return set(zip(src.tolist(), np.asarray(col_idx).tolist()))


def _assert_simple_symmetric(row_ptr, col_idx):
    edges = _edge_set(row_ptr, col_idx)
    assert len(edges) == len(col_idx), "duplicate edges"
    assert all(u != v for u, v in edges), "self-loop"
    assert all((v, u) in edges for u, v in edges), "not symmetric"
    for u in range(len(row_ptr) - 1):
        row = col_idx[row_ptr[u]:row_ptr[u + 1]]
        assert np.all(np.diff(row) > 0), "row not sorted"


# -- generators ---------------------------------------------------------------


@pytest.mark.parametrize("family,params", [
    (rmat_g500, RMAT),
    (rmat_g500, dict(RMAT, scale=9, edge_factor=8)),
])
def test_generator_same_seed_same_graph_and_simple(family, params):
    a = family.generate(params, BIG_SEED)
    b = family.generate(params, BIG_SEED)
    c = family.generate(params, BIG_SEED + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    _assert_simple_symmetric(*a)


def test_rmat_size_and_skew():
    row_ptr, col_idx = rmat_g500.generate(RMAT, 7)
    n = 1 << RMAT["scale"]
    assert len(row_ptr) == n + 1
    assert len(col_idx) // 2 <= RMAT["edge_factor"] * n
    deg = np.diff(row_ptr)
    assert deg.max() > 10 * deg.mean()  # power-law heavy rows


# -- reference ----------------------------------------------------------------


def _graph_and_partition(k=4):
    row_ptr, col_idx = rmat_g500.generate(RMAT, 1)
    n = len(row_ptr) - 1
    part = np.arange(n) % k
    return row_ptr, col_idx, part


def test_reference_cut_matches_brute_force():
    row_ptr, col_idx, part = _graph_and_partition()
    brute = sum(part[u] != part[v] for u, v in _edge_set(row_ptr, col_idx)) // 2
    got = reference.check(row_ptr, col_idx, part, brute, 4, 0.03)
    assert got["cut"] == brute and got["cut_mismatch"] == 0
    assert got["max_block_weight"] <= reference.max_block_weight(len(part), 4, 0.03)
    assert got["empty_blocks"] == 0


def test_reference_rejects_wrong_cut_and_infeasible_partitions():
    row_ptr, col_idx, part = _graph_and_partition()
    n, k = len(part), 4
    cut = reference.edge_cut(row_ptr, col_idx, part)
    assert reference.check(row_ptr, col_idx, part, cut + 1, k, 0.03)["cut_mismatch"] == 1
    heavy = part.copy()
    heavy[: n // 2] = 0
    got = reference.check(row_ptr, col_idx, heavy, None, k, 0.03)
    assert got["max_block_weight"] > reference.max_block_weight(n, k, 0.03)
    empty = np.where(part == 3, 0, part)
    assert reference.check(row_ptr, col_idx, empty, None, k, 0.03)["empty_blocks"] == 1
    assert not reference.check(row_ptr, col_idx, part[: n // 2], cut, k, 0.03)["valid"]
    assert not reference.check(row_ptr, col_idx, part + k, cut, k, 0.03)["valid"]


def test_reference_balance_limit_is_kaminpars():
    # ceil(65536 / 16) = 4096; floor(1.03 * 4096) = 4218
    assert reference.max_block_weight(65536, 16, 0.03) == 4218
    # tiny blocks: the one-node slack wins
    assert reference.max_block_weight(10, 4, 0.03) == 4


# -- trace reduction and peaks ------------------------------------------------

_TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_lp_iterate_bucketed(11)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_contract(12)" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.1" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.2" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 2500000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 0 }
    events { metadata_id: 4 offset_ps: 10000000 duration_ps: 0 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/partition" } }
  event_metadata { key: 2 value { id: 2 name: "bench/compute_partition" } }
  event_metadata { key: 3 value { id: 3 name: "bench/trace_start" } }
  event_metadata { key: 4 value { id: 4 name: "bench/trace_end" } }
}
"""


def test_trace_reduce_on_a_written_trace():
    from jax.profiler import ProfileData

    got = trace_reduce.reduce_profile(ProfileData.from_text_proto(_TRACE))
    # window 0..10 us; programs run 1..4 and 7..8 us
    assert got.window_s == pytest.approx(10e-6)
    assert got.busy_s == pytest.approx(4e-6)
    assert got.idle_frac == pytest.approx(0.6)
    assert got.programs == pytest.approx(
        {"jit_lp_iterate_bucketed": 3e-6, "jit_contract": 1e-6}
    )
    # gaps 4..7 (inside compute_partition), 8..10 and 0..1 (partition only)
    assert [g[0] for g in got.gaps] == [
        "bench/compute_partition", "bench/partition", "bench/partition"
    ]
    assert [g[1] for g in got.gaps] == pytest.approx([3e-6, 2e-6, 1e-6])


def test_trace_reduce_on_a_recorded_chip_trace():
    """``testdata/v5e_rmat10_k4.xplane.pb``: the trace of one partition
    (rmat scale 10, k=4) on a TPU v5 lite, cut to the device's ``XLA
    Modules`` line and the harness's ``bench/`` spans, with the window
    markers put at the ends of its ``bench/partition`` span (it was traced
    whole).  Its full trace had 457,625 op events; their union gave
    0.544660608 s busy."""
    got = trace_reduce.reduce_file(
        ROOT / "perfbench" / "testdata" / "v5e_rmat10_k4.xplane.pb"
    )
    assert got.devices == 1
    assert got.window_s == pytest.approx(0.752729525)
    assert got.busy_s == pytest.approx(0.544660608, rel=1e-3)
    assert got.idle_frac == pytest.approx(0.2762, abs=1e-3)
    assert next(iter(got.programs)) == "jit__pool_kernel"
    assert sum(got.programs.values()) == pytest.approx(got.busy_s, rel=1e-3)
    assert len(got.gaps) == 10
    assert all(name == "bench/compute_partition" for name, _ in got.gaps)
    assert got.gaps[0][1] == pytest.approx(0.0272, abs=1e-4)


def test_trace_reduce_needs_the_window_markers():
    from jax.profiler import ProfileData

    text = _TRACE.replace('"bench/trace_end"', '"other"')
    with pytest.raises(ValueError, match="bench/trace_end"):
        trace_reduce.reduce_profile(ProfileData.from_text_proto(text))


def test_peaks_are_keyed_by_device_kind():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")


# -- harness ------------------------------------------------------------------


def _tiny_root(tmp_path: Path) -> Path:
    """A checkout of the benchmark alone with its configurations cut to a
    size the CPU partitions in seconds."""
    root = tmp_path / "bench_root"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    path = root / "perfbench" / "configs" / "rmat-g500.json"
    conf = json.loads(path.read_text())
    conf["params"]["scale"] = 10
    path.write_text(json.dumps(conf))
    return root


def _cli(root: Path, *args):
    env = dict(os.environ, PYTHONPATH=str(ROOT))
    return subprocess.run(
        [sys.executable, "-m", "perfbench.run", *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )


@pytest.mark.parametrize("trace", [0, 1])
def test_cli_prints_a_well_formed_result_line_on_the_cpu(tmp_path, trace):
    root = _tiny_root(tmp_path)
    proc = _cli(root, "--workload", "rmat-k2", "--seed", str(BIG_SEED),
                "--seconds", "0.1", "--trace", str(trace), "--allow-cpu")
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device",
                         "checks"]
    assert out["correct"] is True and out["attempted"] >= 1 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == 1
    if trace:
        # The CPU writes no device plane, so the device metrics are absent.
        assert {"initial_partitioning_s", "refinement_s",
                "ip_pool_calls", "blocking_syncs", "window_compiles"} <= set(out["metrics"])
        assert out["metrics"]["window_compiles"]["value"] == 0
    else:
        assert {"partition_s", "cut_frac", "setup_s"} <= set(out["metrics"])
        assert all(m["value"] > 0 for m in out["metrics"].values())
    assert all(m["unit"] for m in out["metrics"].values())
    assert set(out["checks"]) == {
        "bad_partitions", "cut_mismatch", "max_block_weight", "empty_blocks"
    }
    last = proc.stderr.strip().splitlines()[-4:]
    assert all(line.startswith("check ") and "limit=" in line for line in last)


def test_cli_refuses_without_a_tpu(tmp_path):
    root = _tiny_root(tmp_path)
    proc = _cli(root, "--workload", "rmat-k2", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_cli_refuses_an_unknown_cell_and_a_bare_directory(tmp_path):
    root = _tiny_root(tmp_path)
    proc = _cli(root, "--workload", "nope", "--seed", "1", "--seconds", "1",
                "--allow-cpu")
    assert proc.returncode == 2 and proc.stdout.strip() == ""
    # Without the program beside it, the harness cannot run at all.
    bare = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", "rmat-k2",
         "--seed", "1", "--seconds", "1", "--allow-cpu"],
        cwd=root, env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        capture_output=True, text=True, timeout=300,
    )
    assert bare.returncode != 0 and not bare.stdout.strip().endswith("}")


_NEW_METRIC = '''"""Nodes of the graph, a metric added as a file."""


def read(run):
    return float(run.n)
'''

_NEW_FAMILY = '''"""A ring with chords to the node ``stride`` ahead, a family added as a file."""

import numpy as np

from perfbench.families.csr import undirected_csr


def generate(params, seed):
    n = int(params["n"])
    order = np.random.default_rng(seed).permutation(n)
    u = np.concatenate([order, order])
    v = np.concatenate([np.roll(order, 1), np.roll(order, int(params["stride"]))])
    return undirected_csr(n, u, v)
'''


def test_new_config_traffic_cell_and_metric_are_found_by_name(tmp_path):
    """A graph family, a configuration, a traffic mix, a cell and a metric
    added as new files and BENCHMARK.json entries run with no code edited."""
    from perfbench.run import chip_devices, result, run_cell

    root = _tiny_root(tmp_path)
    bench_dir = root / "perfbench"
    (bench_dir / "families" / "ring.py").write_text(_NEW_FAMILY)
    (bench_dir / "configs" / "ring-tiny.json").write_text(json.dumps({
        "name": "ring-tiny", "family": "ring", "params": {"n": 1024, "stride": 37},
    }))
    traffic = json.loads((bench_dir / "traffic" / "closed-k2.json").read_text())
    traffic["k"] = 8
    (bench_dir / "traffic" / "closed-k8.json").write_text(json.dumps(traffic))
    (bench_dir / "metrics" / "graph_nodes.py").write_text(_NEW_METRIC)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "ring-tiny", "source": "test",
                            "file": "perfbench/configs/ring-tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "ring-k8", "config": "ring-tiny",
                              "traffic": "closed-k8", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "graph_nodes", "unit": "nodes",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock", "workloads": ["ring-k8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = load_cell("ring-k8", root)
    assert cell.traffic["k"] == 8 and cell.config["params"]["n"] == 1024
    assert "graph_nodes" in [m.name for m in cell.end_to_end]
    assert "graph_nodes" not in [m.name for m in load_cell("rmat-k2", root).end_to_end]
    devices = chip_devices(1, allow_cpu=True)
    run = run_cell(cell, BIG_SEED, 0.1, False, devices)
    out = result(run, False, devices)
    assert out["correct"] is True
    assert out["metrics"]["graph_nodes"] == {"value": 1024.0, "unit": "nodes"}
    with pytest.raises(SpecError, match="no workload named"):
        load_cell("ring-k9", root)


_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_follows_its_rules_and_names_files_that_exist():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert list(spec) == ["command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"]
    end_to_end = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in end_to_end
    assert all(0.01 <= m["bound"] <= 0.25 for m in end_to_end.values())
    cells = {w["name"]: w for w in spec["workloads"]}
    configs = {c["name"]: c for c in spec["configs"]}
    assert {w["config"] for w in cells.values()} == set(configs)
    for conf in configs.values():
        assert (ROOT / conf["file"]).is_file()
        assert conf["file"].startswith(tuple(p + "/" for p in spec["paths"]))
    for name, cell in cells.items():
        assert cell["chips"] in (1, 4)
        assert (ROOT / "perfbench" / "traffic" / f"{cell['traffic']}.json").is_file()
        assert load_cell(name).end_to_end
    for metric in spec["end_to_end"] + spec["per_layer"]:
        assert _NAME.match(metric["name"]) and metric["better"] in ("lower", "higher")
        assert (ROOT / "perfbench" / "metrics" / f"{metric['name']}.py").is_file()
        assert set(metric.get("workloads", cells)) <= set(cells)
    for metric in spec["per_layer"]:
        assert metric["moves"] in end_to_end
    for name in list(cells) + list(configs):
        assert _NAME.match(name)


def test_configure_jax_keeps_an_unbounded_cache_in_the_checkout():
    """Whatever size limit the environment sets: a limited cache evicts and
    locks, and what it drops compiles again in a later run."""
    code = ("from perfbench.run import configure_jax; import json; j = configure_jax(); "
            "print(json.dumps([j.config.jax_compilation_cache_dir, "
            "j.config.jax_compilation_cache_max_size, "
            "j.config.jax_persistent_cache_min_compile_time_secs]))")
    env = dict(os.environ, PYTHONPATH=str(ROOT), JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_MAX_SIZE="1000000",
               JAX_COMPILATION_CACHE_DIR=str(ROOT / "elsewhere"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    cache_dir, max_size, min_s = json.loads(proc.stdout.strip().splitlines()[-1])
    assert cache_dir == str(ROOT / ".xla_cache")
    assert max_size == -1 and min_s == 0


def _break_partitions(monkeypatch, how):
    import kaminpar_tpu as kp

    real = kp.KaMinPar.compute_partition

    def broken(self, k, *args, **kwargs):
        part = np.array(real(self, k, *args, **kwargs))
        if how == "state_unchanged":  # every node left in its first block
            return np.zeros_like(part)
        if how == "half_left_out":
            return part[: len(part) // 2]
        if how == "answer_altered":  # one node moved after the cut was reported
            row_ptr = np.asarray(self.graph.row_ptr)
            col_idx = np.asarray(self.graph.col_idx)
            for v in np.argsort(-np.diff(row_ptr)):
                nbrs = part[col_idx[row_ptr[v]:row_ptr[v + 1]]]
                target = (part[v] + 1) % k
                if np.sum(nbrs == part[v]) != np.sum(nbrs == target):
                    part[v] = target
                    return part
        raise AssertionError(how)

    monkeypatch.setattr(kp.KaMinPar, "compute_partition", broken)


@pytest.mark.parametrize("how", ["state_unchanged", "half_left_out", "answer_altered"])
def test_a_broken_partition_path_is_not_correct(tmp_path, monkeypatch, how):
    from perfbench.run import chip_devices, result, run_cell

    cell = load_cell("rmat-k2", _tiny_root(tmp_path))
    devices = chip_devices(1, allow_cpu=True)
    _break_partitions(monkeypatch, how)
    out = result(run_cell(cell, BIG_SEED, 0.1, False, devices), False, devices)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] >= 1


def test_control_looser_balance_is_not_correct(tmp_path):
    """The control: the program asked for eps = 0.10 where the cell states
    0.03 breaks the stated balance."""
    from perfbench.run import chip_devices, result, run_cell

    cell = load_cell("rmat-k2", _tiny_root(tmp_path))
    devices = chip_devices(1, allow_cpu=True)
    run = run_cell(cell, BIG_SEED, 0.1, False, devices, epsilon=0.10)
    out = result(run, False, devices)
    assert out["correct"] is False
    check = out["checks"]["max_block_weight"]
    assert check["value"] > check["limit"]
