"""Tools + heap profiler + debug dump tests (reference: apps/tools/,
heap_profiler.h, partitioning/debug.cc)."""

import os
import subprocess
import sys

# Subprocesses run on the CPU backend and import this checkout.
_ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__)))}

import numpy as np
import pytest


@pytest.fixture(scope="module")
def metis_file(tmp_path_factory):
    """Self-generated 1024-node METIS fixture (the reference checkout's
    rgg2d.metis is not available in every container)."""
    from kaminpar_tpu.graph import generators
    from kaminpar_tpu.io.metis import write_metis

    g = generators.rgg2d_graph(1024, seed=1)
    path = tmp_path_factory.mktemp("tools") / "rgg2d.metis"
    write_metis(g, str(path))
    return str(path), int(g.n), int(g.m)


def _run_tool(*args):
    return subprocess.run(
        [sys.executable, "-m", "kaminpar_tpu.tools", *args],
        capture_output=True, text=True, timeout=300, env=_ENV,
    )


def test_graph_properties_tool(metis_file):
    path, n, m = metis_file
    out = _run_tool("graph-properties", path)
    assert out.returncode == 0, out.stderr
    assert f"n: {n}" in out.stdout
    assert f"m: {m // 2}" in out.stdout


def test_partition_properties_tool(metis_file, tmp_path):
    path, n, _ = metis_file
    part = np.zeros(n, dtype=np.int64)
    part[n // 2:] = 1
    pfile = tmp_path / "p.part"
    np.savetxt(pfile, part, fmt="%d")
    out = _run_tool("partition-properties", path, str(pfile))
    assert out.returncode == 0, out.stderr
    assert "k: 2" in out.stdout
    assert "cut:" in out.stdout


def test_connected_components_tool(metis_file):
    out = _run_tool("connected-components", metis_file[0])
    assert out.returncode == 0, out.stderr
    assert "Components:" in out.stdout


def test_rearrange_tool(metis_file, tmp_path):
    out_file = tmp_path / "rearranged.metis"
    out = _run_tool("rearrange", metis_file[0], str(out_file))
    assert out.returncode == 0, out.stderr
    from kaminpar_tpu.io.metis import read_metis

    g = read_metis(str(out_file))
    assert g.n == metis_file[1]


def test_heap_profiler_scopes():
    from kaminpar_tpu.utils.heap_profiler import HeapProfiler, memory_summary

    HeapProfiler.reset(enabled=True)
    with HeapProfiler.scope("outer"):
        with HeapProfiler.scope("inner"):
            import jax.numpy as jnp

            _ = jnp.ones(1000).sum()
    rep = HeapProfiler.report()
    assert "outer" in rep and "inner" in rep
    assert isinstance(memory_summary(), dict)


def test_debug_dumps(tmp_path):
    from kaminpar_tpu.graph import generators
    from kaminpar_tpu.kaminpar import KaMinPar
    from kaminpar_tpu.presets import create_context_by_preset_name

    ctx = create_context_by_preset_name("default")
    ctx.debug.dump_dir = str(tmp_path)
    ctx.debug.graph_name = "t"
    ctx.debug.dump_graph_hierarchy = True
    ctx.debug.dump_partition_hierarchy = True
    ctx.coarsening.contraction_limit = 100  # force >= 1 coarse level
    g = generators.rgg2d_graph(1024, seed=1)
    s = KaMinPar(ctx)
    s.set_graph(g)
    s.compute_partition(k=4)
    dumps = list(tmp_path.iterdir())
    assert any(p.suffix == ".metis" for p in dumps), dumps
    assert any(p.suffix == ".part" for p in dumps), dumps


def test_compression_tool(metis_file):
    out = _run_tool("compression", metis_file[0])
    assert out.returncode == 0, out.stderr
    assert "ratio:" in out.stdout


def test_warmup_tool():
    """`tools warmup` precompiles a (tiny) serving ladder and reports the
    per-bucket compile seconds from compile_stats (ISSUE 3 satellite);
    `--lanes` adds the lane-stacked pipeline cells (ISSUE 6 satellite)."""
    out = _run_tool("warmup", "--ladder", "64", "--ks", "4", "-P", "serve",
                    "--lanes", "2")
    assert out.returncode == 0, out.stderr
    assert "cell n_bucket=" in out.stdout
    assert "lanestack cell" in out.stdout and "lanes=2" in out.stdout
    assert "distinct kernel specializations" in out.stdout


# -- tools trace hardening (round 20 satellite) ------------------------------


def test_tools_trace_typed_error_exit_codes(tmp_path, capsys):
    """Malformed inputs get typed errors, not tracebacks: 2 unreadable
    file, 3 malformed/truncated JSON, 4 span-free capture."""
    import json

    from kaminpar_tpu.tools.__main__ import main as tools_main

    assert tools_main(["trace", str(tmp_path / "nope.json")]) == 2
    assert "cannot read trace" in capsys.readouterr().out

    truncated = tmp_path / "truncated.json"
    truncated.write_text('{"traceEvents": [{"name": "x", "ph"')
    assert tools_main(["trace", str(truncated)]) == 3
    assert "malformed trace JSON" in capsys.readouterr().out

    not_obj = tmp_path / "list.json"
    not_obj.write_text("[1, 2, 3]")
    assert tools_main(["trace", str(not_obj)]) == 3
    capsys.readouterr()

    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"traceEvents": [], "otherData": {}}))
    assert tools_main(["trace", str(empty)]) == 4
    assert "no spans" in capsys.readouterr().out


def test_tools_trace_shards_without_shard_lanes(tmp_path, capsys):
    """Regression guard: ``--shards`` on a valid trace with no shard
    lanes reports their absence and exits 0 (it used to be exercised
    only on mesh traces)."""
    from kaminpar_tpu.telemetry import trace as ttrace
    from kaminpar_tpu.tools.__main__ import main as tools_main

    rec = ttrace.TraceRecorder()
    rec.begin("partitioning")
    rec.end("partitioning")
    path = tmp_path / "single.json"
    rec.write(str(path))
    assert tools_main(["trace", str(path), "--shards"]) == 0
    assert "not a mesh trace" in capsys.readouterr().out
