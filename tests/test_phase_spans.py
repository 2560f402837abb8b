"""The program's phases as host spans on the profiler's clock: every timer
scope emits ``kp/<scope>``, also while the timer is disabled, and each
device pool call emits ``kp/ip_pool``.

The traces are taken in a child process, so that no profiler session runs
inside the test worker, whose later compiles would share its state."""

import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = """
import glob, json, sys
import jax
import numpy as np

import kaminpar_tpu as kp
from jax.profiler import ProfileData
from kaminpar_tpu.context import InitialPartitioningContext
from kaminpar_tpu.graph import generators
from kaminpar_tpu.ops import bipartition as bip
from kaminpar_tpu.partitioning.kway import graph_to_host
from kaminpar_tpu.utils.timer import Timer, scoped_timer

root = sys.argv[1]


def host_spans(trace_dir):
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return sorted({
        ev.name
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("kp/")
    })


out = {}
solver = kp.KaMinPar("default")
solver.set_graph(generators.rmat_graph(9, 8, seed=4))
with jax.profiler.trace(f"{root}/partition"):
    part = np.asarray(solver.compute_partition(k=2))
out["partition"] = {"spans": host_spans(f"{root}/partition"),
                    "n": len(part), "graph_n": solver.graph.n}

timer = Timer.global_()
before = timer.phase_seconds("coarsening")
timer.disable()
try:
    with jax.profiler.trace(f"{root}/disabled"):
        with scoped_timer("coarsening"):
            pass
finally:
    timer.enable()
out["disabled"] = {"spans": host_spans(f"{root}/disabled"),
                   "timed": timer.phase_seconds("coarsening") != before}

host = graph_to_host(generators.grid2d_graph(8, 8))
half = int(0.55 * host.total_node_weight)
with jax.profiler.trace(f"{root}/pool"):
    bip.pool_bipartition_device(
        host.row_ptr, host.col_idx, host.node_w, host.edge_w,
        np.array([half, half]), 5, InitialPartitioningContext(), 2,
    )
out["pool"] = {"spans": host_spans(f"{root}/pool")}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(tmp_path_factory.mktemp("traces"))],
        env=env, cwd=_ROOT, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_a_traced_partition_holds_its_phases_on_a_host_plane(traced):
    got = traced["partition"]
    assert got["n"] == got["graph_n"]
    assert {"kp/partitioning", "kp/initial_partitioning",
            "kp/lp_refinement"} <= set(got["spans"])


def test_a_disabled_timer_still_emits_the_span(traced):
    assert "kp/coarsening" in traced["disabled"]["spans"]
    assert traced["disabled"]["timed"] is False


def test_a_device_pool_call_is_a_span(traced):
    assert "kp/ip_pool" in traced["pool"]["spans"]
