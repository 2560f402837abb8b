"""The LP round counter: ``LPState.rounds`` out of the sweep loops, and the
host counter that keeps them unread until ``lp_stats_snapshot``."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaminpar_tpu.graph import generators
from kaminpar_tpu.ops import lp
from kaminpar_tpu.utils import next_key

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clustering_inputs(graph):
    pv, bv = graph.padded(), graph.bucketed()
    idt = pv.row_ptr.dtype
    labels = jnp.concatenate([
        jnp.arange(pv.n, dtype=idt),
        jnp.full(pv.n_pad - pv.n, pv.anchor, dtype=idt),
    ])
    max_w = jnp.full(pv.n_pad, 12, dtype=pv.node_w.dtype)
    return pv, bv, labels, max_w


@pytest.mark.parametrize("min_moved,max_iterations", [(0, 5), (0, 40), (20, 40)])
def test_rounds_is_where_the_host_loop_stops(min_moved, max_iterations):
    graph = generators.grid2d_graph(16, 16)
    pv, bv, labels, max_w = _clustering_inputs(graph)
    key = next_key()
    args = (bv.buckets, bv.heavy, bv.gather_idx, pv.node_w, max_w)

    out = lp.lp_iterate_bucketed(
        lp.init_state(labels, pv.node_w, pv.n_pad), key, *args,
        jnp.int32(min_moved), jnp.int32(max_iterations), num_labels=pv.n_pad,
    )

    state = lp.init_state(labels, pv.node_w, pv.n_pad)
    moved, rounds = np.iinfo(np.int32).max, 0
    while rounds < max_iterations and moved > min_moved:
        state = lp.lp_round_bucketed(
            state, jax.random.fold_in(key, rounds), *args, num_labels=pv.n_pad
        )
        moved = int(np.asarray(state.num_moved))
        rounds += 1

    assert out.rounds.dtype == jnp.int32
    assert 0 < int(out.rounds) <= max_iterations
    assert int(out.rounds) == rounds
    np.testing.assert_array_equal(np.asarray(out.labels), np.asarray(state.labels))


def test_a_single_round_state_carries_no_rounds():
    graph = generators.grid2d_graph(16, 16)
    pv, bv, labels, max_w = _clustering_inputs(graph)
    state = lp.lp_round_bucketed(
        lp.init_state(labels, pv.node_w, pv.n_pad), next_key(), bv.buckets,
        bv.heavy, bv.gather_idx, pv.node_w, max_w, num_labels=pv.n_pad,
    )
    assert int(state.rounds) == 0


# A partition with the counter and with it stubbed out, in a child process
# so that the worker does not keep the partition's compiled programs.
_NO_PULL_SCRIPT = """
import json
import numpy as np
import kaminpar_tpu as kp
from kaminpar_tpu.graph import generators
from kaminpar_tpu.ops import lp
from kaminpar_tpu.utils import sync_stats

graph = generators.rmat_graph(10, 8, seed=3)


def partition():
    solver = kp.KaMinPar("default")
    solver.set_graph(graph)
    return np.asarray(solver.compute_partition(k=4))


partition()  # compile outside the counted runs
out = {}
for name, note in (("on", lp.note_rounds), ("off", lambda state: state)):
    lp.note_rounds = note
    calls = lp.lp_stats_snapshot()["calls"]
    before = sync_stats.snapshot()
    with sync_stats.tripwire():
        part = partition()
    after = sync_stats.snapshot()
    out[name] = {
        "part": part.tolist(),
        "pulls": after["count"] - before["count"],
        "implicit": after["implicit"] - before["implicit"],
        "calls": lp.lp_stats_snapshot()["calls"] - calls,
    }
print(json.dumps(out))
"""


def test_the_counter_adds_no_pull_to_a_partition():
    """The same partition with the counter and with it stubbed out: the
    same explicit and implicit pulls; the counter is read afterwards."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    proc = subprocess.run([sys.executable, "-c", _NO_PULL_SCRIPT], env=env,
                          cwd=_ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    on, off = got["on"], got["off"]
    assert on["part"] == off["part"]
    assert on["pulls"] == off["pulls"]
    assert on["implicit"] == off["implicit"]
    assert on["calls"] > 0 and off["calls"] == 0


def test_snapshot_sums_the_rounds_of_every_sweep():
    graph = generators.grid2d_graph(16, 16)
    pv, bv, labels, max_w = _clustering_inputs(graph)
    before = lp.lp_stats_snapshot()
    expected = 0
    for iterations in (3, 7):
        out = lp.note_rounds(lp.lp_iterate_bucketed(
            lp.init_state(labels, pv.node_w, pv.n_pad), next_key(),
            bv.buckets, bv.heavy, bv.gather_idx, pv.node_w, max_w,
            jnp.int32(0), jnp.int32(iterations), num_labels=pv.n_pad,
        ))
        expected += int(out.rounds)
    after = lp.lp_stats_snapshot()
    assert after["calls"] - before["calls"] == 2
    assert after["rounds"] - before["rounds"] == expected


def test_a_long_process_folds_finished_counts(monkeypatch):
    monkeypatch.setattr(lp, "_FOLD_AT", 4)
    before = lp.lp_stats_snapshot()
    for _ in range(6):
        lp.note_rounds(lp.LPState(None, None, None, jnp.int32(2)))
    assert len(lp._pending_rounds) < 4
    after = lp.lp_stats_snapshot()
    assert after["rounds"] - before["rounds"] == 12
    assert after["calls"] - before["calls"] == 6
