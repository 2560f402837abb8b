"""The compile ledger (utils/compile_stats.py): every program JAX traces,
lowers, compiles or loads, by program and timer phase."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp

from kaminpar_tpu.utils import compile_stats

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A fresh process with its own persistent cache, so that whether a program
# was compiled or loaded depends on this script alone.
_SCRIPT = """
import json
import jax, jax.numpy as jnp
from kaminpar_tpu.utils import compile_stats
from kaminpar_tpu.utils.timer import scoped_timer

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
x = jnp.arange(8.0)

def rows(name):
    return [r for r in compile_stats.compile_ledger_snapshot() if r["program"] == name]

@jax.jit
def ledger_probe(v):
    return jnp.sin(v) * 3.0 + 1.0

with scoped_timer("coarsening"):
    ledger_probe(x).block_until_ready()
first = rows("ledger_probe")
jax.clear_caches()
with scoped_timer("coarsening"):
    ledger_probe(x).block_until_ready()
print(json.dumps({"first": first, "second": rows("ledger_probe"),
                  "totals": compile_stats.compile_time_snapshot(),
                  "by_phase": compile_stats.compile_by_phase_snapshot()}))
"""


def test_a_compile_then_a_load_by_program_and_phase(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env, cwd=_ROOT,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])

    (first,) = got["first"]
    assert first["phase"] == "coarsening"
    assert (first["compiles"], first["loads"]) == (1, 0)
    assert first["trace_s"] > 0 and first["lower_s"] > 0 and first["compile_s"] > 0
    assert first["load_s"] == 0

    (second,) = got["second"]  # the same row, one load more
    assert (second["compiles"], second["loads"]) == (1, 1)
    assert second["load_s"] > 0
    assert second["compile_s"] == first["compile_s"]
    assert second["trace_s"] > first["trace_s"]  # traced again after clearing

    assert set(got["totals"]) == {"backend_compile_s", "trace_s", "compile_events"}
    assert got["by_phase"]["coarsening"]["events"] >= 2


def test_a_nested_jit_is_traced_within_its_caller():
    @jax.jit
    def ledger_inner(v):
        return v * 2

    @jax.jit
    def ledger_outer(v):
        return ledger_inner(v) + 1

    ledger_outer(jnp.ones(5))
    rows = {r["program"]: r for r in compile_stats.compile_ledger_snapshot()}
    assert rows["ledger_outer"]["trace_s"] > 0
    assert rows["ledger_outer"]["compiles"] + rows["ledger_outer"]["loads"] == 1
    # Traced, lowered and compiled only as part of ledger_outer.
    assert "ledger_inner" not in rows
    assert rows["ledger_outer"]["phase"] == "untracked"


def test_the_snapshots_are_views_over_the_ledger():
    @jax.jit
    def ledger_view_probe(v):
        return v - 7

    ledger_view_probe(jnp.ones(3))
    rows = compile_stats.compile_ledger_snapshot()
    totals = compile_stats.compile_time_snapshot()
    by_phase = compile_stats.compile_by_phase_snapshot()
    events = sum(r["compiles"] + r["loads"] for r in rows)
    assert totals["compile_events"] == events
    assert sum(p["events"] for p in by_phase.values()) == events
    seconds = [r["trace_s"] + r["lower_s"] + r["compile_s"] + r["load_s"] for r in rows]
    assert seconds == sorted(seconds, reverse=True)
