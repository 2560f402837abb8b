"""Test configuration: CPU backend with a virtual 8-device mesh.

Mirrors the reference's KaTestrophe trick (oversubscribed single-machine MPI,
tests/cmake/KaTestrophe.cmake) with the JAX equivalent per SURVEY §4: force 8
host platform devices so distributed logic is tested on one box.  The forcing
recipe lives in ``kaminpar_tpu.utils.platform.force_cpu_devices`` (shared with
``__graft_entry__``); it works even after jax is imported because backends
initialize lazily.  The persistent XLA cache is the package's own
(``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.xla_cache``).
"""

import os
import sys

_repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _repo_root not in sys.path:
    sys.path.insert(0, _repo_root)

from kaminpar_tpu.utils.platform import force_cpu_devices  # noqa: E402

force_cpu_devices(8)

import time  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reseed():
    from kaminpar_tpu.utils import reseed

    reseed(42)
    yield


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop JAX's compiled programs after each test module.  On the CPU each
    compiled program holds memory mappings, and a worker that runs many
    modules otherwise climbs toward the kernel's limit (``vm.max_map_count``,
    65,530 by default), past which XLA crashes while serializing a program
    into the compile cache."""
    yield
    import jax

    jax.clear_caches()


# -- tier-1 wall watch (ISSUE 12 satellite) ----------------------------------
#
# Full suite runs append one kind="tier1" ledger entry (suite wall, pass/
# fail counts, top-20 slowest tests) so `tools regress` catches the creep
# toward the 870 s budget (ROADMAP operational item; PR 8 landed ~13.2 min).
# Gated on a minimum test count so `-k` subset runs never pollute the
# regress baseline window, and on KPTPU_LEDGER like every other writer.

_TIER1_MIN_TESTS = 150
_tier1 = {"t0": time.time(), "durations": [], "passed": 0,
          "failed_ids": set()}


def pytest_runtest_logreport(report):
    # Failures count from EVERY phase (a fixture that breaks during setup
    # must not let the suite log a green tier1 entry), deduped per test so
    # a call failure + teardown error is one failed test, not two.
    if report.failed:
        _tier1["failed_ids"].add(report.nodeid)
        return
    if report.when != "call":
        return
    _tier1["durations"].append((float(report.duration), report.nodeid))
    if report.passed:
        _tier1["passed"] += 1


def pytest_sessionfinish(session, exitstatus):
    failed = len(_tier1["failed_ids"])
    ran = _tier1["passed"] + failed
    if ran < _TIER1_MIN_TESTS or os.environ.get("KPTPU_LEDGER", "1") == "0":
        return
    try:
        from kaminpar_tpu.telemetry import ledger

        slowest = sorted(_tier1["durations"], reverse=True)[:20]
        # Per-module wall rollup (round 18): the fleet suite joined the
        # tier-1 budget — a per-file view catches a single suite creeping
        # toward the 870 s ceiling before the total does.
        module_walls: dict = {}
        for dur, nid in _tier1["durations"]:
            module_walls[nid.split("::")[0]] = (
                module_walls.get(nid.split("::")[0], 0.0) + dur
            )
        top_modules = sorted(
            module_walls.items(), key=lambda kv: kv[1], reverse=True
        )[:10]
        record = {
            "backend": "cpu",
            "tier1_wall_s": round(time.time() - _tier1["t0"], 1),
            "tier1_tests": ran,
            "tier1_failed": failed,
        }
        entry = ledger.build_entry(
            record, kind="tier1",
            extra={
                "slowest": [
                    {"nodeid": nid, "s": round(dur, 2)}
                    for dur, nid in slowest
                ],
                "module_walls": [
                    {"module": mod, "s": round(wall, 1)}
                    for mod, wall in top_modules
                ],
            },
        )
        ledger.append(entry)
    except Exception:  # noqa: BLE001 — the wall watch must never fail a run
        pass
