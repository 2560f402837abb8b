"""Blocking host<->device synchronization accounting.

On-silicon profiling (TPU_NOTES.md r5) showed the full-partition wall
dominated by host orchestration: every blocking dispatch round-trip costs
tens of milliseconds over a remote link, and every device->host scalar
pull (``int(n_c)``,
``int(m_c)``, per-round moved counts) serializes the dispatch pipeline.
This module makes the *blocking-transfer count* a first-class,
regression-testable metric, mirroring what :mod:`utils.compile_stats` does
for compiled-shape counts:

- :func:`pull` is the one sanctioned device->host readback primitive: it
  blocks, converts to numpy, and counts one transfer (plus its bytes) per
  array against the current phase.  Orchestration code packs its per-level
  scalars into a single small array so a coarsening level performs exactly
  one ``pull``.
- Phases come from the timer tree: :func:`scoped_timer
  <kaminpar_tpu.utils.timer.scoped_timer>` pushes its scope name as the
  active sync phase, so transfer counts line up with the wall-clock report
  for free.
- :func:`tripwire` patches the jax array scalar-conversion dunders
  (``__int__`` / ``__float__`` / ``__bool__`` / ``item``) to count *implicit*
  pulls — the ``int(x)``-style strays the device-resident spine must not
  contain.  Tests run inside it and assert the implicit count stays zero.
- :func:`guard` additionally arms jax's transfer guard (effective on
  accelerator backends; the CPU backend's zero-copy host arrays never
  trigger it, which is why the tripwire exists).

``bench.py`` embeds :func:`snapshot` in its headline JSON
(``host_sync_count`` + per-phase bytes) and the deep partitioner asserts the
one-readback-per-coarsening-level budget through :func:`phase_count` when
:func:`enable_budget_checks` is armed (single-pipeline test runs; the
counters are process-global, so concurrent replica threads would alias each
other's budgets).
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, Tuple

import numpy as np

from ..telemetry import trace as _ttrace

_lock = threading.Lock()
# phase -> [explicit_count, explicit_bytes, implicit_count, implicit_bytes,
#           lane_pulls, stacked_count, shard_pulls, sharded_count]
# ``lane_pulls`` / ``stacked_count`` (round 11): a lane-stacked readback
# moves L lanes' scalars in ONE blocking transfer; the stacked transfer
# counts once in explicit_count (the budget currency) while lane_pulls
# accumulates L (what the per-graph pipeline would have paid) — the census
# quantifies the readbacks the lane stack amortized away.
# ``shard_pulls`` / ``sharded_count`` (round 13): the mesh analog of the
# lane pair — a readback from a P-shard SPMD computation fans P shards'
# data into ONE blocking transfer (one host program, one gather), where a
# per-rank MPI program would pay P separate device->host reads.  The
# transfer still counts once (budget currency unchanged); shard_pulls
# accumulates P so per-shard-level budgets can be expressed and the
# amortization quantified (shard_pulls - sharded_count = transfers the
# SPMD mesh design saved vs the per-rank layout).
_counts: Dict[str, list] = {}
_tls = threading.local()
_budget_checks = False
_DEFAULT_PHASE = "untracked"
# Cross-thread phase board (round 16, ISSUE 12): thread ident -> (thread
# name, live reference to that thread's phase stack).  The flight
# recorder's heartbeat thread reads it to attribute a hang to the phase
# the process died in; reads race benignly (a torn read sees a stack one
# push/pop off, never a crash).
_phase_board: Dict[int, tuple] = {}


def _phase() -> str:
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else _DEFAULT_PHASE


def active_phase() -> str:
    """This thread's innermost open phase (``"untracked"`` outside any
    scope) — public for the RNG chain's per-phase draw accounting."""
    return _phase()


def push_phase(name: str) -> None:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
        with _lock:
            _phase_board[threading.get_ident()] = (
                threading.current_thread().name or "thread", stack
            )
    stack.append(name)


def pop_phase() -> None:
    stack = getattr(_tls, "stack", None)
    if stack:
        stack.pop()


def current_phases() -> Dict[str, str]:
    """{thread name: innermost open phase} across every thread that ever
    pushed one — the flight recorder's hang-attribution source (threads
    with an empty stack report ""; dead threads linger harmlessly until
    process exit)."""
    with _lock:
        board = list(_phase_board.values())
    out = {}
    for name, stack in board:
        # [-1:] is a single (GIL-atomic) read of the live list — the owning
        # thread may pop between a truthiness check and an index, so the
        # check-then-index idiom would raise here.
        top = stack[-1:]
        out[name] = top[0] if top else ""
    return out


@contextmanager
def scoped(name: str):
    """Attribute transfers inside the block to phase ``name`` (the timer
    tree pushes its scope names through this automatically)."""
    push_phase(name)
    try:
        yield
    finally:
        pop_phase()


def _bump(kind_offset: int, count: int, nbytes: int, phase: str | None = None,
          lanes: int = 0, shards: int = 0) -> None:
    ph = phase or _phase()
    with _lock:
        row = _counts.get(ph)
        if row is None:
            row = _counts[ph] = [0, 0, 0, 0, 0, 0, 0, 0]
        row[kind_offset] += count
        row[kind_offset + 1] += nbytes
        if lanes > 0:
            # Every stacked pull counts, including L=1 (a single-request
            # batch under lane_stack="on" still runs stacked): the census
            # stays consistent with the engine's lanestacked_batches.
            row[4] += lanes * count
            row[5] += count
        if shards > 0:
            # Mesh-wide pull: one transfer services all P shards (even
            # P=1 — a single-shard mesh run stays comparable).
            row[6] += shards * count
            row[7] += count
        total_count = sum(r[0] for r in _counts.values())
        total_bytes = sum(r[1] for r in _counts.values())
        total_implicit = sum(r[2] for r in _counts.values())
    # Telemetry counter sample: the blocking-transfer census as a trace
    # track, one sample per counted transfer (rare by contract — one batched
    # readback per level).
    rec = _ttrace.active()
    if rec is not None:
        rec.counter("host_sync", {
            "count": total_count,
            "bytes": total_bytes,
            "implicit": total_implicit,
        })


def pull(*arrays, phase: str | None = None, lanes: int = 0, shards: int = 0):
    """The sanctioned blocking device->host readback: materialize each array
    on the host, counting one blocking transfer (and its bytes) per array
    against the current phase.  Callers batch their per-level scalars into
    ONE array so one ``pull`` == one transfer.

    ``lanes`` (round 11): mark a *lane-stacked* readback that carries L
    lanes' data in one transfer — the transfer still counts once (budget
    currency unchanged), while the per-lane census records the L logical
    pulls the per-graph pipeline would have paid (``lane_pulls`` /
    ``stacked_count`` in :func:`snapshot`).

    ``shards`` (round 13): mark a *mesh-wide* readback from a P-shard SPMD
    computation — one transfer gathers every shard's slice, where a
    per-rank program would pay P reads.  ``shard_pulls`` accumulates P per
    transfer so :func:`assert_phase_budget` can express per-shard-level
    budgets (pass ``shards=P`` there too).

    Returns a single ndarray for one input, else a tuple of ndarrays.
    """
    import jax

    from ..resilience.faults import maybe_inject

    # Named "readback" injection point (round 17): every counted blocking
    # transfer is a place the device can fail to answer — the chaos
    # harness arms readback-class faults here (disarmed: one flag read).
    maybe_inject("readback", site=phase or _phase())
    out = []
    # The explicit allow makes pull() the sanctioned escape hatch inside
    # guard(): strays raise, batched readbacks pass.
    with jax.transfer_guard_device_to_host("allow"):
        for a in arrays:
            host = np.asarray(a)
            _bump(0, 1, int(host.nbytes), phase, lanes=lanes, shards=shards)
            out.append(host)
    return out[0] if len(out) == 1 else tuple(out)


def record_transfer(nbytes: int, count: int = 1, phase: str | None = None) -> None:
    """Count a blocking transfer performed outside :func:`pull` (host layout
    builders that consume numpy views of device arrays)."""
    _bump(0, count, int(nbytes), phase)


def phase_count(name: str, implicit: bool = False) -> int:
    with _lock:
        row = _counts.get(name)
        if row is None:
            return 0
        return row[2] if implicit else row[0]


def lane_phase_count(name: str) -> Tuple[int, int]:
    """(lane_pulls, stacked_count) of phase ``name`` — the per-lane
    accounting pair of the lane-stacked serve pipeline (round 11)."""
    with _lock:
        row = _counts.get(name)
        if row is None:
            return (0, 0)
        return (row[4], row[5])


def shard_phase_count(name: str) -> Tuple[int, int]:
    """(shard_pulls, sharded_count) of phase ``name`` — the per-shard
    accounting pair of the dist/mesh tier (round 13)."""
    with _lock:
        row = _counts.get(name)
        if row is None:
            return (0, 0)
        return (row[6], row[7])


def snapshot() -> dict:
    """{phase: {count, bytes, implicit, implicit_bytes, lane_pulls,
    stacked_count, shard_pulls, sharded_count}} plus totals.
    ``lane_pulls - stacked_count`` per phase = blocking transfers the lane
    stack amortized away; ``shard_pulls - sharded_count`` = transfers the
    SPMD mesh saved vs a per-rank layout (round 13)."""
    with _lock:
        phases = {
            k: {
                "count": v[0],
                "bytes": v[1],
                "implicit": v[2],
                "implicit_bytes": v[3],
                "lane_pulls": v[4],
                "stacked_count": v[5],
                "shard_pulls": v[6],
                "sharded_count": v[7],
            }
            for k, v in sorted(_counts.items())
        }
    return {
        "phases": phases,
        "count": sum(p["count"] for p in phases.values()),
        "bytes": sum(p["bytes"] for p in phases.values()),
        "implicit": sum(p["implicit"] for p in phases.values()),
        "lane_pulls": sum(p["lane_pulls"] for p in phases.values()),
        "stacked_count": sum(p["stacked_count"] for p in phases.values()),
        "shard_pulls": sum(p["shard_pulls"] for p in phases.values()),
        "sharded_count": sum(p["sharded_count"] for p in phases.values()),
    }


def reset() -> None:
    with _lock:
        _counts.clear()


def enable_budget_checks(on: bool = True) -> None:
    """Arm the in-pipeline budget assertions (deep.py).  Off by default:
    the counters are process-global and concurrent best-of-R replica
    threads would trip each other's budgets."""
    global _budget_checks
    _budget_checks = bool(on)


def budget_checks_enabled() -> bool:
    return _budget_checks


def assert_phase_budget(name: str, budget: int, since: int = 0,
                        shards: int = 0, count_since: int = 0) -> None:
    """Raise when phase ``name`` performed more than ``budget`` blocking
    transfers since the ``since`` snapshot (see :func:`phase_count`).
    No-op unless :func:`enable_budget_checks` armed it.

    With ``shards=P`` (round 13) the budget is expressed *per shard*: the
    check runs in the per-shard currency — ``shard_pulls`` (see
    :func:`shard_phase_count`; ``since`` is then a shard_pulls snapshot)
    must stay within ``budget * P`` — AND in the plain transfer currency
    (``count_since`` is the matching :func:`phase_count` snapshot), so a
    stray pull that forgot its ``shards=`` tag still trips the budget
    instead of hiding from the per-shard ledger.  A mesh-wide pull
    services all P shards in one transfer, so both bounds coincide for
    correctly tagged code; phrasing the budget per shard keeps dist
    budgets comparable across mesh sizes and is the accounting ROADMAP
    item 1's sharded pipeline extends."""
    if not _budget_checks:
        return
    if shards > 0:
        used = shard_phase_count(name)[0] - since
        allowed = budget * shards
        if used > allowed:
            raise AssertionError(
                f"per-shard sync budget exceeded in phase {name!r}: "
                f"{used} logical shard pulls > {budget} per shard x "
                f"{shards} shards = {allowed} (see utils/sync_stats.py)"
            )
        used_count = phase_count(name) - count_since
        if used_count > budget:
            raise AssertionError(
                f"sync budget exceeded in phase {name!r}: {used_count} "
                f"blocking transfers > budget {budget} (includes pulls "
                f"missing their shards= tag; see utils/sync_stats.py)"
            )
        return
    used = phase_count(name) - since
    if used > budget:
        raise AssertionError(
            f"sync budget exceeded in phase {name!r}: {used} blocking "
            f"transfers > budget {budget} (one batched readback per level "
            f"is the contract; see utils/sync_stats.py)"
        )


# ---------------------------------------------------------------------------
# Implicit-sync tripwire: count int()/float()/bool()/.item() on jax arrays.
# ---------------------------------------------------------------------------

_trip_depth = 0
_trip_saved: Dict[str, object] = {}
_TRIP_METHODS: Tuple[str, ...] = ("__int__", "__float__", "__bool__", "item")


def _array_type():
    import jax

    return type(jax.numpy.zeros(0))


def _install_tripwire() -> None:
    cls = _array_type()
    for name in _TRIP_METHODS:
        orig = getattr(cls, name, None)
        if orig is None:  # pragma: no cover - dunder set varies by jaxlib
            continue
        _trip_saved[name] = orig

        def make(orig):
            def patched(self, *args, **kwargs):
                try:
                    _bump(2, 1, int(getattr(self, "nbytes", 0) or 0))
                except Exception:  # noqa: BLE001 - accounting must never break math
                    pass
                return orig(self, *args, **kwargs)

            return patched

        setattr(cls, name, make(orig))


def _uninstall_tripwire() -> None:
    cls = _array_type()
    for name, orig in _trip_saved.items():
        setattr(cls, name, orig)
    _trip_saved.clear()


@contextmanager
def tripwire():
    """Count implicit scalar pulls (``int(x)``/``float(x)``/``bool(x)``/
    ``.item()`` on device arrays) while active.  Nests; test-scoped — the
    patched dunders add a few ns to every jax-array scalar conversion."""
    global _trip_depth
    with _lock:
        _trip_depth += 1
        if _trip_depth == 1:
            _install_tripwire()
    try:
        yield
    finally:
        with _lock:
            _trip_depth -= 1
            if _trip_depth == 0:
                _uninstall_tripwire()


@contextmanager
def guard():
    """Disallow implicit device->host transfers at the jax runtime level.
    Effective on accelerator backends (raises on any transfer not routed
    through an explicit allow); the CPU backend's host-resident arrays never
    trigger it — pair with :func:`tripwire` for CPU CI."""
    import jax

    with jax.transfer_guard_device_to_host("disallow"):
        yield


@contextmanager
def allow_transfers():
    """Escape hatch inside :func:`guard` for a sanctioned pull."""
    import jax

    with jax.transfer_guard_device_to_host("allow"):
        yield
