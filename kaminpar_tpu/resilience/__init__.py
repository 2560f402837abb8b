"""kaminpar_tpu.resilience — the unified resilience layer (ISSUE 13).

Until round 17 every failure mode had a bespoke, partial handler: the
round-11 lanestack latch, the round-9 device-IP-pool fallback, the
round-14/15 compressed-path fallbacks, and the round-16 capacity
preflight each protected one path, while a hung compile, a mid-batch
execute exception, or a poisoned shape cell could still wedge the serve
queue or silently degrade results.  This package centralizes the whole
recovery surface:

- :mod:`errors` — the typed failure hierarchy (CompileTimeout,
  ExecuteFault, CapacityExceeded, BackendUnavailable, PoisonedCell,
  WorkerHung, GraphValidationError) plus :func:`errors.classify`, the ONE
  classifier every pipeline/serve dispatch site routes caught exceptions
  through (enforced statically by the kptlint ``error-discipline`` rule).
- :mod:`faults` — the deterministic fault-injection harness: named
  injection points (compile, execute, readback, queue-admit, warmup)
  armed via ``Context.resilience.fault_plan`` / env ``KPTPU_FAULTS``,
  seed-keyed so chaos runs are replayable.
- :mod:`breakers` — the per-(path, shape-cell) circuit-breaker registry
  (closed → open → half-open) driving the explicit degradation ladder
  (pallas→xla LP, device_decode→dense, lanestack→per-graph, device IP→
  host pool, strong→fast quality): every demotion is counted, warned
  once, surfaced in ``engine.stats()``/Prometheus, and reversible via
  half-open probing after a cooldown.
- :mod:`watchdog` — the execution watchdog: bounds hung
  compiles/executes with a monitor thread that assembles a
  flight-recorder-style dossier (dying phase from the sync-stats phase
  board, every thread's stack via faulthandler) and converts the hang
  into a breaker trip + typed future resolution instead of a killed
  process.

The package is dependency-light by design: :mod:`errors`, :mod:`faults`,
:mod:`breakers`, and :mod:`watchdog` import no jax at module scope, so
the classifier and the chaos harness work even when the backend is the
thing that is broken.
"""

from .breakers import BreakerRegistry, CircuitBreaker, global_registry
from .errors import (
    BackendUnavailable,
    CapacityExceeded,
    CompileTimeout,
    ExecuteFault,
    GraphValidationError,
    PoisonedCell,
    ResilienceError,
    WorkerHung,
    classify,
)
from .faults import FaultPlan, injected_faults, maybe_inject
from .watchdog import ExecutionWatchdog

__all__ = [
    "BackendUnavailable",
    "BreakerRegistry",
    "CapacityExceeded",
    "CircuitBreaker",
    "CompileTimeout",
    "ExecuteFault",
    "ExecutionWatchdog",
    "FaultPlan",
    "GraphValidationError",
    "PoisonedCell",
    "ResilienceError",
    "WorkerHung",
    "classify",
    "global_registry",
    "injected_faults",
    "maybe_inject",
]
