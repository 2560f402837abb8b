"""Typed failure hierarchy + the one classifier.

Every pipeline/serve failure routes through :func:`classify`: ad-hoc
exceptions (jax backend errors, XLA runtime faults, allocator
exhaustion, timeouts) are mapped to exactly one typed failure class so
breakers, the degradation ladder, retry policies, and operators all
speak the same vocabulary.  The serve tier's pre-existing admission
errors (:class:`~kaminpar_tpu.serve.errors.QueueFullError`,
``DeadlineExceededError``, ``RequestCancelledError``) are *control-flow*
outcomes, not faults — the classifier passes them through untouched so
admission semantics never change under classification.

Pure stdlib at import time: the classifier must work when jax itself is
the broken component.
"""

from __future__ import annotations

from typing import Optional, Tuple


class ResilienceError(RuntimeError):
    """Base of the typed failure hierarchy.

    ``failure_class`` is the stable machine-readable class name (breaker
    keys, Prometheus labels, fault-plan error names); ``site`` names the
    dispatch site that observed the failure; ``injected`` marks faults
    raised by the chaos harness (:mod:`kaminpar_tpu.resilience.faults`)
    so recovery metrics can separate injected from organic failures.
    """

    failure_class = "unclassified"

    def __init__(self, message: str = "", *, site: str = "",
                 injected: bool = False):
        self.site = str(site)
        self.injected = bool(injected)
        super().__init__(message or self.failure_class)


class CompileTimeout(ResilienceError):
    """A compile/trace (warmup cell, AOT lowering, fresh shape bucket)
    exceeded its watchdog budget."""

    failure_class = "compile-timeout"


class ExecuteFault(ResilienceError):
    """A device execution (or its readback) failed or timed out
    mid-batch — the pipeline dispatched, the result never (validly)
    came back."""

    failure_class = "execute-fault"


class CapacityExceeded(ResilienceError):
    """Device memory pressure: the allocator refused (RESOURCE_EXHAUSTED
    / OOM) or the admission preflight predicted it would (wrapping the
    round-16 :class:`~kaminpar_tpu.serve.errors.CapacityError`)."""

    failure_class = "capacity-exceeded"


class BackendUnavailable(ResilienceError):
    """The accelerator backend is missing, failed to initialize, or the
    configuration requires a mode the runtime cannot provide."""

    failure_class = "backend-unavailable"


class PoisonedCell(ResilienceError):
    """A (shape-cell, backend) circuit breaker is open: this cell failed
    deterministically enough times that further dispatches are rejected
    fast instead of wedging the queue.  ``retry_after_s`` is the
    remaining cooldown before the half-open probe re-admits one
    request."""

    failure_class = "poisoned-cell"

    def __init__(self, cell: Tuple = (), retry_after_s: float = 0.0, *,
                 site: str = "", injected: bool = False):
        self.cell = tuple(cell)
        self.retry_after_s = float(retry_after_s)
        super().__init__(
            f"shape cell {self.cell} is poisoned (circuit breaker open); "
            f"half-open probe in {self.retry_after_s:.3f}s",
            site=site, injected=injected,
        )


class WorkerHung(ResilienceError):
    """The engine's dispatcher/worker thread died or hung mid-batch —
    in-flight requests are force-resolved with this instead of blocking
    their callers forever (ISSUE 13 satellite: bounded drain)."""

    failure_class = "worker-hung"


class GraphValidationError(ResilienceError, ValueError):
    """Rejected graph input at the facade boundary (non-monotone
    row_ptr, out-of-range columns, negative/overflowing weights) —
    typed rejection instead of downstream kernel garbage.  Also a
    ``ValueError`` so pre-round-17 callers catching the facade's
    validation errors keep working."""

    failure_class = "graph-validation"


#: failure-class name -> error type (fault plans name errors by class).
FAILURE_CLASSES = {
    cls.failure_class: cls
    for cls in (
        CompileTimeout, ExecuteFault, CapacityExceeded, BackendUnavailable,
        PoisonedCell, WorkerHung, GraphValidationError,
    )
}


# Message fragments that identify backend bring-up failures vs allocator
# exhaustion inside the undifferentiated RuntimeError/XlaRuntimeError soup
# jax raises (backend init failures + the jaxlib error strings).
_BACKEND_MARKERS = (
    "unavailable", "failed to initialize", "no visible device",
    "backend", "failed precondition", "deadline_exceeded",
    "unable to initialize", "device or resource busy",
)
_CAPACITY_MARKERS = (
    "resource_exhausted", "resource exhausted", "out of memory", "oom",
    "allocation", "hbm", "bytes_limit",
)


def _passthrough(exc: BaseException) -> Optional[BaseException]:
    """Control-flow outcomes that must not be reclassified as faults."""
    if isinstance(exc, ResilienceError):
        return exc
    try:
        from ..serve import errors as serve_errors
    except Exception:  # noqa: BLE001 — serve tier optional for the classifier
        return None
    if isinstance(exc, (
        serve_errors.QueueFullError,
        serve_errors.DeadlineExceededError,
        serve_errors.RequestCancelledError,
        serve_errors.EngineStoppedError,
    )):
        return exc
    return None


def classify(exc: BaseException, site: str = "") -> ResilienceError:
    """Map an arbitrary exception to exactly one typed failure class.

    The ONE classifier of the resilience layer: every ``except`` around a
    pipeline/serve dispatch site routes through here (statically enforced
    by the kptlint ``error-discipline`` rule).  Idempotent on already-
    typed errors; admission/control-flow serve errors pass through via
    the caller re-raising (:func:`is_control_flow` tells them apart).
    The original exception is chained as ``__cause__``.
    """
    hit = _passthrough(exc)
    if isinstance(hit, ResilienceError):
        return hit
    if hit is not None:
        # A control-flow serve error reached the classifier anyway: wrap
        # as an execute fault so the caller still gets a typed error, but
        # keep the original chained (callers should re-raise these
        # instead — see is_control_flow).
        err = ExecuteFault(f"{type(exc).__name__}: {exc}", site=site)
        err.__cause__ = exc
        return err

    msg = str(exc).lower()
    name = type(exc).__name__

    out: ResilienceError
    try:
        from ..serve.errors import CapacityError

        preflight = isinstance(exc, CapacityError)
    except Exception:  # noqa: BLE001
        preflight = False
    if preflight or isinstance(exc, MemoryError) or any(
        m in msg for m in _CAPACITY_MARKERS
    ):
        out = CapacityExceeded(f"{name}: {exc}", site=site)
    elif isinstance(exc, TimeoutError):
        out = (
            CompileTimeout(f"{name}: {exc}", site=site)
            if "compile" in (site or "").lower() or "compile" in msg
            else ExecuteFault(f"{name}: {exc}", site=site)
        )
    elif isinstance(exc, (ImportError, ModuleNotFoundError)) or any(
        m in msg for m in _BACKEND_MARKERS
    ):
        out = BackendUnavailable(f"{name}: {exc}", site=site)
    else:
        out = ExecuteFault(f"{name}: {exc}", site=site)
    out.__cause__ = exc
    return out


def is_control_flow(exc: BaseException) -> bool:
    """True for admission/lifecycle outcomes (queue full, deadline,
    cancel, engine stopped) that dispatch-site handlers should re-raise
    untouched rather than classify as faults."""
    hit = _passthrough(exc)
    return hit is not None and not isinstance(hit, ResilienceError)
