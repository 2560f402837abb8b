"""Distinct-compiled-shape accounting for the hot kernels.

On-silicon profiling (TPU_NOTES.md r5) showed the full-partition wall-clock
dominated not by LP compute but by dozens of per-shape cold compiles
(~35-48 s each, measured r5).  This module makes the shape count a
first-class, regression-testable metric: the jitted LP iterate / contraction
entry points call :func:`record` *inside* their traced bodies, so a record
fires exactly once per (shape, static-arg) specialization — i.e. once per
XLA compile of that kernel family per process (the persistent cache may make
the compile warm, but the specialization count is what the padding policy
controls and what a cold environment pays for).

``bench.py`` embeds :func:`snapshot` in its headline JSON
(``compiled_shape_count``), and tests/test_pallas_lp.py asserts the v-cycle
bound.

Round 16 (ISSUE 12 tentpole a) adds the **executable census**: what every
compiled program *would do* on silicon, straight from XLA's own analyses —
``lowered.cost_analysis()`` (flops, bytes accessed) and
``compiled.memory_analysis()`` (argument/output/temp/peak bytes) — keyed by
``(kind, shape cell)``.  Harvest sites: the AOT export suites
(utils/aot.py, ``census=True``), the serve engine's warmup cells
(``PartitionEngine._warmup``), and :mod:`telemetry.capacity`'s planner
lowerings.  The census is **armed explicitly** (:func:`arm_executable_census`)
and is strictly host-side — lowering abstract shapes and reading analysis
dicts performs zero device transfers and zero collectives, so an armed run
is bit-identical to an unarmed one (asserted in tests/test_capacity.py).
The **compile ledger** is always on: one set of ``jax.monitoring``
listeners, installed when the package is imported, books every program JAX
traces, lowers, compiles or loads from the persistent cache under the
program's name and the timer phase open on the dispatching thread
(:func:`compile_ledger_snapshot`).  It runs only when JAX compiles or loads
a program, so a warm partition pays nothing for it.
:func:`compile_time_snapshot` and :func:`compile_by_phase_snapshot` are
views over it.
"""

from __future__ import annotations

import re
import threading
from collections import defaultdict

from ..telemetry import trace as _ttrace
from . import sync_stats

_lock = threading.Lock()
_shapes: dict = defaultdict(set)
_listener_installed = False
_census_armed = False
# (kind, cell) -> {flops, bytes_accessed, argument_bytes, output_bytes,
#                  temp_bytes, peak_bytes, generated_code_bytes, count}
_census: dict = {}
# The compile ledger: (program, phase) -> a copy of _LEDGER_ROW.
_LEDGER_ROW = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0, "compiles": 0,
               "load_s": 0.0, "loads": 0}
_ledger: dict = {}
# Per dispatching thread: open trace events, and whether the persistent
# cache answered the backend-compile event in flight.
_events_tls = threading.local()


def _sig_of(arrays, statics) -> tuple:
    sig = []
    for a in arrays:
        if hasattr(a, "shape"):
            sig.append((tuple(a.shape), str(a.dtype)))
        else:
            sig.append(repr(a))
    return tuple(sig), tuple(statics)


def record(kind: str, arrays=(), statics=()) -> None:
    """Record one kernel specialization.  Call from *inside* a jitted body:
    Python there runs once per compile, never per execution."""
    sig = _sig_of(arrays, statics)
    with _lock:
        new = sig not in _shapes[kind]
        _shapes[kind].add(sig)
        total = sum(len(v) for v in _shapes.values())
    # Telemetry counter sample only when the specialization is NEW — record()
    # re-fires on retraces of known shapes, and those must not spam the
    # trace; the track then shows exactly the cold-compile bursts.
    if new:
        rec = _ttrace.active()
        if rec is not None:
            rec.counter("compiled_shapes", {"total": total})


def distinct(kind: str | None = None) -> int:
    with _lock:
        if kind is not None:
            return len(_shapes.get(kind, ()))
        return sum(len(v) for v in _shapes.values())


def snapshot() -> dict:
    """{kind: distinct specialization count} plus a total."""
    with _lock:
        out = {k: len(v) for k, v in sorted(_shapes.items())}
    out["total"] = sum(out.values())
    return out


def reset() -> None:
    with _lock:
        _shapes.clear()
        _census.clear()
        _ledger.clear()


_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_WRAPPED_NAME = re.compile(r"^\w+\((.*)\)$")


def _program_name(fun_name) -> str:
    """JAX names a trace by the function (``f``) and its lowering and
    compile by the wrapped program (``jit(f)``); the ledger uses ``f``."""
    name = str(fun_name)
    wrapped = _WRAPPED_NAME.match(name)
    return wrapped.group(1) if wrapped else name


def _book(fun_name, field: str, seconds: float, count_field: str | None = None):
    key = (_program_name(fun_name), sync_stats.active_phase())
    with _lock:
        row = _ledger.get(key)
        if row is None:
            row = _ledger[key] = dict(_LEDGER_ROW)
        row[field] += seconds
        if count_field is not None:
            row[count_field] += 1


def _on_scalar(event, value, **kwargs):
    # JAX records a scalar when a trace event opens; a trace that opens
    # inside another (a nested jit traced with its caller) is part of the
    # outer one's time and is not booked again.
    if event == _TRACE_EVENT:
        _events_tls.trace_depth = getattr(_events_tls, "trace_depth", 0) + 1


def _on_event(event, **kwargs):
    # Fired on the compiling thread inside the backend-compile event when the
    # persistent cache holds the program (jax/_src/compiler.py).
    if event == _CACHE_HIT_EVENT:
        _events_tls.cache_hit = True


def _on_duration(event, duration, fun_name="", **kwargs):
    if event == _TRACE_EVENT:
        depth = getattr(_events_tls, "trace_depth", 1) - 1
        _events_tls.trace_depth = max(depth, 0)
        if depth <= 0:
            _book(fun_name, "trace_s", duration)
    elif event == _LOWER_EVENT:
        _book(fun_name, "lower_s", duration)
    elif event == _BACKEND_EVENT:
        hit = getattr(_events_tls, "cache_hit", False)
        _events_tls.cache_hit = False
        if hit:
            _book(fun_name, "load_s", duration, "loads")
        else:
            _book(fun_name, "compile_s", duration, "compiles")


def _install_ledger() -> None:
    """Install the compile ledger's ``jax.monitoring`` listeners (once; the
    module's import does)."""
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        _listener_installed = True
    import jax.monitoring as monitoring

    monitoring.register_scalar_listener(_on_scalar)
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def compile_ledger_snapshot() -> list:
    """One row per (program, timer phase) that JAX traced, lowered, compiled
    or loaded: ``trace_s`` (jaxpr tracing, a nested jit inside its caller's),
    ``lower_s`` (jaxpr to MLIR), ``compile_s``/``compiles`` (backend
    compiles the persistent cache missed) and ``load_s``/``loads`` (backend
    compile events it answered).  The phase is the innermost
    :func:`~kaminpar_tpu.utils.timer.scoped_timer` scope open on the thread
    that dispatched the program (``"untracked"`` outside any).  Rows come
    most seconds first."""
    with _lock:
        rows = [
            {"program": program, "phase": phase, **row}
            for (program, phase), row in _ledger.items()
        ]
    return sorted(
        rows,
        key=lambda r: -(r["trace_s"] + r["lower_s"] + r["compile_s"] + r["load_s"]),
    )


def compile_time_snapshot() -> dict:
    """Totals over the ledger: ``backend_compile_s`` and ``compile_events``
    count backend-compile events, loads from the persistent cache included;
    ``trace_s`` is jaxpr tracing."""
    rows = compile_ledger_snapshot()
    return {
        "backend_compile_s": round(
            sum(r["compile_s"] + r["load_s"] for r in rows), 2
        ),
        "trace_s": round(sum(r["trace_s"] for r in rows), 2),
        "compile_events": sum(r["compiles"] + r["loads"] for r in rows),
    }


# -- executable census (round 16, ISSUE 12) ----------------------------------


def arm_executable_census(on: bool = True) -> None:
    """Arm (or disarm) the executable census.  Armed harvesting is pure
    host-side compiler introspection: zero blocking transfers, zero
    collectives, bit-identical results (tests/test_capacity.py asserts
    both).  Off by default so tier-1 engine warmups stay cheap."""
    global _census_armed
    _census_armed = bool(on)


def executable_census_armed() -> bool:
    return _census_armed


def _cell_key(kind: str, cell) -> str:
    return f"{kind}|{','.join(str(c) for c in cell)}" if cell else kind


def harvest(kind: str, lowered=None, compiled=None, cell=()) -> dict | None:
    """Record one executable's cost/memory analysis under ``(kind, cell)``.

    ``lowered`` is a ``jax.stages.Lowered`` (flops / bytes accessed via
    ``cost_analysis``); ``compiled`` a ``jax.stages.Compiled``
    (argument/output/temp bytes via ``memory_analysis``).  Either may be
    None.  Never raises — a census failure must not void the compile it
    rode on.  Returns the stored row (or None when nothing was harvested).
    """
    row = {
        "flops": None, "bytes_accessed": None, "argument_bytes": None,
        "output_bytes": None, "temp_bytes": None, "peak_bytes": None,
        "generated_code_bytes": None, "count": 1,
    }
    got = False
    try:
        if lowered is not None:
            ca = lowered.cost_analysis()
            if isinstance(ca, (list, tuple)):  # per-device list on some jax
                ca = ca[0] if ca else {}
            if isinstance(ca, dict):
                row["flops"] = float(ca.get("flops", 0.0))
                row["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
                got = True
    except Exception:  # noqa: BLE001
        pass
    try:
        if compiled is not None:
            ma = compiled.memory_analysis()
            if ma is not None:
                arg = int(getattr(ma, "argument_size_in_bytes", 0))
                out = int(getattr(ma, "output_size_in_bytes", 0))
                tmp = int(getattr(ma, "temp_size_in_bytes", 0))
                alias = int(getattr(ma, "alias_size_in_bytes", 0))
                code = int(getattr(ma, "generated_code_size_in_bytes", 0))
                row.update({
                    "argument_bytes": arg, "output_bytes": out,
                    "temp_bytes": tmp, "generated_code_bytes": code,
                    # The executable's device high-water mark: arguments +
                    # outputs + temporaries live simultaneously (aliased
                    # donation bytes counted once — they overlap arguments).
                    "peak_bytes": arg + out + tmp - alias + code,
                })
                got = True
    except Exception:  # noqa: BLE001
        pass
    if not got:
        return None
    key = _cell_key(kind, cell)
    with _lock:
        prev = _census.get(key)
        if prev is not None:
            row["count"] = prev["count"] + 1
        _census[key] = row
    rec = _ttrace.active()
    if rec is not None:
        rec.counter("executable_census", {
            k: v for k, v in row.items()
            if k in ("flops", "bytes_accessed", "temp_bytes", "peak_bytes")
            and v is not None
        })
    return row


def harvest_fn(kind: str, fn, *args, cell=(), compile_it: bool = True,
               **kwargs):
    """Lower (and optionally compile) ``fn`` for the ambient backend and
    harvest its analyses.  ``fn`` may be a jitted callable (lowered
    directly) or a plain traceable (wrapped in a throwaway jit closed over
    ``kwargs``).  ``args`` may be concrete arrays or
    ``jax.ShapeDtypeStruct`` — shape-only lowering never touches device
    data.  No-op (returns None) when the census is not armed."""
    if not _census_armed:
        return None
    import jax

    try:
        target = fn if hasattr(fn, "lower") else None
        if target is not None:
            lowered = target.lower(*args, **kwargs)
        else:
            lowered = jax.jit(lambda *a: fn(*a, **kwargs)).lower(*args)
        compiled = lowered.compile() if compile_it else None
    except Exception:  # noqa: BLE001 — the census never voids the caller
        return None
    return harvest(kind, lowered, compiled, cell=cell)


def executable_census_snapshot() -> dict:
    """{ "kind|cell": {flops, bytes_accessed, ..., peak_bytes}, ... } plus
    a ``totals`` row (sums over harvested executables; peak is a max — one
    executable runs at a time)."""
    with _lock:
        out = {k: dict(v) for k, v in sorted(_census.items())}
    totals = {
        "executables": len(out),
        "flops": sum(v["flops"] or 0.0 for v in out.values()),
        "bytes_accessed": sum(v["bytes_accessed"] or 0.0 for v in out.values()),
        "peak_bytes_max": max(
            (v["peak_bytes"] or 0 for v in out.values()), default=0
        ),
    }
    out["totals"] = totals
    return out


def census_peak_temp_bytes(kind: str, cell=()) -> int | None:
    """The harvested temp bytes of ``(kind, cell)`` — the number the
    capacity planner composes with the resident-buffer model; None when the
    cell was never harvested."""
    with _lock:
        row = _census.get(_cell_key(kind, cell))
    return None if row is None else row.get("temp_bytes")


def compile_by_phase_snapshot() -> dict:
    """{phase: {events, backend_compile_s}} — which phases paid the
    backend-compile events (loads from the persistent cache included): the
    ledger summed over programs."""
    out: dict = {}
    for row in compile_ledger_snapshot():
        acc = out.setdefault(row["phase"], {"events": 0, "backend_compile_s": 0.0})
        acc["events"] += row["compiles"] + row["loads"]
        acc["backend_compile_s"] += row["compile_s"] + row["load_s"]
    return {
        ph: {"events": acc["events"],
             "backend_compile_s": round(acc["backend_compile_s"], 3)}
        for ph, acc in sorted(out.items())
    }


def census_prometheus_families() -> list:
    """The executable census as Prometheus families (rendered into
    ``PartitionEngine.metrics_text()`` alongside the serve families)."""
    snap = executable_census_snapshot()
    totals = snap.pop("totals")
    flops, peaks, temps = [], [], []
    for key, row in snap.items():
        kind, _, cell = key.partition("|")
        labels = {"kind": kind, "cell": cell}
        if row.get("flops") is not None:
            flops.append((labels, row["flops"]))
        if row.get("peak_bytes") is not None:
            peaks.append((labels, row["peak_bytes"]))
        if row.get("temp_bytes") is not None:
            temps.append((labels, row["temp_bytes"]))
    return [
        ("kaminpar_executable_census_total", "gauge",
         "Executables harvested by the compiled-executable census",
         [({}, totals["executables"])]),
        ("kaminpar_executable_flops", "gauge",
         "XLA cost-analysis flops per compiled executable (kind, shape cell)",
         flops or [({}, None)]),
        ("kaminpar_executable_peak_bytes", "gauge",
         "XLA memory-analysis peak bytes (arguments + outputs + temps) per "
         "compiled executable",
         peaks or [({}, None)]),
        ("kaminpar_executable_temp_bytes", "gauge",
         "XLA memory-analysis temp bytes per compiled executable — the "
         "transient the HBM capacity planner composes with the resident "
         "model (telemetry/capacity.py)",
         temps or [({}, None)]),
    ]


_install_ledger()
