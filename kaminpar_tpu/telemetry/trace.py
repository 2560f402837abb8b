"""Structured per-run event trace with Chrome trace-event export (ISSUE 5).

The reference partitioner's experimental interface is a global hierarchical
timer plus per-level statistics printed as ``TIME``/``RESULT`` lines
(kaminpar-common/timer.h, kaminpar-shm/kaminpar.cc:48-68).  This module is
the TPU port's unified equivalent: one :class:`TraceRecorder` per run
collects

- **span events** fed by every ``scoped_timer`` scope (utils/timer.py emits
  begin/end pairs here) and by the serve engine's queue lifecycle points,
- **counter samples** fed by the blocking-transfer census
  (utils/sync_stats.py), the compiled-shape census (utils/compile_stats.py),
  the device-memory watermark (utils/heap_profiler.py), and the per-level
  quality probes (telemetry/probes.py), and
- **quality rows** — the structured per-level records (level n/m, cut,
  imbalance, moved counts) that bench.py embeds in its JSON
  artifacts.

The trace exports to Chrome trace-event JSON (``chrome://tracing`` /
Perfetto's legacy-JSON importer): ``python -m kaminpar_tpu ... --trace-out
trace.json`` and ``python -m kaminpar_tpu.tools trace`` are the user-facing
ends.  Timestamps are microseconds on one process-wide monotonic clock
(``time.perf_counter`` relative to recorder start), so a run's spans line up
side-by-side with a ``jax.profiler`` capture the recorder can arm around
configured phases (:attr:`TraceRecorder.profile_phases`).

Everything no-ops when no recorder is active (:func:`active` returns None);
the instrumented hot paths pay one attribute load per scope.
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

_PID = os.getpid()
_active_lock = threading.Lock()
_active: Optional["TraceRecorder"] = None


class TraceRecorder:
    """Thread-safe event accumulator for one run.

    Events follow the Chrome trace-event format: ``B``/``E`` duration pairs
    per (pid, tid), ``C`` counter samples, ``i`` instants, ``M`` metadata.
    Thread ids are small sequential ints with ``thread_name`` metadata, so
    serve worker threads render as named rows.
    """

    #: Event-count bound: a recorder can outlive a whole serve session, and
    #: an unbounded list would grow with every request; past the cap only
    #: span-closing "E" events are admitted (keeping B/E matched) and drops
    #: are counted into the export's otherData.
    DEFAULT_MAX_EVENTS = 500_000

    def __init__(self, profile_phases=(), profile_dir: str = "",
                 max_events: int = DEFAULT_MAX_EVENTS):
        self._t0 = time.perf_counter()
        self.epoch_s = time.time()
        self._lock = threading.RLock()
        self._events: List[dict] = []
        self.max_events = int(max_events)
        self.dropped_events = 0
        # Per-tid stack of "was this span's B admitted?" flags: an E is
        # emitted iff its B was, so the cap can never orphan an E (which
        # would fail validation and mis-nest the viewer's span stacks).
        self._span_admitted: Dict[int, List[bool]] = {}
        #: structured per-level quality rows (probes.py); exported into the
        #: trace's otherData and embedded by bench.py.
        self.quality: List[dict] = []
        #: free-form run metadata (graph, k, preset, ...), exported verbatim.
        self.meta: Dict[str, object] = {}
        # jax.profiler arming: phases (timer-scope names) around which the
        # recorder starts/stops an XLA profiler capture so device timelines
        # can be aligned with the host-side spans.
        self.profile_phases = frozenset(profile_phases)
        self.profile_dir = profile_dir or ".jax_profile"
        self._profiling = False
        self._tids: Dict[int, int] = {}

    # -- event intake ------------------------------------------------------

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def to_us(self, t_perf: float) -> float:
        """Convert a ``time.perf_counter()`` reading taken elsewhere into
        this recorder's trace clock (µs since recorder start, clamped to
        0).  Lets event stores that stamp their own perf_counter times —
        the request-trace registry (telemetry/reqtrace.py) — replay onto
        lanes of this trace without re-instrumenting."""
        return max(0.0, (float(t_perf) - self._t0) * 1e6)

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.get(ident)
                if tid is None:
                    tid = self._tids[ident] = len(self._tids)
                    self._events.append({
                        "name": "thread_name", "ph": "M", "ts": 0.0,
                        "pid": _PID, "tid": tid,
                        "args": {"name": threading.current_thread().name},
                    })
        return tid

    def _emit(self, ev: dict) -> None:
        """Capped intake for non-span events (B/E pairs go through
        begin()/end(), which keep their admission flags paired)."""
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped_events += 1
                return
            self._events.append(ev)

    def begin(self, name: str, **args) -> None:
        tid = self._tid()
        ev = {"name": name, "ph": "B", "ts": self._now_us(),
              "pid": _PID, "tid": tid}
        if args:
            ev["args"] = args
        with self._lock:
            admitted = len(self._events) < self.max_events
            self._span_admitted.setdefault(tid, []).append(admitted)
            if admitted:
                self._events.append(ev)
            else:
                self.dropped_events += 1

    def end(self, name: str) -> None:
        tid = self._tid()
        ev = {"name": name, "ph": "E", "ts": self._now_us(),
              "pid": _PID, "tid": tid}
        with self._lock:
            stack = self._span_admitted.get(tid)
            admitted = stack.pop() if stack else True
            # The E of an admitted B always lands, even past the cap —
            # matched pairs are the export invariant.
            if admitted:
                self._events.append(ev)
            else:
                self.dropped_events += 1

    def lane_tid(self, lane: str) -> int:
        """tid of a *synthetic* lane row (e.g. per-shard mesh lanes,
        round 13) — named via thread_name metadata like real threads, but
        fed by :meth:`lane_span` with explicit timestamps instead of the
        ambient clock.  Lane keys live in the same tid namespace as thread
        idents (string keys cannot collide with ints)."""
        key = f"lane:{lane}"
        tid = self._tids.get(key)
        if tid is None:
            with self._lock:
                tid = self._tids.get(key)
                if tid is None:
                    tid = self._tids[key] = len(self._tids)
                    self._events.append({
                        "name": "thread_name", "ph": "M", "ts": 0.0,
                        "pid": _PID, "tid": tid, "args": {"name": lane},
                    })
        return tid

    def lane_span(self, lane: str, name: str, ts_begin_us: float,
                  ts_end_us: float, **args) -> None:
        """Append one CLOSED span on a synthetic lane row with explicit
        timestamps (monotonic per lane as long as callers emit spans in
        chronological order, which the sequential dist pipeline does).  The
        B/E pair is admitted or dropped atomically so the cap can never
        orphan half a span."""
        tid = self.lane_tid(lane)
        t0 = float(ts_begin_us)
        t1 = float(max(ts_end_us, ts_begin_us))
        b = {"name": name, "ph": "B", "ts": t0, "pid": _PID, "tid": tid}
        if args:
            b["args"] = args
        e = {"name": name, "ph": "E", "ts": t1, "pid": _PID, "tid": tid}
        with self._lock:
            if len(self._events) + 1 >= self.max_events:
                self.dropped_events += 2
                return
            self._events.append(b)
            self._events.append(e)

    def instant(self, name: str, **args) -> None:
        ev = {"name": name, "ph": "i", "s": "t", "ts": self._now_us(),
              "pid": _PID, "tid": self._tid()}
        if args:
            ev["args"] = args
        self._emit(ev)

    def counter(self, name: str, values: Dict[str, float]) -> None:
        """One counter sample; ``values`` keys render as series in the
        trace viewer's counter track."""
        self._emit({"name": name, "ph": "C", "ts": self._now_us(),
                    "pid": _PID, "tid": self._tid(),
                    "args": {k: v for k, v in values.items() if v is not None}})

    def quality_row(self, kind: str, **values) -> dict:
        """Record a structured per-level quality row AND its counter sample
        (numeric values only ride the counter track)."""
        row = {"kind": kind, "t_us": round(self._now_us(), 1)}
        row.update(values)
        with self._lock:
            self.quality.append(row)
        self.counter(
            f"quality/{kind}",
            {k: v for k, v in values.items() if isinstance(v, (int, float))
             and not isinstance(v, bool)},
        )
        return row

    # -- jax profiler arming ----------------------------------------------

    def arm_profiler(self, phase: str) -> bool:
        """Start a ``jax.profiler`` capture if ``phase`` is configured and
        none is running; returns whether this call armed it."""
        if phase not in self.profile_phases or self._profiling:
            return False
        try:
            import jax

            os.makedirs(self.profile_dir, exist_ok=True)
            jax.profiler.start_trace(self.profile_dir)
        except Exception as exc:  # noqa: BLE001 — profiling must never kill a run
            self.instant("jax_profiler_error", phase=phase,
                         error=f"{type(exc).__name__}: {exc}"[:200])
            return False
        self._profiling = True
        self.instant("jax_profiler_start", phase=phase,
                     log_dir=self.profile_dir)
        return True

    def disarm_profiler(self) -> None:
        if not self._profiling:
            return
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as exc:  # noqa: BLE001
            self.instant("jax_profiler_error",
                         error=f"{type(exc).__name__}: {exc}"[:200])
        self._profiling = False
        self.instant("jax_profiler_stop")

    # -- export ------------------------------------------------------------

    def chrome_trace(self) -> dict:
        """The Chrome trace-event JSON object.

        Events are sorted by timestamp (stable, so per-thread ordering — and
        with it B/E nesting — is preserved); any span still open when the
        trace is exported gets a synthetic close at the export timestamp so
        the file always carries matched B/E pairs.
        """
        now = self._now_us()
        with self._lock:
            events = sorted(self._events, key=lambda e: e.get("ts", 0.0))
            quality = list(self.quality)
            meta = dict(self.meta)
        open_spans: Dict[tuple, list] = {}
        for ev in events:
            key = (ev.get("pid"), ev.get("tid"))
            if ev.get("ph") == "B":
                open_spans.setdefault(key, []).append(ev["name"])
            elif ev.get("ph") == "E":
                stack = open_spans.get(key)
                if stack:
                    stack.pop()
        for (pid, tid), stack in open_spans.items():
            for name in reversed(stack):
                events.append({"name": name, "ph": "E", "ts": now,
                               "pid": pid, "tid": tid})
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "kaminpar_tpu.telemetry",
                "epoch_s": round(self.epoch_s, 3),
                "dropped_events": self.dropped_events,
                "quality": quality,
                **meta,
            },
        }

    def write(self, path: str) -> str:
        obj = self.chrome_trace()
        with open(path, "w") as fh:
            json.dump(obj, fh)
        return path

    def summary(self) -> dict:
        """Compact artifact-embeddable summary (bench.py)."""
        with self._lock:
            events = list(self._events)
            n_quality = len(self.quality)
        spans = sum(1 for e in events if e.get("ph") == "B")
        counters = sum(1 for e in events if e.get("ph") == "C")
        return {
            "events": len(events),
            "spans": spans,
            "counter_samples": counters,
            "quality_rows": n_quality,
            "dropped_events": self.dropped_events,
            "duration_s": round(self._now_us() / 1e6, 3),
        }


# -- module-level run management --------------------------------------------


def active() -> Optional[TraceRecorder]:
    """The run's recorder, or None when telemetry is off (the fast path the
    instrumented scopes check)."""
    return _active


def start(profile_phases=(), profile_dir: str = "") -> TraceRecorder:
    global _active
    with _active_lock:
        if _active is not None:
            raise RuntimeError(
                "a telemetry run is already active (one recorder per process; "
                "call telemetry.trace.stop() first)"
            )
        _active = TraceRecorder(profile_phases=profile_phases,
                                profile_dir=profile_dir)
    return _active


def stop() -> Optional[TraceRecorder]:
    global _active
    with _active_lock:
        rec, _active = _active, None
    if rec is not None:
        rec.disarm_profiler()
    return rec


@contextmanager
def run(trace_out: str = "", profile_phases=(), profile_dir: str = ""):
    """Record one telemetry run; writes the Chrome trace to ``trace_out``
    (when given) on exit, even when the run raises."""
    rec = start(profile_phases=profile_phases,
                profile_dir=profile_dir or (trace_out + ".profile" if trace_out else ""))
    try:
        yield rec
    finally:
        stop()
        if trace_out:
            try:
                rec.write(trace_out)
            except OSError as exc:
                # A failed trace write must not mask the run body's own
                # exception (or fail an otherwise-finished run).
                import warnings

                warnings.warn(
                    f"kaminpar_tpu: could not write trace {trace_out!r}: {exc}",
                    RuntimeWarning,
                    stacklevel=2,
                )


def shard_lane_summary(obj: dict) -> list:
    """Per-shard imbalance from the mesh lanes' span walls (round 13).

    The dist pipeline emits per-level spans on synthetic ``shardN`` lanes
    whose walls are work-proportional estimates of each shard's share of
    the bulk-synchronous level (dist/partitioner.py — a measured per-shard
    wall does not exist under SPMD).  Returns one row per span name:
    ``{name, walls_ms: [per-shard summed wall], min_ms, mean_ms, max_ms,
    imb}`` with imb = max/mean, the reference's dist-timer convention."""
    import re as _re

    events = obj.get("traceEvents") or []
    lane_of_tid = {}
    for ev in events:
        if (
            ev.get("ph") == "M"
            and ev.get("name") == "thread_name"
            and _re.fullmatch(r"shard\d+", (ev.get("args") or {}).get("name", ""))
        ):
            lane_of_tid[(ev.get("pid"), ev.get("tid"))] = int(
                ev["args"]["name"][5:]
            )
    if not lane_of_tid:
        return []
    num_shards = max(lane_of_tid.values()) + 1
    walls: Dict[str, list] = {}
    open_b: Dict[tuple, list] = {}
    for ev in events:
        key = (ev.get("pid"), ev.get("tid"))
        if key not in lane_of_tid:
            continue
        if ev.get("ph") == "B":
            open_b.setdefault(key, []).append(ev)
        elif ev.get("ph") == "E":
            stack = open_b.get(key)
            if not stack:
                continue
            b = stack.pop()
            row = walls.setdefault(b["name"], [0.0] * num_shards)
            row[lane_of_tid[key]] += (ev["ts"] - b["ts"]) / 1e3
    out = []
    for name in sorted(walls):
        ms = walls[name]
        mean = sum(ms) / max(len(ms), 1)
        out.append({
            "name": name,
            "walls_ms": [round(v, 3) for v in ms],
            "min_ms": round(min(ms), 3),
            "mean_ms": round(mean, 3),
            "max_ms": round(max(ms), 3),
            "imb": round(max(ms) / mean, 4) if mean > 0 else 1.0,
        })
    return out


# -- validation (tools trace / tier-1 smoke tests) ---------------------------


def validate_chrome_trace(obj: dict) -> dict:
    """Validate a Chrome trace-event object; raises ValueError on any
    malformation and returns a summary dict.

    Checks: ``traceEvents`` is a list, every non-metadata event carries
    name/ph/ts/pid/tid, timestamps are monotonically non-decreasing per
    (pid, tid), every ``E`` matches the innermost open ``B`` of its thread
    (and none stay open), and counter samples carry numeric args.
    """
    if not isinstance(obj, dict) or not isinstance(obj.get("traceEvents"), list):
        raise ValueError("not a Chrome trace: missing traceEvents list")
    events = obj["traceEvents"]
    stacks: Dict[tuple, list] = {}
    last_ts: Dict[tuple, float] = {}
    spans = counters = instants = 0
    span_names: set = set()
    counter_names: set = set()
    ts_min = ts_max = None
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"event {i} is not an object")
        ph = ev.get("ph")
        if ph == "M":
            continue
        for field in ("name", "ts", "pid", "tid"):
            if field not in ev:
                raise ValueError(f"event {i} ({ph!r}) missing {field!r}")
        ts = ev["ts"]
        if not isinstance(ts, (int, float)):
            raise ValueError(f"event {i} has non-numeric ts {ts!r}")
        key = (ev["pid"], ev["tid"])
        if ts < last_ts.get(key, float("-inf")):
            raise ValueError(
                f"event {i} ({ev['name']!r}): ts {ts} goes backwards on "
                f"pid/tid {key}"
            )
        last_ts[key] = ts
        ts_min = ts if ts_min is None else min(ts_min, ts)
        ts_max = ts if ts_max is None else max(ts_max, ts)
        if ph == "B":
            stacks.setdefault(key, []).append(ev["name"])
            span_names.add(ev["name"])
        elif ph == "E":
            stack = stacks.get(key)
            if not stack:
                raise ValueError(f"event {i}: E {ev['name']!r} without open B")
            top = stack.pop()
            if top != ev["name"]:
                raise ValueError(
                    f"event {i}: E {ev['name']!r} does not match open B {top!r}"
                )
            spans += 1
        elif ph == "C":
            args = ev.get("args")
            if not isinstance(args, dict) or not all(
                isinstance(v, (int, float)) and not isinstance(v, bool)
                for v in args.values()
            ):
                raise ValueError(f"event {i}: counter args must be numeric")
            counters += 1
            counter_names.add(ev["name"])
        elif ph in ("i", "I"):
            instants += 1
        else:
            raise ValueError(f"event {i}: unknown phase type {ph!r}")
    unmatched = {k: v for k, v in stacks.items() if v}
    if unmatched:
        raise ValueError(f"unmatched B events at end of trace: {unmatched}")
    return {
        "events": len(events),
        "spans": spans,
        "counters": counters,
        "instants": instants,
        "span_names": sorted(span_names),
        "counter_names": sorted(counter_names),
        "duration_us": (ts_max - ts_min) if ts_max is not None else 0.0,
        "quality_rows": len((obj.get("otherData") or {}).get("quality", [])),
    }
