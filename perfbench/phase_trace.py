"""Charge a profiler trace's device time and idle gaps to the program's
phases.

    python -m perfbench.phase_trace <trace.xplane.pb>

The program's timer scopes emit host spans ``kp/<scope>``
(``kaminpar_tpu/utils/timer.py``) into the same trace as the device's
``XLA Modules`` events, so both share one clock.  Inside the window that
``trace_reduce`` reads (``bench/trace_start`` to ``bench/trace_end``), this
prints one JSON object:

- ``phase_busy``: for each phase of a partition, a ``kp/`` span whose
  closest enclosing ``kp/`` span on its host line is ``kp/partitioning``,
  the device busy time (the union of module events) inside the phase's
  spans, mean over the devices;
- ``unattributed_s``: the busy time outside every phase span;
- ``busy_s``: the whole busy time, as ``trace_reduce`` reads it;
- ``gaps``: the longest idle gaps of the first device, each named after the
  innermost closed ``bench/`` or ``kp/`` span that covers its middle;
- ``events``: the number of events on each line of each device plane.

Keep a run's trace with ``python -m perfbench.run ... --trace 1
--trace-out <dir>``.
"""

from __future__ import annotations

import json
import sys

from perfbench.trace_reduce import (
    SPAN_PREFIX,
    WINDOW_END,
    WINDOW_START,
    _is_device,
    _label,
    _union_ns,
)

PHASE_PREFIX = "kp/"
PARTITION_SPAN = "kp/partitioning"


def _overlap_ns(a, b) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _phase_spans(lines) -> dict:
    """{phase name: [(start, end)]}: the ``kp/`` spans of each host line
    whose closest enclosing ``kp/`` span on that line is ``kp/partitioning``."""
    phases: dict = {}
    for spans in lines:
        spans = sorted(spans, key=lambda s: (s[1], -s[2]))
        open_spans: list = []
        for name, start, end in spans:
            while open_spans and open_spans[-1][2] <= start:
                open_spans.pop()
            if open_spans and open_spans[-1][0] == PARTITION_SPAN:
                phases.setdefault(name, []).append((start, end))
            open_spans.append((name, start, end))
    return phases


def phase_profile(profile, top: int = 10) -> dict:
    """``profile`` is a ``jax.profiler.ProfileData``."""
    spans, phase_lines, devices = [], [], []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                mine = [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events
                        if ev.name.startswith((SPAN_PREFIX, PHASE_PREFIX))]
                spans += mine
                phase_lines.append([s for s in mine if s[0].startswith(PHASE_PREFIX)])
        elif _is_device(plane.name):
            devices.append(plane)
    marks = {name: (s, e) for name, s, e in spans}
    if WINDOW_START not in marks or WINDOW_END not in marks:
        raise ValueError(
            f"the trace lacks the host markers {WINDOW_START!r} and {WINDOW_END!r}"
        )
    w0, w1 = marks[WINDOW_START][0], marks[WINDOW_END][1]
    phases = {name: _union_ns(ivs) for name, ivs in _phase_spans(phase_lines).items()}

    events, unions = {}, []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        events[plane.name] = {
            name: sum(1 for _ in line.events) for name, line in lines.items()
        }
        line = lines.get("XLA Modules") or lines.get("XLA Ops")
        if line is None:
            continue
        clipped = [(max(ev.start_ns, w0), min(ev.end_ns, w1)) for ev in line.events
                   if ev.end_ns > w0 and ev.start_ns < w1]
        if clipped:
            unions.append(_union_ns(clipped))
    if not unions:
        raise ValueError("no device operation ran inside the traced window")

    n_dev = len(unions)
    busy = sum(e - s for u in unions for s, e in u) / n_dev
    phase_busy = {
        name: sum(_overlap_ns(u, ivs) for u in unions) / n_dev * 1e-9
        for name, ivs in phases.items()
    }
    edges = [w0] + [t for iv in unions[0] for t in iv] + [w1]
    gaps = [
        (_label((start + end) / 2, spans), (end - start) * 1e-9)
        for start, end in zip(edges[0::2], edges[1::2]) if end > start
    ]
    gaps.sort(key=lambda g: -g[1])
    return {
        "busy_s": busy * 1e-9,
        "phase_busy": dict(sorted(phase_busy.items(), key=lambda kv: -kv[1])),
        "unattributed_s": busy * 1e-9 - sum(phase_busy.values()),
        "gaps": gaps[:top],
        "events": events,
    }


def phase_file(path: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    return phase_profile(ProfileData.from_file(str(path)), top)


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__.split("\n\n")[1].strip())
    print(json.dumps(phase_file(sys.argv[1])))
