"""Hierarchical wall-clock timer tree.

Mirrors the reference's global ``Timer`` (``kaminpar-common/timer.h:20-62``):
nested named scopes accumulate wall time into a tree, printed human-readable
or as machine-readable ``TIME key=value`` lines (kaminpar-shm/kaminpar.cc:50-68).
On TPU the device work is asynchronous, so scopes that wrap device computation
should pass ``block=True`` (calls ``jax.block_until_ready`` on a sentinel) or
time whole jitted calls.  Each scope also emits a host span
``jax.profiler.TraceAnnotation("kp/<name>")``, so a profiler trace holds the
phases on the same clock as the device's programs, and a
``jax.named_scope`` that names the HLO ops traced inside it.

Thread model (ISSUE 5 satellite): every thread accumulates into its OWN
subtree — the creating thread owns the primary root, any other thread gets a
thread-local root lazily — and reports merge the subtrees by scope name at
read time.  Before this, concurrent ``scoped_timer`` scopes from the serve
engine's dispatcher/worker threads raced on one shared scope stack
(pop-from-the-wrong-thread corrupted the tree); now a thread can never see
another thread's stack.  Merging sums ``elapsed``/``starts`` per name, so
single-threaded reports are byte-identical to the pre-merge behavior.

Every scope also feeds the run telemetry (telemetry/trace.py) when a
recorder is active: a span begin/end pair per scope, plus optional
``jax.profiler`` arming for phases named in the recorder's
``profile_phases``.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

from ..telemetry import phases as _phases
from ..telemetry import trace as _ttrace


class _TimerNode:
    __slots__ = ("name", "elapsed", "starts", "children")

    def __init__(self, name: str):
        self.name = name
        self.elapsed = 0.0
        self.starts = 0
        self.children: Dict[str, "_TimerNode"] = {}

    def child(self, name: str) -> "_TimerNode":
        node = self.children.get(name)
        if node is None:
            node = self.children[name] = _TimerNode(name)
        return node


def _merge(dst: _TimerNode, src: _TimerNode) -> None:
    dst.elapsed += src.elapsed
    dst.starts += src.starts
    # list(): src may belong to a live thread inserting children mid-merge;
    # a racing insert is simply missed by this report, never a crash.
    for name, child in list(src.children.items()):
        _merge(dst.child(name), child)


class Timer:
    """Global hierarchical timer (reference: ``Timer::global()``)."""

    _global: Optional["Timer"] = None

    def __init__(self, name: str = "root"):
        self._root = _TimerNode(name)
        self._tls = threading.local()
        self._tls.stack = [self._root]  # binds for the creating thread only
        # Other threads' lazily-created roots; merged into reports.
        self._subtrees: List[_TimerNode] = []
        self._subtree_lock = threading.Lock()
        self._disabled = 0  # depth counter: parallel sections nest
        self._disabled_lock = threading.Lock()  # += from pool workers races
        self._t0 = time.perf_counter()

    @classmethod
    def global_(cls) -> "Timer":
        if cls._global is None:
            cls._global = Timer()
        return cls._global

    @classmethod
    def reset_global(cls) -> None:
        cls._global = Timer()

    def enable(self) -> None:
        with self._disabled_lock:
            self._disabled = max(self._disabled - 1, 0)

    def disable(self) -> None:
        """Reference disables timers during parallel IP
        (deep_multilevel.cc:213); we disable during per-block host work.
        disable/enable nest as a depth counter: an inner parallel section's
        re-enable must not reactivate scope accounting while an outer
        parallel section still has worker threads running."""
        with self._disabled_lock:
            self._disabled += 1

    def _stack(self) -> list:
        """This thread's scope stack (created on first use; non-creator
        threads root in their own subtree)."""
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            root = _TimerNode(threading.current_thread().name or "thread")
            with self._subtree_lock:
                self._subtrees.append(root)
            stack = self._tls.stack = [root]
        return stack

    @contextmanager
    def scope(self, name: str):
        """Time ``name`` as a child of this thread's open scope.  The host
        span ``kp/<name>`` is emitted even while the timer is disabled, so
        worker threads' phases show on their own lines of a trace."""
        import jax

        with jax.profiler.TraceAnnotation("kp/" + name):
            if self._disabled:
                yield
                return
            stack = self._stack()
            node = stack[-1].child(name)
            node.starts += 1
            stack.append(node)
            rec = _ttrace.active()
            armed = False
            if rec is not None:
                rec.begin(name)
                armed = rec.arm_profiler(name)
            start = time.perf_counter()
            try:
                with jax.named_scope(name):
                    yield
            finally:
                node.elapsed += time.perf_counter() - start
                stack.pop()
                if rec is not None:
                    if armed:
                        rec.disarm_profiler()
                    rec.end(name)

    # -- reporting ---------------------------------------------------------

    def merged_root(self) -> _TimerNode:
        """One tree over every thread's subtree: per-name sums of
        elapsed/starts.  Worker threads' *top-level* scopes merge as
        top-level phases (they run the same phase names the main thread
        would).  Reads race benignly with live scopes — a report taken
        mid-scope simply misses the open scope's in-flight time."""
        out = _TimerNode(self._root.name)
        _merge(out, self._root)
        with self._subtree_lock:
            subtrees = list(self._subtrees)
        for sub in subtrees:
            # list(): the owning thread may insert a sibling scope mid-read.
            for child in list(sub.children.values()):
                _merge(out.child(child.name), child)
        return out

    def phase_seconds(self, *path: str) -> Optional[float]:
        """Merged elapsed seconds of the scope at ``path`` (e.g.
        ``phase_seconds("partitioning", "coarsening")``); None when the
        scope never ran."""
        node = self.merged_root()
        for name in path:
            node = node.children.get(name)
            if node is None:
                return None
        return node.elapsed

    def _walk(self, node: _TimerNode, prefix: str, depth: int, max_depth: int, out: list):
        if depth > max_depth:
            return
        out.append((depth, node.name, node.elapsed, node.starts))
        for child in node.children.values():
            self._walk(child, prefix, depth + 1, max_depth, out)

    def render(self, max_depth: int = 4) -> str:
        rows: list = []
        for child in self.merged_root().children.values():
            self._walk(child, "", 0, max_depth, rows)
        lines = []
        for depth, name, elapsed, starts in rows:
            lines.append(f"{'  ' * depth}`-- {name}: {elapsed:.3f} s ({starts} runs)")
        return "\n".join(lines)

    def machine_readable(self) -> str:
        """``TIME key=value`` line (reference: kaminpar.cc:50-68)."""
        rows: list = []
        for child in self.merged_root().children.values():
            self._walk(child, "", 0, 99, rows)
        parts = []
        stack: list = []
        for depth, name, elapsed, _ in rows:
            stack = stack[:depth] + [name]
            parts.append(f"{'.'.join(stack)}={elapsed:.6f}")
        return "TIME " + " ".join(parts)


class SyncSentinel:
    """Mutable sentinel holder yielded by :func:`scoped_timer`.

    Under async dispatch a timer scope measures *dispatch* time, not compute;
    a scope that ends with device work notes a result array here and, when
    sync mode is on, the scope blocks on it before recording elapsed time so
    the compute is attributed to the right timer."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = None

    def note(self, x) -> None:
        self.value = x


_sync_mode = False


def set_sync_mode(on: bool) -> None:
    """Profiling mode: make ``scoped_timer(..., sync=True)`` scopes block on
    their noted sentinel before closing.  Off by default — blocking at every
    phase boundary serializes the async dispatch pipeline the device-resident
    spine exists to keep full (it adds waits, never transfers; the
    sync_stats budget is unaffected)."""
    global _sync_mode
    _sync_mode = bool(on)


def sync_mode() -> bool:
    """Effective sync-timer flag: the active EngineRuntime's setting when a
    pipeline activation is current on this thread (per-engine ownership,
    ISSUE 6), else the process default set via :func:`set_sync_mode`."""
    from ..context import current_runtime

    rt = current_runtime()
    return rt.sync_timers if rt is not None else _sync_mode


@contextmanager
def scoped_timer(name: str, sync: bool = False):
    """``SCOPED_TIMER`` + ``SCOPED_HEAP_PROFILER`` equivalent (timer.h /
    heap_profiler.h macro APIs — the reference pairs them on every scope).

    Also pushes ``name`` as the active :mod:`utils.sync_stats` phase so
    blocking-transfer counts line up with the timer tree, checks ``name``
    against the canonical phase registry (telemetry/phases.py — a misspelled
    phase warns instead of silently escaping the sync budget), and emits a
    telemetry span when a trace recorder is active.  ``sync=True`` marks a
    scope that ends with in-flight device work: the scope yields a
    :class:`SyncSentinel`, and when :func:`set_sync_mode` is on the scope
    calls ``jax.block_until_ready`` on the noted array before recording its
    elapsed time."""
    from . import sync_stats
    from .heap_profiler import HeapProfiler

    _phases.check(name)
    sentinel = SyncSentinel()
    with Timer.global_().scope(name):
        with HeapProfiler.scope(name):
            with sync_stats.scoped(name):
                try:
                    yield sentinel
                finally:
                    if sync and sync_mode() and sentinel.value is not None:
                        import jax

                        jax.block_until_ready(sentinel.value)
