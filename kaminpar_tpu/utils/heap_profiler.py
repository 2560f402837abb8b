"""Device/heap memory profiler.

Reference: ``kaminpar-common/heap_profiler.h:22-70`` — scoped
START/STOP_HEAP_PROFILER sections recording allocation peaks per scope.
The TPU analog reads the XLA allocator statistics that
``jax.Device.memory_stats()`` exposes (``bytes_in_use``,
``peak_bytes_in_use``, ...) at scope entry/exit, building the same
tree-shaped report.  On backends without allocator stats (some CPU
builds) it degrades to a no-op with a single warning.
"""

from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional


def _device_stats() -> Optional[dict]:
    import jax

    try:
        dev = jax.devices()[0]
        stats = dev.memory_stats()
        return stats if stats else None
    except Exception:
        return None


@dataclass
class HeapScope:
    name: str
    bytes_at_entry: int = 0
    bytes_at_exit: int = 0
    # XLA's peak_bytes_in_use is a *global* monotone high-water mark; a
    # scope's true local peak is unobservable through the allocator API, so
    # we record the global mark at exit and report it as such.
    global_peak_at_exit: int = 0
    children: List["HeapScope"] = field(default_factory=list)


class HeapProfiler:
    """Singleton scoped profiler (mirrors the global heap profiler tree).

    Thread model (mirrors utils/timer.py): every thread records into its own
    subtree — the resetting thread owns the primary root, other threads get
    a lazily-created root listed in ``_subtrees`` — so concurrent
    ``scoped_timer`` scopes from serve worker threads can never pop another
    thread's stack.  ``report`` walks the primary tree plus each thread
    subtree."""

    _root: Optional[HeapScope] = None
    _subtrees: List[HeapScope] = []
    _tls = threading.local()
    _root_owner: int = 0
    _lock = threading.Lock()
    enabled: bool = False

    @classmethod
    def reset(cls, enabled: bool = True) -> None:
        cls._root = HeapScope("root")
        cls._subtrees = []
        cls._root_owner = threading.get_ident()
        # Forget every per-thread stack; a thread mid-scope keeps popping
        # its orphaned (pre-reset) list, which is harmless.
        cls._tls = threading.local()
        cls._tls.stack = [cls._root]
        cls.enabled = enabled

    @classmethod
    def _stack(cls) -> List[HeapScope]:
        stack = getattr(cls._tls, "stack", None)
        if stack is None:
            if threading.get_ident() == cls._root_owner:
                stack = [cls._root]
            else:
                root = HeapScope(
                    f"thread:{threading.current_thread().name or 'worker'}"
                )
                with cls._lock:
                    cls._subtrees.append(root)
                stack = [root]
            cls._tls.stack = stack
        return stack

    @classmethod
    @contextlib.contextmanager
    def scope(cls, name: str):
        if not cls.enabled or cls._root is None:
            yield
            return
        stack = cls._stack()
        stats = _device_stats()
        node = HeapScope(name, bytes_at_entry=(stats or {}).get("bytes_in_use", 0))
        stack[-1].children.append(node)
        stack.append(node)
        try:
            yield
        finally:
            stats = _device_stats()
            node.bytes_at_exit = (stats or {}).get("bytes_in_use", 0)
            node.global_peak_at_exit = (stats or {}).get("peak_bytes_in_use", 0)
            stack.pop()
            if stats:
                # Per-phase device-memory counter sample on the run trace
                # (ISSUE 5 satellite): live bytes + the global HBM high-water
                # mark at every scope boundary.
                from ..telemetry import trace as _ttrace

                rec = _ttrace.active()
                if rec is not None:
                    rec.counter("hbm_bytes", {
                        "in_use": node.bytes_at_exit,
                        "peak": node.global_peak_at_exit,
                    })

    @classmethod
    def report(cls) -> str:
        if cls._root is None:
            return "heap profiler: disabled"
        stats = _device_stats()
        lines = []
        if stats is None:
            lines.append("heap profiler: backend exposes no allocator stats")
        else:
            lines.append(
                "heap profiler: bytes_in_use=%d peak_bytes_in_use=%d"
                % (stats.get("bytes_in_use", 0), stats.get("peak_bytes_in_use", 0))
            )

        def walk(node: HeapScope, depth: int):
            # list(): an owning thread may append a sibling mid-report.
            for ch in list(node.children):
                lines.append(
                    "%s%s: entry=%d exit=%d (delta %+d, global peak %d)"
                    % (
                        "  " * depth, ch.name, ch.bytes_at_entry,
                        ch.bytes_at_exit, ch.bytes_at_exit - ch.bytes_at_entry,
                        ch.global_peak_at_exit,
                    )
                )
                walk(ch, depth + 1)

        walk(cls._root, 1)
        with cls._lock:
            subtrees = list(cls._subtrees)
        for sub in subtrees:
            lines.append(f"  {sub.name}:")
            walk(sub, 2)
        return "\n".join(lines)


def memory_summary() -> Dict[str, int]:
    """One-shot allocator summary (bytes_in_use / peak / limit when known)."""
    stats = _device_stats() or {}
    return {
        k: int(stats[k])
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        if k in stats
    }


def live_array_bytes() -> int:
    """Total bytes of live jax arrays in this process (metadata sum over
    ``jax.live_arrays()`` — no transfer).  On the CPU backend, where the
    allocator exposes no stats, this is the honest device-buffer proxy the
    capacity planner validates against (telemetry/capacity.py)."""
    import jax

    try:
        return int(sum(int(a.nbytes) for a in jax.live_arrays()))
    except Exception:  # noqa: BLE001 — accounting must never fail a run
        return 0


def _rss_bytes() -> Dict[str, int]:
    """Current and peak resident-set bytes of this process (Linux)."""
    out: Dict[str, int] = {}
    try:
        with open("/proc/self/statm") as fh:
            out["rss_bytes"] = int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except Exception:  # noqa: BLE001
        pass
    try:
        import resource

        out["peak_rss_bytes"] = (
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        )
    except Exception:  # noqa: BLE001
        pass
    return out


def watermark_backend() -> str:
    """Which measurement the watermark numbers come from (ISSUE 12
    satellite): ``tpu_hbm`` (accelerator allocator stats),
    ``cpu_allocator`` (a CPU build that exposes allocator stats), or
    ``cpu_rss_proxy`` (no allocator stats — RSS + live-array fallback).
    Consumers comparing watermarks against HBM ceilings (the ledger,
    ``tools regress`` windows, HBM_BUDGET.md tables) MUST check this label:
    a CPU-measured watermark is a host-memory proxy, never an HBM truth."""
    stats = _device_stats()
    if stats is None:
        return "cpu_rss_proxy"
    try:
        import jax

        platform = jax.devices()[0].platform
    except Exception:  # noqa: BLE001
        platform = "cpu"
    return "tpu_hbm" if platform != "cpu" else "cpu_allocator"


def watermark_report() -> Dict[str, object]:
    """HBM watermark record for bench.py:
    live bytes, the peak high-water mark, the allocator limit, and the peak's
    fraction of it — the number to cross-check against the per-chip budgets
    derived in HBM_BUDGET.md.  Every record is labeled with its measurement
    ``backend`` (ISSUE 12 satellite): allocator-less backends (most CPU
    builds) fall back to the RSS proxy + live-array bytes instead of
    silently reporting nothing — so a CPU-measured watermark can never be
    mistaken for an HBM number in the ledger or a regress window."""
    out: Dict[str, object] = dict(memory_summary())
    backend = watermark_backend()
    out["backend"] = backend
    peak = out.get("peak_bytes_in_use")
    limit = out.get("bytes_limit")
    if peak is not None and limit:
        out["peak_frac_of_limit"] = round(int(peak) / int(limit), 4)
    if backend == "cpu_rss_proxy":
        out.update(_rss_bytes())
        out["live_array_bytes"] = live_array_bytes()
    out["budget_doc"] = "HBM_BUDGET.md"
    return out
