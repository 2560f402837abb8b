"""Hang-forensics flight recorder (ISSUE 12 tentpole c).

A benchmark child killed at its deadline used to leave no phase, no stack
and no cause behind.  This module gives every killable measurement child a
black box:

- a **daemon heartbeat thread** appends one JSON line per tick to a
  sidecar file (monotonic + wall timestamps, the current phase from the
  sync-stats phase board — i.e. the timer stack — per thread, RSS), so a
  SIGKILL'd process leaves a record of *what it was doing when it died*;
- ``faulthandler.dump_traceback_later`` armed just under the parent's
  kill timeout dumps every thread's Python stack to a second sidecar
  moments before the kill lands;
- :func:`read_dossier` (run by the parent AFTER the kill) assembles both
  plus an env/backend fingerprint into the dossier ``bench.py`` attaches
  to every killed child, and :func:`classify_phase` maps the dying phase
  to an init / compile / execute hang class.

The module is **pure stdlib at import time** (no jax, no package-relative
imports) so a child can load it by file path and start heartbeating
BEFORE ``import jax`` — backend-init hangs are precisely the case that
must not escape the recorder.  The phase board is read lazily
and best-effort: until kaminpar_tpu is imported there are no phases and
the explicit :meth:`FlightRecorder.note` marker (e.g. ``backend_init``)
carries the attribution.

Heartbeat wall-attribution semantics (TPU_NOTES.md round 16): the phase in
a heartbeat line is whatever the dying process's timer stack showed at the
tick — attribution granularity is one heartbeat interval, and a phase that
both opened and closed between ticks is invisible.  Good enough for
20-minute hangs; not a profiler.
"""

from __future__ import annotations

import faulthandler
import json
import os
import threading
import time
from typing import Dict, List, Optional

#: Env fingerprint keys worth carrying in a dossier — the knobs that decide
#: which backend a child initializes and what it would have measured.
ENV_FINGERPRINT_KEYS = (
    "JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR", "KPTPU_BENCH_SCALE",
    "KPTPU_BENCH_FULL_SCALE", "KPTPU_BENCH_SHARD_NATIVE",
    "KAMINPAR_TPU_LANE_STACK", "KAMINPAR_TPU_DEVICE_DECODE",
)

_PAGE = 4096
try:
    _PAGE = os.sysconf("SC_PAGE_SIZE")
except (ValueError, OSError, AttributeError):  # pragma: no cover
    pass


def _rss_bytes() -> Optional[int]:
    try:
        with open("/proc/self/statm") as fh:
            return int(fh.read().split()[1]) * _PAGE
    except Exception:  # noqa: BLE001 — heartbeats must never raise
        return None


def _board_phases() -> Dict[str, str]:
    """Best-effort read of the sync-stats phase board ({thread: phase});
    empty until kaminpar_tpu is imported (a child hanging in backend init
    has no phases yet — the explicit note covers it)."""
    try:
        import sys

        sync_stats = sys.modules.get("kaminpar_tpu.utils.sync_stats")
        if sync_stats is None:
            return {}
        return {k: v for k, v in sync_stats.current_phases().items() if v}
    except Exception:  # noqa: BLE001
        return {}


class FlightRecorder:
    """One heartbeat sidecar + one armed stack dump per measurement child.

    Usage (a measurement child)::

        rec = FlightRecorder(hb_path, interval_s=5.0,
                             stack_path=stack_path, stack_after_s=1170.0)
        rec.start()
        rec.note("backend_init")
        import jax; jax.devices()          # may hang -> heartbeats keep
        rec.note("bench")                  # flowing, stack dumps at 1170 s
    """

    def __init__(self, path: str, interval_s: float = 10.0,
                 stack_path: str = "", stack_after_s: Optional[float] = None):
        self.path = path
        self.interval_s = max(float(interval_s), 0.05)
        self.stack_path = stack_path
        self.stack_after_s = stack_after_s
        self._note = "startup"
        self._seq = 0
        self._t0 = time.monotonic()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._stack_file = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "FlightRecorder":
        if self._thread is not None:
            return self
        if self.stack_path and self.stack_after_s:
            try:
                # Keep the handle alive for faulthandler; the dump fires
                # once, just under the parent's kill timeout, with every
                # thread's stack.
                self._stack_file = open(self.stack_path, "w")
                faulthandler.dump_traceback_later(
                    float(self.stack_after_s), repeat=False,
                    file=self._stack_file, exit=False,
                )
            except Exception:  # noqa: BLE001 — forensics must not kill the run
                self._stack_file = None
        self.beat()  # line 0 proves the recorder armed before any hang
        self._thread = threading.Thread(
            target=self._loop, name="kpt-flight-recorder", daemon=True
        )
        self._thread.start()
        return self

    def rearm_stack_dump(self, after_s: float) -> None:
        """Re-arm the single faulthandler timer for a LATER deadline (a
        child re-arms once backend init succeeds: the init-phase dump
        slot no longer applies and an execute-phase hang killed at the
        attempt timeout must carry its own dying stack, not a stale
        init-era one).  Truncates the sidecar so only the newest dump
        survives."""
        if after_s <= 0:
            return
        try:
            if self._stack_file is not None:
                faulthandler.cancel_dump_traceback_later()
                self._stack_file.close()
            if not self.stack_path:
                return
            self._stack_file = open(self.stack_path, "w")
            faulthandler.dump_traceback_later(
                float(after_s), repeat=False, file=self._stack_file,
                exit=False,
            )
            self.stack_after_s = float(after_s)
        except Exception:  # noqa: BLE001 — forensics must not kill the run
            self._stack_file = None

    def stop(self) -> None:
        self._stop.set()
        if self.stack_after_s and self._stack_file is not None:
            try:
                faulthandler.cancel_dump_traceback_later()
                self._stack_file.close()
            except Exception:  # noqa: BLE001
                pass
            self._stack_file = None

    def note(self, phase: str) -> None:
        """Explicit phase marker for stretches the timer stack cannot cover
        (pre-import backend init, child startup); beats immediately so the
        transition itself is on record."""
        self._note = str(phase)
        self.beat()

    # -- heartbeat ---------------------------------------------------------

    def beat(self) -> None:
        """Append one heartbeat line now (also called each tick)."""
        phases = _board_phases()
        main_phase = phases.get("MainThread") or self._note
        line = {
            "seq": self._seq,
            "t_mono_s": round(time.monotonic() - self._t0, 3),
            "ts": round(time.time(), 3),
            "iso": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "phase": main_phase,
            "note": self._note,
            "rss_bytes": _rss_bytes(),
        }
        if phases:
            line["phases"] = phases
        self._seq += 1
        try:
            with open(self.path, "a") as fh:
                fh.write(json.dumps(line) + "\n")
        except Exception:  # noqa: BLE001 — a full disk must not kill the run
            pass

    def _loop(self) -> None:
        # The tick body runs under the registered "heartbeat" phase: the
        # recorder itself must never pull from the device, and attributing
        # its (empty) sync activity keeps any future stray loud.
        while not self._stop.wait(self.interval_s):
            try:
                import sys

                sync_stats = sys.modules.get("kaminpar_tpu.utils.sync_stats")
                if sync_stats is not None:
                    with sync_stats.scoped("heartbeat"):
                        self.beat()
                else:
                    self.beat()
            except Exception:  # noqa: BLE001
                pass


def arm_from_env() -> Optional[FlightRecorder]:
    """Start a recorder from the standard env contract (the bench child's
    entry): ``KPTPU_FLIGHT_RECORDER`` (heartbeat path; unset = no
    recorder), ``KPTPU_HEARTBEAT_S``, ``KPTPU_FLIGHT_STACK``,
    ``KPTPU_FLIGHT_STACK_AFTER_S``."""
    path = os.environ.get("KPTPU_FLIGHT_RECORDER", "")
    if not path:
        return None
    try:
        rec = FlightRecorder(
            path,
            interval_s=float(os.environ.get("KPTPU_HEARTBEAT_S", 10.0)),
            stack_path=os.environ.get("KPTPU_FLIGHT_STACK", ""),
            stack_after_s=float(os.environ.get("KPTPU_FLIGHT_STACK_AFTER_S", 0))
            or None,
        )
        return rec.start()
    except Exception:  # noqa: BLE001 — forensics must not kill the child
        return None


# -- parent-side sidecar contract -------------------------------------------

#: Fraction of the kill timeout the stack dump is armed early (absorbs the
#: child's startup skew — the dump must be on disk before SIGKILL lands).
STACK_MARGIN_FRAC = 0.2


def child_sidecar_env(base_path: str, kill_after_s: float,
                      attempt_after_s: Optional[float] = None,
                      heartbeat_s: Optional[float] = None):
    """The ONE definition of the parent->child sidecar env contract
    (consumed by :func:`arm_from_env` in the child; bench's `_run_child`
    builds it here).  Returns ``(env_updates, hb_path, stack_path)``;
    stale sidecars from a previous attempt are removed.
    ``attempt_after_s`` (a post-backend-init deadline) arms the re-arm
    contract."""
    hb_path = base_path + ".hb.jsonl"
    stack_path = base_path + ".stack"
    cleanup_sidecars(hb_path, stack_path)
    env = {
        "KPTPU_FLIGHT_RECORDER": hb_path,
        "KPTPU_FLIGHT_STACK": stack_path,
        "KPTPU_FLIGHT_STACK_AFTER_S":
            str(max(1.0, kill_after_s * (1.0 - STACK_MARGIN_FRAC))),
        "KPTPU_HEARTBEAT_S": str(
            heartbeat_s if heartbeat_s is not None
            else max(0.2, min(10.0, kill_after_s / 10.0))
        ),
    }
    if attempt_after_s is not None:
        env["KPTPU_FLIGHT_STACK_AFTER_OK_S"] = str(
            max(1.0, attempt_after_s * (1.0 - STACK_MARGIN_FRAC))
        )
    return env, hb_path, stack_path


def cleanup_sidecars(hb_path: str, stack_path: str = "") -> None:
    for path in (hb_path, stack_path):
        if not path:
            continue
        try:
            os.remove(path)
        except OSError:
            pass


# -- parent-side dossier assembly -------------------------------------------


def classify_phase(phase: Optional[str]) -> str:
    """Map a dying phase name to a hang class: ``init`` (backend/device bring-up), ``compile``
    (warmup/AOT/trace), ``execute`` (a real pipeline phase)."""
    p = (phase or "").lower()
    if p in ("", "startup", "backend_init", "devices", "init"):
        return "init"
    if any(tag in p for tag in ("warmup", "compile", "aot", "lowering",
                                "trace_export")):
        return "compile"
    return "execute"


def read_dossier(hb_path: str, stack_path: str = "",
                 tail_lines: int = 30) -> Optional[dict]:
    """Assemble the post-mortem dossier of a killed child: last heartbeat
    (phase, RSS, age), heartbeat count, the stack dump's tail, and the env
    fingerprint.  None when no heartbeat line survives (the child died
    before arming — itself a datum, recorded by the caller)."""
    last = None
    count = 0
    try:
        with open(hb_path) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    last = json.loads(line)
                    count += 1
                except ValueError:
                    continue  # a torn final write is expected under SIGKILL
    except OSError:
        return None
    if last is None:
        return None
    dossier: dict = {
        "phase": last.get("phase") or last.get("note"),
        "phase_class": classify_phase(last.get("phase") or last.get("note")),
        "heartbeats": count,
        "last_heartbeat": {
            k: last.get(k)
            for k in ("seq", "t_mono_s", "iso", "rss_bytes", "phases")
            if last.get(k) is not None
        },
        "env": {
            k: os.environ[k] for k in ENV_FINGERPRINT_KEYS if k in os.environ
        },
    }
    tail = _stack_tail(stack_path, tail_lines)
    if tail:
        dossier["stack_tail"] = tail
    return dossier


def _stack_tail(stack_path: str, tail_lines: int) -> List[str]:
    if not stack_path:
        return []
    try:
        with open(stack_path) as fh:
            lines = [ln.rstrip() for ln in fh.readlines() if ln.strip()]
    except OSError:
        return []
    return lines[-int(tail_lines):]
