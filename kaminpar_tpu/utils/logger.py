"""Logger with verbosity ladder and the parseable ``RESULT`` line.

Mirrors the reference's ``Logger`` (``kaminpar-common/logger.h:34-50``) and
``OutputLevel::{QUIET..DEBUG}`` (kaminpar.h:849-855).  The single-line
``RESULT cut=... imbalance=... feasible=... k=...`` record
(kaminpar-shm/kaminpar.cc:48) is the de-facto experiment interface and is
reproduced byte-compatibly by :func:`log_result_line`.

Structured mode (ISSUE 5 satellite): ``KAMINPAR_TPU_LOG=json`` switches every
line to a one-object-per-line JSON record (``{"ts", "level", "msg", ...}``;
the RESULT line additionally carries its fields as ``"event": "result"``)
so bench and serve logs are machine-parseable.  Default plain-text output
is byte-identical to before.
"""

from __future__ import annotations

import enum
import json
import os
import sys
import time


class OutputLevel(enum.IntEnum):
    QUIET = 0
    PROGRESS = 1
    APPLICATION = 2
    EXPERIMENT = 3
    DEBUG = 4


def json_mode() -> bool:
    """Structured-log switch, read per call so tests and long-lived
    processes can flip it via the environment."""
    return os.environ.get("KAMINPAR_TPU_LOG", "").strip().lower() == "json"


def _json_record(msg: str, level: str, **extra) -> str:
    rec = {"ts": round(time.time(), 3), "level": level, "msg": msg}
    rec.update(extra)
    return json.dumps(rec)


class Logger:
    level: OutputLevel = OutputLevel.APPLICATION
    stream = sys.stdout

    @classmethod
    def log(cls, msg: str, level: OutputLevel = OutputLevel.APPLICATION) -> None:
        if cls.level >= level:
            if json_mode():
                msg = _json_record(msg, level.name.lower())
            print(msg, file=cls.stream, flush=True)

    @classmethod
    def warning(cls, msg: str) -> None:
        if cls.level > OutputLevel.QUIET:
            line = (
                _json_record(msg, "warning")
                if json_mode()
                else f"[Warning] {msg}"
            )
            print(line, file=sys.stderr, flush=True)

    @classmethod
    def error(cls, msg: str) -> None:
        line = _json_record(msg, "error") if json_mode() else f"[Error] {msg}"
        print(line, file=sys.stderr, flush=True)


def log_result_line(cut: int, imbalance: float, feasible: bool, k: int, seconds: float) -> str:
    """Reference: kaminpar-shm/kaminpar.cc:48."""
    line = (
        f"RESULT cut={int(cut)} imbalance={imbalance} feasible={int(feasible)} "
        f"k={int(k)} time={seconds}"
    )
    if json_mode():
        if Logger.level >= OutputLevel.EXPERIMENT:
            print(
                _json_record(
                    line, "experiment", event="result", cut=int(cut),
                    imbalance=float(imbalance), feasible=bool(feasible),
                    k=int(k), time=float(seconds),
                ),
                file=Logger.stream, flush=True,
            )
    else:
        Logger.log(line, OutputLevel.EXPERIMENT)
    # The run trace records the RESULT as an instant event so the final
    # quality lands next to the per-level probes.
    from ..telemetry import trace as _ttrace

    rec = _ttrace.active()
    if rec is not None:
        rec.instant(
            "result", cut=int(cut), imbalance=float(imbalance),
            feasible=bool(feasible), k=int(k), seconds=round(float(seconds), 4),
        )
    return line
