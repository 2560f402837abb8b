"""Persistent run ledger + regression sentinel (round 13).

Every headline number so far lives in write-once artifacts (BENCH_r0x.json)
with no machinery to compare runs over time (ROADMAP item 5).  The ledger
fixes that: each bench / serve run appends ONE
compact JSON line to ``RUNS.jsonl`` — git head, device kind, the record's
numeric headline metrics, per-phase walls, and the sync / collective /
compile censuses plus the kptlint summary — and ``tools regress`` compares
the latest entry against a baseline window of earlier entries with
noise-aware thresholds, exiting nonzero on regression.  This is the
recorded-probe substrate ROADMAP item 5's future ``tools autotune`` reads
from: entries are append-only, schema-versioned, and cheap enough to write
on every run.

Direction semantics for :func:`compare`: wall/latency/cut/census metrics
are lower-better; throughput/ratio metrics are higher-better (the key
classifier below).  Wall metrics use a relative tolerance over the
baseline *median* (single-run walls on shared boxes are noisy) plus an
absolute floor; census counts are deterministic per build, so they use the
baseline *max* with zero default tolerance — one stray blocking transfer
or collective is a real regression, not noise.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

SCHEMA = 1
DEFAULT_WINDOW = 5
#: Relative wall tolerance: BENCH_r0x partition walls on this box vary by
#: ~±30% rep to rep (TPU_NOTES round 11), so anything tighter cries wolf.
DEFAULT_WALL_TOL = 0.35
DEFAULT_COUNT_TOL = 0.0
#: Quality (cut) tolerance: seeds are pinned, but refinement tie-breaks
#: can drift a few percent across environments.
DEFAULT_QUALITY_TOL = 0.10
_ABS_WALL_FLOOR_S = 0.05

_HIGHER_BETTER_MARKERS = (
    "_gps", "edges_per_sec", "_rate", "vs_baseline", "_vs_", "gbps",
    "frac_of_peak",
    # compress_ab (ISSUE 10): compression_ratio / resident-bytes reduction
    # factors — a drop means the compressed tier lost ground.
    "_ratio", "_reduction",
)
_LOWER_BETTER_MARKERS = (
    "_s", "_ms", "_cut", "cut", "count", "bytes", "_shapes", "fallbacks",
    "splits", "timed_out", "fresh",
)


def default_path() -> str:
    """RUNS.jsonl next to the repo root (overridable via KPTPU_RUNS_PATH)."""
    env = os.environ.get("KPTPU_RUNS_PATH")
    if env:
        return env
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(here, "RUNS.jsonl")


_HEAD_CACHE: Optional[str] = None


def resolve_git_head(force: bool = False) -> str:
    """The short git head of the repo this module lives in, resolved ONCE
    per process (round 20 satellite).  Fallback chain: ``KPTPU_GIT_HEAD``
    env override (tests, hermetic CI sandboxes without a git binary) →
    ``git rev-parse --short HEAD`` via subprocess → "" when neither works
    (not a checkout, no git).  Before this existed every tier-1/bench
    entry writer that did not thread its own head recorded
    ``"git_head": ""`` — making ``stale_vs_head`` meaningless — because
    :func:`build_entry` had no fallback of its own."""
    global _HEAD_CACHE
    if _HEAD_CACHE is not None and not force:
        return _HEAD_CACHE
    head = os.environ.get("KPTPU_GIT_HEAD", "")
    if not head:
        try:
            import subprocess

            here = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
            proc = subprocess.run(
                ["git", "-C", here, "rev-parse", "--short", "HEAD"],
                capture_output=True, text=True, timeout=10,
            )
            head = proc.stdout.strip() if proc.returncode == 0 else ""
        except Exception:  # noqa: BLE001 — ledger writes must never fail
            head = ""
    _HEAD_CACHE = head
    return head


def metric_direction(key: str) -> Optional[str]:
    """'up' (higher is better), 'down' (lower is better), or None
    (uncompared).  Higher-better markers win ties: ``serve_vs_single`` is a
    ratio even though it has no unit suffix."""
    if key == "value":  # the LP-microbench headline (edges/sec)
        return "up"
    for marker in _HIGHER_BETTER_MARKERS:
        if marker in key:
            return "up"
    for marker in _LOWER_BETTER_MARKERS:
        if key.endswith(marker) or marker in key:
            return "down"
    return None


def _numeric_metrics(record: dict) -> Dict[str, float]:
    out = {}
    for key, value in record.items():
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            continue
        out[key] = value
    return out


def build_entry(record: dict, *, kind: str, git_head: str = "",
                extra: dict | None = None) -> dict:
    """One compact ledger entry from a bench/serve headline record.

    Census snapshots come from the record when the measuring process
    embedded them (the bench children do) and fall back to this process's
    own counters — so both the in-process CPU path and the salvage path
    produce comparable entries.
    """
    from ..utils import collective_stats, compile_stats, sync_stats

    sync = record.get("host_sync")
    sync_totals = {
        "count": record.get("host_sync_count"),
        "bytes": record.get("host_sync_bytes"),
    }
    if sync_totals["count"] is None:
        snap = sync_stats.snapshot()
        sync_totals = {
            "count": snap["count"], "bytes": snap["bytes"],
            "implicit": snap["implicit"],
            "lane_pulls": snap["lane_pulls"],
            "shard_pulls": snap["shard_pulls"],
        }
        sync = {
            ph: row["count"] for ph, row in snap["phases"].items()
        }
    else:
        sync = {
            ph: row.get("count") for ph, row in (sync or {}).items()
        }

    coll = record.get("collectives") or collective_stats.snapshot()
    compile_snap = record.get("compiled_shape_count") or compile_stats.snapshot()
    # Executable census (round 16, ISSUE 12): the compact totals ride every
    # entry — flops/bytes of what the harvested executables WOULD do, and
    # the single-executable peak-bytes high-water mark the capacity
    # planner's ceiling checks consume.  From the record when the measuring
    # child embedded them, else this process's own registry.
    census = record.get("executable_census")
    if not census:
        census = compile_stats.executable_census_snapshot()
    census_totals = (census or {}).get("totals") or {}

    entry = {
        "schema": SCHEMA,
        "ts": round(time.time(), 1),
        "iso": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "kind": kind,
        "git_head": git_head or record.get("git_head")
        or resolve_git_head(),
        "backend": record.get("backend", ""),
        "device_kind": record.get("device_kind", ""),
        "stale_vs_head": bool(record.get("stale_vs_head", False)),
        "metrics": _numeric_metrics(record),
        "phase_walls_s": record.get("phase_walls_s") or phase_walls(),
        "sync_phases": sync,
        "sync": sync_totals,
        "collectives": {
            "count": coll.get("count", 0),
            "logical_bytes": coll.get("logical_bytes", 0),
            "by_op": {
                op: row.get("count", 0)
                for op, row in (coll.get("by_op") or {}).items()
            },
        },
        "compiled_shapes": compile_snap.get("total", 0)
        if isinstance(compile_snap, dict) else compile_snap,
        "executable_census": {
            "executables": census_totals.get("executables", 0),
            "flops": census_totals.get("flops", 0.0),
            "bytes_accessed": census_totals.get("bytes_accessed", 0.0),
            "peak_bytes_max": census_totals.get("peak_bytes_max", 0),
        },
        "lint": record.get("lint"),
    }
    if extra:
        entry.update(extra)
    return entry


def phase_walls() -> Dict[str, float]:
    """Top-level phase walls from this process's merged timer tree."""
    try:
        from ..utils import Timer

        root = Timer.global_().merged_root()
        return {
            child.name: round(child.elapsed, 4)
            for child in root.children.values()
            if child.elapsed > 0
        }
    except Exception:  # noqa: BLE001 — ledger writes must never fail a run
        return {}


def append(entry: dict, path: str | None = None) -> str:
    path = path or default_path()
    with open(path, "a") as fh:
        fh.write(json.dumps(entry) + "\n")
    return path


def read(path: str | None = None) -> List[dict]:
    path = path or default_path()
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                continue  # a torn write must not poison the whole ledger
    return out


def tail(n: int = 10, path: str | None = None) -> List[dict]:
    return read(path)[-n:]


def record_run(record: dict, *, kind: str, git_head: str = "",
               path: str | None = None) -> Optional[str]:
    """Build + append in one guarded step (the bench entry point).
    Returns the path, or None when disabled (KPTPU_LEDGER=0) or failed —
    a ledger problem must never void the run's own artifact."""
    if os.environ.get("KPTPU_LEDGER", "1") == "0":
        return None
    try:
        return append(build_entry(record, kind=kind, git_head=git_head), path)
    except Exception:  # noqa: BLE001
        return None


# -- regression sentinel -----------------------------------------------------


def _median(values: List[float]) -> float:
    vs = sorted(values)
    mid = len(vs) // 2
    if len(vs) % 2:
        return float(vs[mid])
    return (vs[mid - 1] + vs[mid]) / 2.0


def _flat_comparables(entry: dict) -> Dict[str, float]:
    """The metrics a regression check covers: the record's numeric headline
    metrics plus the census totals (namespaced so they cannot collide)."""
    out = dict(entry.get("metrics") or {})
    sync = entry.get("sync") or {}
    if sync.get("count") is not None:
        out["census.host_sync_count"] = sync["count"]
    coll = entry.get("collectives") or {}
    if coll.get("count") is not None:
        out["census.collective_count"] = coll["count"]
    if entry.get("compiled_shapes") is not None:
        out["census.compiled_shapes"] = entry["compiled_shapes"]
    for phase, wall in (entry.get("phase_walls_s") or {}).items():
        out[f"phase.{phase}_s"] = wall
    return out


def compare(latest: dict, baseline: List[dict], *,
            wall_tol: float = DEFAULT_WALL_TOL,
            count_tol: float = DEFAULT_COUNT_TOL,
            quality_tol: float = DEFAULT_QUALITY_TOL) -> List[dict]:
    """Regressions of ``latest`` vs a window of baseline entries.

    Noise model per metric class:

    - **walls / latencies** (``*_s``/``*_ms``): regression when latest
      exceeds the baseline *median* by ``wall_tol`` relatively AND by an
      absolute floor (sub-50 ms jitter never flags).
    - **censuses** (``census.*``: blocking transfers, traced collectives,
      compiled shapes): deterministic per build — regression when latest
      exceeds the baseline *max* by more than ``count_tol`` relatively.
    - **quality** (``*cut*``): ``quality_tol`` over the median.
    - **throughputs / ratios** (higher-better): regression when latest
      falls below median * (1 - wall_tol).

    Returns one dict per regression; an identical replay returns [].
    """
    regressions = []
    latest_vals = _flat_comparables(latest)
    base_vals: Dict[str, List[float]] = {}
    for entry in baseline:
        for key, value in _flat_comparables(entry).items():
            base_vals.setdefault(key, []).append(float(value))

    for key, value in latest_vals.items():
        base = base_vals.get(key)
        if not base:
            continue
        value = float(value)
        if key.startswith("census."):
            limit = max(base) * (1.0 + count_tol)
            if value > limit:
                regressions.append({
                    "metric": key, "latest": value, "baseline_max": max(base),
                    "threshold": round(limit, 4), "direction": "down",
                    "class": "census",
                })
            continue
        med = _median(base)
        if "cut" in key:
            limit = med * (1.0 + quality_tol)
            if value > limit:
                regressions.append({
                    "metric": key, "latest": value, "baseline_median": med,
                    "threshold": round(limit, 4), "direction": "down",
                    "class": "quality",
                })
            continue
        direction = metric_direction(key)
        if direction == "down":
            limit = med * (1.0 + wall_tol)
            if value > limit and value - med > _ABS_WALL_FLOOR_S:
                regressions.append({
                    "metric": key, "latest": value, "baseline_median": med,
                    "threshold": round(limit, 4), "direction": "down",
                    "class": "wall",
                })
        elif direction == "up":
            limit = med * (1.0 - wall_tol)
            if value < limit:
                regressions.append({
                    "metric": key, "latest": value, "baseline_median": med,
                    "threshold": round(limit, 4), "direction": "up",
                    "class": "throughput",
                })
    return regressions


#: Workload-configuration metrics: entries disagreeing on any of these are
#: different experiments, not baselines for each other (a scale-17 wall
#: judged against a scale-9 window would flag everything).
_CONFIG_KEYS = ("partition_scale", "partition_k", "serve_k",
                "serve_requests")


def baseline_window(entries: List[dict], latest: dict,
                    window: int = DEFAULT_WINDOW) -> List[dict]:
    """The comparable baseline for ``latest``: the most recent earlier
    entries of the same kind AND backend (a cpu-fallback run must never be
    judged against a TPU window) AND the same workload configuration
    (scale/k), newest last, at most ``window``."""
    latest_cfg = {
        key: (latest.get("metrics") or {}).get(key) for key in _CONFIG_KEYS
    }

    def comparable(entry: dict) -> bool:
        if (
            entry is latest
            or entry.get("kind") != latest.get("kind")
            or entry.get("backend") != latest.get("backend")
        ):
            return False
        metrics = entry.get("metrics") or {}
        return all(
            value is None or metrics.get(key) is None
            or metrics.get(key) == value
            for key, value in latest_cfg.items()
        )

    return [e for e in entries if comparable(e)][-window:]


# -- ledger analytics (round 20): trend + regression attribution -------------
#
# Everything below is pure stdlib over the already-parsed JSONL entries —
# `tools report` must run on a machine with no jax at all (CI dashboards,
# laptops reading a synced RUNS.jsonl), so nothing here may import from the
# partitioner.

#: A trend verdict needs a sustained relative move; one-entry jitter below
#: this fraction of the prior median reads as "flat".
TREND_TOL = 0.10

#: Attribution floors: a phase wall must move by this many seconds and a
#: census count by at least one unit before it can be named a suspect —
#: without the floors, micro-phases with ~0 medians dominate every ranking
#: through huge relative deltas that explain nothing.
_ATTR_WALL_FLOOR_S = 0.02
_ATTR_COUNT_FLOOR = 1.0


def config_signature(entry: dict) -> tuple:
    """The workload-configuration fingerprint of an entry — the
    ``_CONFIG_KEYS`` it actually carries, as a hashable tuple.  Two
    entries with the same (kind, backend, signature) are the same
    experiment over time; everything else is apples-to-oranges."""
    metrics = entry.get("metrics") or {}
    return tuple(
        (key, metrics.get(key)) for key in _CONFIG_KEYS
        if metrics.get(key) is not None
    )


def group_entries(entries: List[dict]) -> Dict[tuple, List[dict]]:
    """Entries grouped by (kind, backend, config signature), file order
    (= chronological order — `append` only ever appends) preserved."""
    groups: Dict[tuple, List[dict]] = {}
    for entry in entries:
        key = (str(entry.get("kind", "")), str(entry.get("backend", "")),
               config_signature(entry))
        groups.setdefault(key, []).append(entry)
    return groups


def metric_trends(entries: List[dict]) -> Dict[str, dict]:
    """Per-metric trajectory over one group's entries (chronological).

    For each comparable key present in >= 2 entries: first/last/min/max,
    the median of all entries *before* the last one (the trend baseline),
    the relative delta of the last entry vs that median, and a verdict —
    ``regressed`` / ``improved`` when the move exceeds :data:`TREND_TOL`
    in the metric's bad/good direction, else ``flat``.  Config keys are
    constant within a group by construction and are skipped."""
    series: Dict[str, List[float]] = {}
    for entry in entries:
        for key, value in _flat_comparables(entry).items():
            if key in _CONFIG_KEYS:
                continue
            series.setdefault(key, []).append(float(value))
    trends: Dict[str, dict] = {}
    for key, values in series.items():
        if len(values) < 2:
            continue
        last = values[-1]
        prior_median = _median(values[:-1])
        if prior_median != 0:
            delta_rel = (last - prior_median) / abs(prior_median)
        else:
            delta_rel = 0.0 if last == 0 else float("inf")
        direction = metric_direction(key)
        verdict = "flat"
        if abs(delta_rel) > TREND_TOL and direction != "neutral":
            worse = delta_rel > 0 if direction == "down" else delta_rel < 0
            verdict = "regressed" if worse else "improved"
        trends[key] = {
            "n": len(values),
            "first": values[0],
            "last": last,
            "min": min(values),
            "max": max(values),
            "prior_median": prior_median,
            "delta_rel": (round(delta_rel, 4)
                          if delta_rel != float("inf") else None),
            "direction": direction,
            "verdict": verdict,
        }
    return trends


def attribute(latest: dict, baseline: List[dict],
              regressions: Optional[List[dict]] = None,
              top: int = 3) -> List[dict]:
    """Regression attribution: for each *headline* regression of ``latest``
    vs ``baseline``, rank the co-moving ``phase.*`` walls and ``census.*``
    counts as suspects.

    The phase walls and censuses are the only sub-metrics the ledger
    carries, and in practice one of them is where a wall regression
    actually lives ("partition_wall_s moved because phase.refine_s
    doubled") or what a census regression *is* ("host syncs went from 0
    to 4").  A suspect must itself have moved beyond an absolute floor
    (see ``_ATTR_*_FLOOR``); suspects are ranked by relative move, and
    each regression names at most ``top`` of them."""
    regs = regressions if regressions is not None else compare(latest, baseline)
    if not regs:
        return []
    latest_vals = _flat_comparables(latest)
    base_vals: Dict[str, List[float]] = {}
    for entry in baseline:
        for key, value in _flat_comparables(entry).items():
            base_vals.setdefault(key, []).append(float(value))

    suspects: List[dict] = []
    for key, base in base_vals.items():
        if not (key.startswith("phase.") or key.startswith("census.")):
            continue
        if key not in latest_vals:
            continue
        cur = float(latest_vals[key])
        med = _median(base)
        delta = cur - med
        floor = (_ATTR_COUNT_FLOOR if key.startswith("census.")
                 else _ATTR_WALL_FLOOR_S)
        if abs(delta) < floor:
            continue
        rel = delta / abs(med) if med != 0 else float("inf")
        suspects.append({
            "metric": key,
            "latest": cur,
            "baseline_median": med,
            "delta": round(delta, 6),
            "delta_rel": round(rel, 4) if rel != float("inf") else None,
        })
    suspects.sort(
        key=lambda s: (s["delta_rel"] is None,
                       -(abs(s["delta_rel"]) if s["delta_rel"] is not None
                         else abs(s["delta"]))),
    )

    out: List[dict] = []
    for reg in regs:
        metric = reg["metric"]
        if metric.startswith("census."):
            # a census regression IS its own attribution — name only itself
            mine = [s for s in suspects if s["metric"] == metric]
        elif metric.startswith("phase."):
            mine = [s for s in suspects if s["metric"] == metric]
        else:
            # headline metric: every moved sub-metric is a candidate, but a
            # wall regression is best explained by walls and a count
            # regression by counts — keep the full ranked list and let the
            # floor + ranking do the work.
            mine = [s for s in suspects if s["metric"] != metric]
        out.append({"metric": metric, "suspects": mine[:top]})
    return out


def build_report(entries: Optional[List[dict]] = None, *,
                 path: Optional[str] = None,
                 window: int = DEFAULT_WINDOW,
                 kinds: Optional[List[str]] = None) -> dict:
    """The full analytics report over a ledger: one row per
    (kind, backend, config) group with its metric trends, the latest
    entry's regressions vs its baseline window, and per-regression
    attribution.  ``kinds`` filters groups (e.g. ["tier1", "chaos"])."""
    if entries is None:
        entries = read(path)
    if kinds:
        wanted = set(kinds)
        entries = [e for e in entries if str(e.get("kind", "")) in wanted]
    groups = group_entries(entries)

    rows: List[dict] = []
    for (kind, backend, cfg), group in sorted(
            groups.items(), key=lambda kv: (kv[0][0], kv[0][1], str(kv[0][2]))):
        latest = group[-1]
        base = baseline_window(group, latest, window)
        regs = compare(latest, base) if base else []
        rows.append({
            "kind": kind,
            "backend": backend,
            "config": dict(cfg),
            "entries": len(group),
            "first_iso": group[0].get("iso", ""),
            "latest_iso": latest.get("iso", ""),
            "latest_git_head": latest.get("git_head", ""),
            "trends": metric_trends(group),
            "regressions": regs,
            "attribution": attribute(latest, base, regs) if regs else [],
        })

    regressed = [r for r in rows if r["regressions"]]
    report = {
        "schema": SCHEMA,
        "window": int(window),
        "summary": {
            "entries": len(entries),
            "groups": len(rows),
            "regressed_groups": len(regressed),
            "total_regressions": sum(len(r["regressions"]) for r in rows),
            "trend_regressed_metrics": sum(
                1 for r in rows for t in r["trends"].values()
                if t["verdict"] == "regressed"),
            "trend_improved_metrics": sum(
                1 for r in rows for t in r["trends"].values()
                if t["verdict"] == "improved"),
        },
        "groups": rows,
    }
    return report


def _fmt_num(value: float) -> str:
    if value is None:
        return "-"
    if value == int(value) and abs(value) < 1e9:
        return str(int(value))
    return f"{value:.4g}"


def render_report_markdown(report: dict) -> str:
    """Markdown rendering of :func:`build_report` — trend tables per
    group, regressions with their attributed suspects inline."""
    lines: List[str] = []
    s = report["summary"]
    lines.append("# Ledger report")
    lines.append("")
    lines.append(
        f"{s['entries']} entries, {s['groups']} groups, "
        f"{s['regressed_groups']} regressed "
        f"({s['total_regressions']} regressions); trends: "
        f"{s['trend_regressed_metrics']} regressed / "
        f"{s['trend_improved_metrics']} improved "
        f"(window={report['window']})")
    for row in report["groups"]:
        cfg = " ".join(f"{k}={v}" for k, v in row["config"].items())
        title = f"{row['kind']} / {row['backend'] or '?'}"
        if cfg:
            title += f" / {cfg}"
        lines.append("")
        lines.append(f"## {title}")
        lines.append("")
        head = row["latest_git_head"] or "?"
        lines.append(
            f"{row['entries']} entries "
            f"({row['first_iso']} .. {row['latest_iso']}), "
            f"latest head `{head}`")
        if row["trends"]:
            lines.append("")
            lines.append(
                "| metric | n | first | median | latest | delta | verdict |")
            lines.append("|---|---|---|---|---|---|---|")
            for key in sorted(
                    row["trends"],
                    key=lambda k: (row["trends"][k]["verdict"] == "flat", k)):
                t = row["trends"][key]
                delta = ("inf" if t["delta_rel"] is None
                         else f"{t['delta_rel'] * 100:+.1f}%")
                lines.append(
                    f"| {key} | {t['n']} | {_fmt_num(t['first'])} "
                    f"| {_fmt_num(t['prior_median'])} "
                    f"| {_fmt_num(t['last'])} | {delta} | {t['verdict']} |")
        if row["regressions"]:
            lines.append("")
            lines.append("### Regressions (latest vs baseline window)")
            attribution = {a["metric"]: a["suspects"]
                           for a in row["attribution"]}
            for reg in row["regressions"]:
                base = reg.get("baseline_median",
                               reg.get("baseline_max"))
                lines.append(
                    f"- **{reg['metric']}** [{reg['class']}]: "
                    f"{_fmt_num(reg['latest'])} vs baseline "
                    f"{_fmt_num(base)} (threshold {_fmt_num(reg['threshold'])})")
                for sus in attribution.get(reg["metric"], []):
                    rel = ("inf" if sus["delta_rel"] is None
                           else f"{sus['delta_rel'] * 100:+.1f}%")
                    lines.append(
                        f"  - suspect {sus['metric']}: "
                        f"{_fmt_num(sus['baseline_median'])} -> "
                        f"{_fmt_num(sus['latest'])} ({rel})")
    lines.append("")
    return "\n".join(lines)
