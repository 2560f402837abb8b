"""Run one cell of the benchmark once and print its result line.

    python -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip.  It generates the cell's graph from ``--seed``
as a host CSR, partitions it once to warm every program the window uses
(set-up), then partitions it back to back for ``--seconds``: each partition
builds a fresh ``CSRGraph`` from the host arrays and a fresh
``kaminpar_tpu.KaMinPar``, calls ``compute_partition`` and ends with the
partition on the host.  Once the window has closed and the peak device
memory has been read, every partition of the window is checked against the
NumPy reference (``reference.py``).  With ``--trace 1`` the window's first
partition runs under the JAX profiler and the per-layer metrics are
printed in place of the end-to-end ones.

The last stdout line is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with its limit).
The checks are also the last lines on stderr.  Without a TPU, or with fewer
chips than the cell asks for, it exits with code 2 and prints no result;
``--allow-cpu`` lets the tests run it on the CPU.
"""

from __future__ import annotations

import time

_PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

from perfbench import reference  # noqa: E402
from perfbench.spec import ROOT, Cell, SpecError, load_cell  # noqa: E402

_RESULT = re.compile(r"RESULT cut=(\d+)")
# Timer scopes (children of the program's ``partitioning`` scope) recorded
# per partition; metrics/ read them by these names.
_PHASE_ROOT = "partitioning"


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Partition:
    """One partition of the window, as timed and as checked."""

    wall_s: float
    part: object = None  # host ndarray, dropped once checked
    reported_cut: int | None = None
    error: str | None = None
    readings: dict = field(default_factory=dict)  # reference.check()
    phases: dict = field(default_factory=dict)  # timer scope -> seconds
    syncs: int = 0
    pool_calls: int = 0
    compiles: int = 0

    @property
    def ok(self) -> bool:
        return self.error is None and self.readings.get("valid", False)


@dataclass
class Run:
    """What one run collected; ``metrics/<name>.py`` read from it."""

    cell: Cell
    seed: int
    n: int
    edges: int  # undirected edges of the graph
    setup_s: float
    window_s: float
    partitions: list
    peak_bytes: int | None
    trace: object = None  # trace_reduce.TraceSummary
    warmup: dict = field(default_factory=dict)

    @property
    def done(self) -> list:
        return [p for p in self.partitions if p.ok]


class CompileCounter:
    """Counts XLA compiles (and loads from the persistent cache) through
    ``jax.monitoring``; one per process, since listeners cannot be
    removed."""

    _instance = None

    def __init__(self):
        import jax.monitoring as monitoring

        self.events = 0
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.events += 1
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = CompileCounter()
        return cls._instance


def _say(line: str) -> None:
    print(line, flush=True)


def chip_devices(chips: int, allow_cpu: bool) -> list:
    """The devices the cell runs on; raises NoChip without a TPU (or the
    CPU where ``allow_cpu``) holding ``chips`` devices."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not (allow_cpu and platform == "cpu"):
        raise NoChip(f"JAX found platform {platform!r}, not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def partition_once(row_ptr, col_idx, traffic: dict):
    """One user-visible partition: fresh graph and facade from the host
    arrays, ``compute_partition``, result on the host.  Returns (partition,
    the cut the program reported)."""
    import jax

    import kaminpar_tpu as kp
    from kaminpar_tpu.graph.csr import from_numpy_csr
    from kaminpar_tpu.utils.logger import Logger, OutputLevel

    with jax.profiler.TraceAnnotation("bench/build_graph"):
        graph = from_numpy_csr(row_ptr, col_idx)
    with jax.profiler.TraceAnnotation("bench/set_graph"):
        solver = kp.KaMinPar(traffic["preset"])
        solver.set_graph(graph)
    log = io.StringIO()
    saved = Logger.stream, Logger.level
    Logger.stream, Logger.level = log, OutputLevel.EXPERIMENT
    try:
        with jax.profiler.TraceAnnotation("bench/compute_partition"):
            part = np.asarray(
                solver.compute_partition(
                    k=int(traffic["k"]), epsilon=float(traffic["epsilon"])
                )
            )
    finally:
        Logger.stream, Logger.level = saved
    found = _RESULT.findall(log.getvalue())
    return part, int(found[-1]) if found else None


def _program_counters() -> dict:
    from kaminpar_tpu.ops.bipartition import pool_stats_snapshot
    from kaminpar_tpu.utils import sync_stats

    return {
        "syncs": sync_stats.snapshot()["count"],
        "pool_calls": pool_stats_snapshot()["calls"],
        "compiles": CompileCounter.get().events,
    }


def _phases() -> dict:
    from kaminpar_tpu.utils.timer import Timer

    node = Timer.global_().merged_root().children.get(_PHASE_ROOT)
    if node is None:
        return {}
    return {name: child.elapsed for name, child in node.children.items()}


class _PartitionTrace:
    """The JAX profiler over one whole partition.

    The TPU trace mode ``TRACE_ONLY_XLA`` records one event per program run
    and none per op: a v5e in its default mode wrote 457,625 op events for
    the 0.75 s of one rmat scale 10, k=4 partition.  Host markers
    ``bench/trace_start`` and ``bench/trace_end`` bound the window that
    ``trace_reduce`` reads.  The Python function tracer is off.
    """

    def __init__(self, trace_dir: str):
        import jax

        self._jax = jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation("bench/trace_start"):
            pass

    def finish(self) -> None:
        with self._jax.profiler.TraceAnnotation("bench/trace_end"):
            pass
        self._jax.profiler.stop_trace()


def _timed_partition(row_ptr, col_idx, traffic, trace_dir=None) -> Partition:
    import jax

    before = _program_counters()
    tracer = _PartitionTrace(trace_dir) if trace_dir is not None else None
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("bench/partition"):
            part, reported = partition_once(row_ptr, col_idx, traffic)
        rec = Partition(time.perf_counter() - t0, part, reported)
    except Exception:  # noqa: BLE001 — a failed partition is counted, not fatal
        rec = Partition(time.perf_counter() - t0, error=traceback.format_exc())
        sys.stderr.write(rec.error)
    finally:
        if tracer is not None:
            tracer.finish()
    after = _program_counters()
    rec.phases = _phases()
    for key in before:
        setattr(rec, key, after[key] - before[key])
    return rec


def _traffic(cell: Cell, epsilon: float | None) -> dict:
    traffic = dict(cell.traffic)
    if traffic.get("loop") != "closed" or int(traffic.get("clients", 1)) != 1:
        raise SpecError(
            f"traffic {cell.traffic_name!r}: only a closed loop of one "
            "client is supported"
        )
    if epsilon is not None:
        traffic["epsilon"] = epsilon
    return traffic


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices: list, epsilon: float | None = None,
             trace_out: str | None = None, warm_up: bool = True) -> Run:
    """Set-up, window and checks of one run.  ``epsilon`` overrides the
    balance the program is asked for (the control); the checks keep the
    traffic's own.  The control, which times nothing, skips the warm-up
    partition (``warm_up=False``)."""
    compiles = CompileCounter.get()
    traffic = _traffic(cell, epsilon)
    t0 = time.perf_counter()
    row_ptr, col_idx = cell.family.generate(cell.config["params"], seed)
    n, edges = len(row_ptr) - 1, len(col_idx) // 2
    _say(f"graph: {cell.config_name} n={n} edges={edges} "
         f"generation_s={time.perf_counter() - t0}")

    warmup = {}
    if warm_up:
        c0, s0, h0 = compiles.events, compiles.seconds, compiles.cache_hits
        warm = _timed_partition(row_ptr, col_idx, traffic)
        if warm.error is not None:
            raise RuntimeError("the warm-up partition failed")
        from kaminpar_tpu.utils import compile_stats

        warmup = {
            "wall_s": warm.wall_s,
            "compile_events": compiles.events - c0,
            "compile_s": compiles.seconds - s0,
            "cache_hits": compiles.cache_hits - h0,
            "shapes": compile_stats.snapshot().get("total"),
        }
        _say("warm-up: " + " ".join(f"{k}={v}" for k, v in warmup.items()))
        del warm
    setup_s = time.perf_counter() - _PROCESS_T0

    partitions = []
    with tempfile.TemporaryDirectory() as tmp:
        trace_dir = str(Path(tmp) / "trace") if trace else None
        w0 = time.perf_counter()
        while True:
            rec = _timed_partition(
                row_ptr, col_idx, traffic,
                trace_dir if not partitions else None,
            )
            partitions.append(rec)
            elapsed = time.perf_counter() - w0
            if elapsed + rec.wall_s > seconds:
                break
        window_s = time.perf_counter() - w0
        peak = _peak_bytes(devices)
        summary = None
        # The CPU backend writes no device plane; its traces hold nothing
        # to reduce.
        if trace and devices[0].platform != "cpu":
            summary = _reduce_trace(trace_dir, trace_out)
    _say(f"window: partitions={len(partitions)} window_s={window_s} "
         f"walls_s={[p.wall_s for p in partitions]}")

    for rec in partitions:
        if rec.error is None:
            rec.readings = reference.check(
                row_ptr, col_idx, rec.part, rec.reported_cut,
                int(traffic["k"]), float(cell.traffic["epsilon"]),
            )
        rec.part = None
    return Run(cell, seed, n, edges, setup_s, window_s, partitions, peak,
               summary, warmup)


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _reduce_trace(trace_dir: str, trace_out: str | None):
    from perfbench.trace_reduce import reduce_file

    found = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not found:
        raise RuntimeError("the profiler wrote no .xplane.pb")
    if trace_out:
        Path(trace_out).mkdir(parents=True, exist_ok=True)
        shutil.copy(found[0], trace_out)
    t0 = time.perf_counter()
    summary = reduce_file(found[0])
    _say(f"trace: bytes={Path(found[0]).stat().st_size} "
         f"reduce_s={time.perf_counter() - t0}")
    return summary


def _limits(run: Run) -> dict:
    """The limit of each reading of one partition (``reference.check``)."""
    k = int(run.cell.traffic["k"])
    eps = float(run.cell.traffic["epsilon"])
    return {
        "cut_mismatch": 0,
        "max_block_weight": reference.max_block_weight(run.n, k, eps),
        "empty_blocks": 0,
    }


def _passes(p: Partition, limits: dict) -> bool:
    return p.ok and all(
        p.readings[key] is not None and p.readings[key] <= limit
        for key, limit in limits.items()
    )


def checks(run: Run) -> dict:
    """Each number compared, worst over the window, beside its limit.
    ``bad_partitions`` counts partitions that raised, came back malformed
    or without a reported cut."""
    limits = _limits(run)
    valid = [p.readings for p in run.partitions if p.ok]
    out = {"bad_partitions": {
        "value": sum(1 for p in run.partitions
                     if not p.ok or p.readings["cut_mismatch"] is None),
        "limit": 0,
    }}
    for key, limit in limits.items():
        values = [r[key] for r in valid if r[key] is not None]
        out[key] = {"value": max(values) if values else None, "limit": limit}
    return out


def result(run: Run, trace: bool, devices: list) -> dict:
    metrics = {}
    for metric in run.cell.per_layer if trace else run.cell.end_to_end:
        value = metric.reader.read(run)
        if value is not None:
            metrics[metric.name] = {"value": value, "unit": metric.unit}
    limits = _limits(run)
    failed = sum(1 for p in run.partitions if not _passes(p, limits))
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": run.peak_bytes,
    }
    out = {
        "correct": bool(run.partitions) and failed == 0,
        "attempted": len(run.partitions),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in list(run.trace.programs.items())[:10]],
            "idle_gaps": [[name, s] for name, s in run.trace.gaps],
        }
    out["checks"] = checks(run)
    return out


def configure_jax():
    """Import the program and keep every compiled program, however short its
    compile, in ``<checkout>/.xla_cache``, so that only a checkout's first
    run compiles and nothing is shared with another checkout.

    The cache is unbounded whatever ``JAX_COMPILATION_CACHE_MAX_SIZE`` says:
    with a size limit JAX evicts entries and guards every read and write with
    a file lock that times out after 10 s, and an entry that times out or is
    evicted is compiled again."""
    import jax

    import kaminpar_tpu  # noqa: F401

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".xla_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True, help="cell name in BENCHMARK.json")
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--trace-out", help="directory to keep the traced .xplane.pb in")
    p.add_argument("--allow-cpu", action="store_true",
                   help="run on the CPU (tests only; never a measurement)")
    args = p.parse_args(argv)

    try:
        cell = load_cell(args.workload, ROOT)
        devices = chip_devices(cell.chips, args.allow_cpu)
    except (SpecError, NoChip) as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return 2
    jax = configure_jax()
    _say(f"device: kind={devices[0].device_kind} count={len(devices)} "
         f"compile_cache_dir={jax.config.jax_compilation_cache_dir}")
    run = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   trace_out=args.trace_out)
    out = result(run, bool(args.trace), devices)
    for name, check in out["checks"].items():
        print(f"check {name}: value={check['value']} limit={check['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    _say(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
