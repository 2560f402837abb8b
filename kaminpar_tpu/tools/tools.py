"""Tool implementations (reference: apps/tools/*.cc)."""

from __future__ import annotations

import argparse

import numpy as np


def _read(path: str):
    from ..io import read_graph

    return read_graph(path, decompress=True)


def graph_properties(argv) -> int:
    """Reference: GraphPropertiesTool.cc — structural summary of a graph."""
    p = argparse.ArgumentParser(prog="graph-properties")
    p.add_argument("graph")
    args = p.parse_args(argv)
    g = _read(args.graph)
    deg = np.diff(np.asarray(g.row_ptr))
    nw = np.asarray(g.node_w)
    ew = np.asarray(g.edge_w)
    print(f"Graph: {args.graph}")
    print(f"  n: {g.n}")
    print(f"  m: {g.m // 2} (undirected)")
    print(f"  total node weight: {nw.sum()}  max: {nw.max() if g.n else 0}")
    print(f"  total edge weight: {ew.sum() // 2}")
    print(f"  degrees: min={deg.min() if g.n else 0} max={deg.max() if g.n else 0} "
          f"avg={deg.mean():.2f} median={np.median(deg):.0f}")
    print(f"  isolated nodes: {(deg == 0).sum()}")
    print(f"  node weighted: {bool((nw != 1).any())}  "
          f"edge weighted: {bool((ew != 1).any())}")
    return 0


def partition_properties(argv) -> int:
    """Reference: PartitionPropertiesTool.cc — quality metrics of a
    partition file (one block id per line)."""
    p = argparse.ArgumentParser(prog="partition-properties")
    p.add_argument("graph")
    p.add_argument("partition")
    p.add_argument("-e", "--epsilon", type=float, default=0.03)
    args = p.parse_args(argv)
    g = _read(args.graph)
    part = np.loadtxt(args.partition, dtype=np.int64).reshape(-1)
    if len(part) != g.n:
        print(f"error: partition has {len(part)} entries, graph has {g.n} nodes")
        return 1
    from ..graph import metrics

    k = int(part.max()) + 1
    W = int(np.asarray(g.node_w).sum())
    perfect = -(W // -k)
    max_bw = np.full(k, max(int((1 + args.epsilon) * perfect), perfect + 1))
    bw = np.asarray(metrics.block_weights(g, part, k))
    print(f"Partition: {args.partition}")
    print(f"  k: {k}")
    print(f"  cut: {metrics.edge_cut(g, part)}")
    print(f"  imbalance: {metrics.imbalance(g, part, k):.6f}")
    print(f"  feasible (eps={args.epsilon}): "
          f"{metrics.is_feasible(g, part, k, max_bw)}")
    print(f"  block weights: min={bw.min()} max={bw.max()} avg={bw.mean():.1f}")
    return 0


def connected_components(argv) -> int:
    """Reference: ConnectedComponentsTool.cc — component count + sizes."""
    p = argparse.ArgumentParser(prog="connected-components")
    p.add_argument("graph")
    args = p.parse_args(argv)
    g = _read(args.graph)
    # Union-find with path halving (host; the tool is IO-bound anyway).
    parent = np.arange(g.n, dtype=np.int64)

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    u_arr = np.repeat(np.arange(g.n), np.diff(np.asarray(g.row_ptr)))
    for a, b in zip(u_arr, np.asarray(g.col_idx)):
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[ra] = rb
    roots = np.array([find(x) for x in range(g.n)])
    _, sizes = np.unique(roots, return_counts=True)
    sizes = np.sort(sizes)[::-1]
    print(f"Components: {len(sizes)}")
    print(f"  largest: {sizes[:5].tolist()}")
    print(f"  singletons: {(sizes == 1).sum()}")
    return 0


def rearrange(argv) -> int:
    """Reference: GraphRearrangementTool.cc — write the degree-bucket
    permuted graph (the layout the partitioner uses internally)."""
    p = argparse.ArgumentParser(prog="rearrange")
    p.add_argument("graph")
    p.add_argument("output")
    args = p.parse_args(argv)
    from ..graph.csr import rearrange_by_degree_buckets
    from ..io.metis import write_metis

    g = _read(args.graph)
    gg, perm = rearrange_by_degree_buckets(g)
    write_metis(gg, args.output)
    np.savetxt(args.output + ".perm", perm, fmt="%d")
    print(f"wrote {args.output} (+ .perm with old->new node mapping)")
    return 0


def compression(argv) -> int:
    """Reference: GraphCompressionTool.cc — report the compressed footprint
    of a graph (graph/compressed.py, the TeraPart analog)."""
    p = argparse.ArgumentParser(prog="compression")
    p.add_argument("graph")
    args = p.parse_args(argv)
    from ..graph.compressed import compress

    g = _read(args.graph)
    cg = compress(g)
    print(f"Graph: {args.graph}")
    print(f"  n: {cg.n}  m: {cg.m // 2} (undirected)")
    print(f"  uncompressed (CSR int32): {cg.uncompressed_bytes()} B")
    print(f"  compressed:               {cg.memory_bytes()} B")
    print(f"  ratio:                    {cg.compression_ratio():.2f}x")
    print(f"  mean gap width:           {float(cg.width.mean()):.1f} bits")
    return 0


def warmup(argv) -> int:
    """Precompile the serving ladder and print per-bucket compile seconds
    (ISSUE 3 satellite; no reference counterpart — C++ compiles AOT).  The
    same warmup a ``PartitionEngine.start()`` performs, run offline so an
    operator can pay the cold-compile tax before pointing traffic at the
    process (the persistent XLA cache keeps it paid across restarts)."""
    p = argparse.ArgumentParser(prog="warmup")
    p.add_argument("--ladder", default="256,1024",
                   help="comma-separated node-count rungs to warm")
    p.add_argument("--ks", default="8", help="comma-separated k values")
    p.add_argument("-P", "--preset", default="serve")
    p.add_argument("--edge-factor", type=int, default=8)
    p.add_argument("--lanes", default="",
                   help="comma-separated lane counts to warm the "
                        "lane-stacked serve pipeline at (round 11; empty "
                        "skips the lane-stack warm pass)")
    p.add_argument("--fleet", type=int, default=0, metavar="N",
                   help="warm an N-replica PartitionFleet instead of one "
                        "engine (round 18): replica 0 pays the ladder, "
                        "replicas 1..N-1 inherit its warm state; prints "
                        "per-replica inherited vs locally-compiled cells "
                        "(-1 = one replica per visible device)")
    args = p.parse_args(argv)
    from ..utils import compile_stats

    kwargs = dict(
        warm_ladder=tuple(int(s) for s in args.ladder.split(",") if s.strip()),
        warm_ks=tuple(int(s) for s in args.ks.split(",") if s.strip()),
        warm_edge_factor=args.edge_factor,
        warm_lanes=tuple(int(s) for s in args.lanes.split(",") if s.strip()),
    )

    def _print_report(report, indent="  "):
        total_wall = 0.0
        for row in report:
            total_wall += row["wall_s"]
            kind = row.get("kind", "pipeline")
            lanes = f" lanes={row['lanes']}" if "lanes" in row else ""
            src = " [inherited]" if row.get("inherited") else ""
            print(f"{indent}{kind} cell n_bucket={row['n_bucket']} "
                  f"m_bucket={row['m_bucket']} k={row['k']}{lanes}: "
                  f"{row['wall_s']:.2f} s "
                  f"(compile {row['backend_compile_s']:.2f} s, "
                  f"trace {row['trace_s']:.2f} s){src}")
        return total_wall

    if args.fleet:
        from ..serve.fleet import PartitionFleet

        fleet = PartitionFleet(
            args.preset,
            replicas=(None if args.fleet < 0 else args.fleet),
            **kwargs,
        )
        fleet.start(warmup=True)
        try:
            print(f"fleet warmup ({args.preset} preset, "
                  f"{len(fleet.replicas)} replicas):")
            for i, eng in enumerate(fleet.replicas):
                cells = eng.warmup_cell_counts()
                print(f"  replica {i}: {cells['local']} locally compiled, "
                      f"{cells['inherited']} inherited")
                _print_report(eng.warmup_report, indent="    ")
            snap = compile_stats.snapshot()
            print(f"  {snap.get('total', 0)} distinct kernel "
                  "specializations process-wide")
        finally:
            fleet.shutdown(drain=False)
        return 0

    from ..serve.engine import PartitionEngine

    engine = PartitionEngine(args.preset, **kwargs)
    engine.start(warmup=True)
    try:
        print(f"warmup ({args.preset} preset):")
        total_wall = _print_report(engine.warmup_report)
        snap = compile_stats.snapshot()
        print(f"  total: {total_wall:.2f} s over {len(engine.warmup_report)} "
              f"cells, {snap.get('total', 0)} distinct kernel specializations")
    finally:
        engine.shutdown(drain=False)
    return 0


def trace(argv) -> int:
    """Validate, summarize, and optionally re-emit a telemetry trace
    (ISSUE 5; no reference counterpart — the reference prints TIME lines).
    The input is a Chrome trace-event JSON produced by ``--trace-out``;
    validation enforces what Perfetto/chrome://tracing require (monotonic
    per-thread timestamps, matched B/E pairs, numeric counter args).
    ``--out`` re-emits the validated trace (a load/validate/dump round
    trip), ``--quality`` prints the embedded per-level quality rows.

    Exit codes are typed (round 20 hardening — CI scripts branch on
    them): 0 valid, 1 structurally invalid trace, 2 unreadable file,
    3 malformed/truncated JSON, 4 span-free capture (nothing to look
    at — usually a run that crashed before the first phase closed)."""
    import json

    p = argparse.ArgumentParser(prog="trace")
    p.add_argument("trace", help="Chrome trace-event JSON (from --trace-out)")
    p.add_argument("--out", default=None, metavar="FILE",
                   help="re-emit the validated trace to this path")
    p.add_argument("--quality", action="store_true",
                   help="print the per-level quality rows as JSON lines")
    p.add_argument("--shards", action="store_true",
                   help="summarize per-shard imbalance from the mesh "
                        "lanes' span walls (round 13: the dist pipeline "
                        "emits work-proportional shard-lane spans)")
    args = p.parse_args(argv)
    from ..telemetry.trace import shard_lane_summary, validate_chrome_trace

    try:
        with open(args.trace) as fh:
            obj = json.load(fh)
    except OSError as exc:
        print(f"error: cannot read trace: {exc}")
        return 2
    except json.JSONDecodeError as exc:
        print(f"error: malformed trace JSON (truncated capture?): {exc}")
        return 3
    if not isinstance(obj, dict):
        print("error: malformed trace JSON: top level is not an object")
        return 3
    try:
        summary = validate_chrome_trace(obj)
    except ValueError as exc:
        print(f"error: invalid trace: {exc}")
        return 1
    if summary["spans"] == 0:
        print("error: trace has no spans (empty or counter-only capture "
              "— did the run crash before the first phase closed?)")
        return 4
    other = obj.get("otherData") or {}
    print(f"Trace: {args.trace}")
    print(f"  events: {summary['events']} (spans {summary['spans']}, "
          f"counters {summary['counters']}, instants {summary['instants']})")
    print(f"  duration: {summary['duration_us'] / 1e6:.3f} s")
    print(f"  span names: {', '.join(summary['span_names']) or '(none)'}")
    print(f"  counter tracks: {', '.join(summary['counter_names']) or '(none)'}")
    print(f"  quality rows: {summary['quality_rows']}")
    if args.shards:
        rows = shard_lane_summary(obj)
        if not rows:
            print("  shard lanes: (none — not a mesh trace)")
        else:
            print(f"  shard-lane walls over {len(rows[0]['walls_ms'])} shards "
                  "(work-proportional estimates; imb = max/mean):")
            for row in rows:
                print(
                    f"    {row['name']}: min {row['min_ms']:.2f} / mean "
                    f"{row['mean_ms']:.2f} / max {row['max_ms']:.2f} ms "
                    f"(imb {row['imb']:.2f})"
                )
    if args.quality:
        for row in other.get("quality", []):
            print(json.dumps(row))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(obj, fh)
        print(f"re-emitted {summary['events']} events to {args.out}")
    return 0


def ledger(argv) -> int:
    """Run-ledger maintenance (round 13; see telemetry/ledger.py): every
    bench run appends one compact JSON line to RUNS.jsonl —
    ``show`` prints compact per-entry lines, ``tail`` the raw JSON,
    ``append`` adds an entry built from a headline record file (the manual
    path for artifacts produced elsewhere)."""
    import json

    p = argparse.ArgumentParser(prog="ledger")
    p.add_argument("action", choices=["show", "tail", "append"])
    p.add_argument("--runs", default=None, metavar="PATH",
                   help="ledger path (default: RUNS.jsonl in the repo root)")
    p.add_argument("-n", type=int, default=10,
                   help="entries to show/tail (default 10)")
    p.add_argument("--from-json", default=None, metavar="FILE",
                   help="append: headline record JSON to build the entry from")
    p.add_argument("--kind", default="manual",
                   help="append: entry kind (default 'manual')")
    args = p.parse_args(argv)
    from ..telemetry import ledger as led

    path = args.runs or led.default_path()
    if args.action == "append":
        if not args.from_json:
            print("error: append requires --from-json FILE")
            return 1
        with open(args.from_json) as fh:
            record = json.load(fh)
        led.append(led.build_entry(record, kind=args.kind), path)
        print(f"appended 1 {args.kind} entry to {path}")
        return 0
    entries = led.tail(args.n, path)
    if not entries:
        print(f"(no ledger entries at {path})")
        return 0
    if args.action == "tail":
        for entry in entries:
            print(json.dumps(entry))
        return 0
    for entry in entries:
        metrics = entry.get("metrics") or {}
        headline = " ".join(
            f"{key}={metrics[key]}" for key in
            ("value", "partition_wall_s", "partition_cut",
             "serve_throughput_gps")
            if key in metrics
        )
        sync = (entry.get("sync") or {}).get("count")
        coll = (entry.get("collectives") or {}).get("count")
        print(
            f"{entry.get('iso', '?'):>19}  {entry.get('kind', '?'):<7} "
            f"{entry.get('backend', '?'):<12} head={entry.get('git_head') or '?':<9} "
            f"sync={sync} coll={coll} {headline}"
        )
    return 0


def regress(argv) -> int:
    """Regression sentinel (round 13): compare the newest RUNS.jsonl entry
    against a baseline window of earlier same-kind/same-backend entries
    with noise-aware thresholds (telemetry/ledger.compare).  Exit 1 on any
    regression, 0 when quiet — the CI gate over the run ledger."""
    import json

    p = argparse.ArgumentParser(prog="regress")
    p.add_argument("--runs", default=None, metavar="PATH")
    p.add_argument("--window", type=int, default=None,
                   help="baseline entries to compare against (default 5)")
    p.add_argument("--wall-tol", type=float, default=None,
                   help="relative wall/throughput tolerance (default 0.35)")
    p.add_argument("--count-tol", type=float, default=None,
                   help="relative census tolerance (default 0.0 — one "
                        "stray transfer or collective is a regression)")
    p.add_argument("--quality-tol", type=float, default=None,
                   help="relative cut tolerance (default 0.10)")
    p.add_argument("--json", action="store_true", dest="as_json")
    args = p.parse_args(argv)
    from ..telemetry import ledger as led

    entries = led.read(args.runs)
    if not entries:
        print("regress: ledger is empty — nothing to compare")
        return 0
    latest = entries[-1]
    window = led.baseline_window(
        entries[:-1], latest, args.window or led.DEFAULT_WINDOW
    )
    if not window:
        print(
            f"regress: no baseline window for kind={latest.get('kind')!r} "
            f"backend={latest.get('backend')!r} — nothing to compare"
        )
        return 0
    kwargs = {}
    if args.wall_tol is not None:
        kwargs["wall_tol"] = args.wall_tol
    if args.count_tol is not None:
        kwargs["count_tol"] = args.count_tol
    if args.quality_tol is not None:
        kwargs["quality_tol"] = args.quality_tol
    regressions = led.compare(latest, window, **kwargs)
    # Round 20: the ledger-wide report summary rides the sentinel so one
    # `regress --json` call answers both "did the newest run regress?"
    # and "how is the whole ledger trending?" without a second pass.
    report_summary = led.build_report(
        entries, window=args.window or led.DEFAULT_WINDOW)["summary"]
    if args.as_json:
        print(json.dumps({
            "latest_iso": latest.get("iso"),
            "baseline_entries": len(window),
            "regressions": regressions,
            "report_summary": report_summary,
        }))
    else:
        print(
            f"regress: latest {latest.get('iso')} ({latest.get('kind')}/"
            f"{latest.get('backend')}) vs {len(window)} baseline entries"
        )
        print(
            f"  ledger: {report_summary['groups']} groups, "
            f"{report_summary['regressed_groups']} regressed, trends "
            f"{report_summary['trend_regressed_metrics']} down / "
            f"{report_summary['trend_improved_metrics']} up"
        )
        for reg in regressions:
            ref = reg.get("baseline_median", reg.get("baseline_max"))
            print(
                f"  REGRESSION {reg['metric']}: {reg['latest']} vs "
                f"baseline {ref} (threshold {reg['threshold']}, "
                f"{reg['class']})"
            )
        if not regressions:
            print("  no regressions")
    return 1 if regressions else 0


def report(argv) -> int:
    """Ledger analytics report (round 20; telemetry/ledger.py): render
    RUNS.jsonl into a per-(kind, backend, workload) trend report — metric
    trajectories over time, the latest entry's regressions vs its noise-
    aware baseline window, and per-regression *attribution* (which
    ``phase.*`` wall or ``census.*`` count co-moved when a headline
    metric regressed).  Pure stdlib over the JSONL — runs jax-free, so a
    dashboard box with only the RUNS.jsonl file can render it."""
    import json

    p = argparse.ArgumentParser(prog="report")
    p.add_argument("--runs", default=None, metavar="PATH",
                   help="ledger path (default: RUNS.jsonl in the repo root)")
    p.add_argument("--window", type=int, default=None,
                   help="baseline entries per group (default 5)")
    p.add_argument("--kind", action="append", default=None, metavar="KIND",
                   help="only these entry kinds (repeatable, e.g. "
                        "--kind tier1 --kind chaos)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit the structured report instead of markdown")
    p.add_argument("--out", default=None, metavar="FILE",
                   help="write the report to this path instead of stdout")
    args = p.parse_args(argv)
    import os

    from ..telemetry import ledger as led

    path = args.runs or led.default_path()
    if not os.path.exists(path):
        print(f"error: no ledger at {path}")
        return 2
    rep = led.build_report(
        path=path, window=args.window or led.DEFAULT_WINDOW, kinds=args.kind)
    text = (json.dumps(rep, indent=2) if args.as_json
            else led.render_report_markdown(rep))
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text if text.endswith("\n") else text + "\n")
        s = rep["summary"]
        print(f"wrote report for {s['entries']} entries / {s['groups']} "
              f"groups to {args.out}")
    else:
        print(text)
    return 0


def capacity(argv) -> int:
    """HBM capacity planner (ISSUE 12; telemetry/capacity.py): print the
    fit/no-fit ladder of a workload family against a device kind's HBM
    ceiling — resident-buffer model composed with XLA's own
    memory-analysis temp bytes (the executable census) — plus the max
    feasible scale per arm.  ``--validate`` additionally runs the CPU
    predicted-vs-measured check (the tier-1 assertion, printed as the
    measured-vs-predicted rows HBM_BUDGET.md embeds)."""
    import json as _json

    p = argparse.ArgumentParser(prog="capacity")
    p.add_argument("--device-kind", default="v5e",
                   help="device kind substring for the HBM ceiling "
                        "(v2/v3/v4/v5e/v5p/v6e; default v5e)")
    p.add_argument("--family", default="rmat", help="rmat | rgg | grid")
    p.add_argument("-k", type=int, default=64)
    p.add_argument("--edge-factor", type=int, default=16)
    p.add_argument("--scales", default="16:30",
                   help="scale range lo:hi (inclusive; default 16:30)")
    p.add_argument("-P", "--shards", type=int, default=1,
                   help="mesh shards (per-shard slices + the r15 pad tax)")
    p.add_argument("--lanes", type=int, default=1,
                   help="lane-stacked batch width")
    p.add_argument("--ceiling-bytes", type=int, default=None,
                   help="explicit ceiling override (skips the device table)")
    p.add_argument("--no-census", action="store_true",
                   help="skip the XLA memory-analysis harvest (closed-form "
                        "temp model only; no compiles)")
    p.add_argument("--validate", action="store_true",
                   help="run the scale-12 CPU predicted-vs-measured check")
    p.add_argument("--validate-scale", type=int, default=12)
    p.add_argument("--json", action="store_true", dest="as_json")
    args = p.parse_args(argv)
    from ..telemetry import capacity as cap
    from ..utils import compile_stats

    if not args.no_census:
        compile_stats.arm_executable_census()
    lo, _, hi = args.scales.partition(":")
    scales = range(int(lo), int(hi or lo) + 1)
    lad = cap.ladder(
        args.family, args.k, device_kind=args.device_kind, scales=scales,
        P=args.shards, lanes=args.lanes, edge_factor=args.edge_factor,
        ceiling_bytes=args.ceiling_bytes,
    )
    validation = cap.validate_cpu(args.validate_scale,
                                  args.edge_factor) if args.validate else None
    if args.as_json:
        out = {
            **{k: lad[k] for k in ("family", "k", "P", "lanes",
                                   "device_kind", "ceiling_bytes",
                                   "max_feasible_scale")},
            "rows": [
                {arm: row[arm].to_dict() for arm in row}
                for row in lad["rows"]
            ],
        }
        if validation is not None:
            out["validation"] = validation
        print(_json.dumps(out))
        return 0
    ceiling = lad["ceiling_bytes"]
    print(f"capacity ladder: {args.family} k={args.k} P={args.shards} "
          f"lanes={args.lanes} on {args.device_kind} "
          f"(ceiling {cap.format_bytes(ceiling)}"
          f" = HBM x {cap.DEFAULT_HEADROOM:.0%} headroom)")
    print(f"  {'scale':>5} {'m (est)':>12} {'dense peak':>12} {'fit':>4} "
          f"{'decode peak':>12} {'fit':>4}  temp source")
    for row in lad["rows"]:
        d, c = row["dense"], row["device_decode"]

        def _fit(pred):
            return {True: "yes", False: "NO", None: "?"}[pred.fits]

        print(f"  {d.scale:>5} {d.m:>12,} "
              f"{cap.format_bytes(d.predicted_peak_bytes):>12} {_fit(d):>4} "
              f"{cap.format_bytes(c.predicted_peak_bytes):>12} {_fit(c):>4}"
              f"  {d.temp_source}")
    mf = lad["max_feasible_scale"]
    print(f"  max feasible scale: dense {mf['dense']}, "
          f"device_decode {mf['device_decode']}")
    if validation is not None:
        print(f"  CPU validation (scale {validation['scale']}, backend "
              f"{validation['watermark_backend']}, tolerance "
              f"{validation['tolerance']:.0%}):")
        for arm in ("dense", "device_decode"):
            v = validation[arm]
            print(f"    {arm}: predicted "
                  f"{cap.format_bytes(v['predicted_bytes'])} vs measured "
                  f"{cap.format_bytes(v['measured_bytes'])} "
                  f"(rel err {v['rel_err']:.1%})")
    return 0


def _gen_graph(spec: str):
    """Build a graph from a generator spec — ``rmat:S[:EF[:SEED]]``,
    ``grid:RxC``, ``star:N`` — or read it from a file path.  Shared by
    ``tools resume`` and the chaos preemption scenario (the killed child
    and the resuming parent must agree on the graph bit for bit)."""
    from ..graph import generators as gen

    kind, _, rest = spec.partition(":")
    if kind == "rmat":
        parts = [int(x) for x in rest.split(":")] if rest else [10]
        scale = parts[0]
        ef = parts[1] if len(parts) > 1 else 8
        seed = parts[2] if len(parts) > 2 else 0
        return gen.rmat_graph(scale, edge_factor=ef, seed=seed)
    if kind == "grid":
        rows, _, cols = rest.partition("x")
        return gen.grid2d_graph(int(rows), int(cols or rows))
    if kind == "star":
        return gen.star_graph(int(rest))
    return _read(spec)


def resume(argv) -> int:
    """Resume a preempted deep run from its checkpoint (ISSUE 15):
    validates the checkpoint fingerprint against the graph/context,
    rebuilds the level stack into the same shape-ladder buckets, and
    continues BIT-IDENTICAL to the uninterrupted run
    (resilience/checkpoint.py).  ``--verify`` additionally reruns the
    whole pipeline uninterrupted and asserts the identity."""
    import time as _time

    import numpy as _np

    p = argparse.ArgumentParser(prog="resume")
    p.add_argument("--ckpt", required=True,
                   help="checkpoint file, or a directory (latest wins)")
    p.add_argument("--graph", required=True,
                   help="graph file or generator spec "
                        "(rmat:S[:EF[:SEED]] / grid:RxC / star:N) — must "
                        "be the dead run's graph; the fingerprint check "
                        "rejects anything else")
    p.add_argument("-k", type=int, required=True)
    p.add_argument("-e", "--epsilon", type=float, default=0.03)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("-P", "--preset", default="default")
    p.add_argument("-o", "--output", default=None,
                   help="write the partition (one block id per line)")
    p.add_argument("--verify", action="store_true",
                   help="rerun uninterrupted and assert bit-identity")
    args = p.parse_args(argv)

    from ..graph import metrics
    from ..kaminpar import KaMinPar
    from ..presets import create_context_by_preset_name
    from ..resilience.checkpoint import CheckpointMismatchError

    g = _gen_graph(args.graph)

    def _solver():
        ctx = create_context_by_preset_name(args.preset)
        ctx.seed = args.seed
        s = KaMinPar(ctx)
        s.set_graph(g)
        return s

    t0 = _time.monotonic()
    try:
        part = _solver().compute_partition(
            args.k, args.epsilon, resume=args.ckpt
        )
    except CheckpointMismatchError as exc:
        print(f"fingerprint mismatch: {exc}")
        return 2
    wall = _time.monotonic() - t0
    cut = metrics.edge_cut(g, part)
    print(f"resumed from {args.ckpt}: cut={cut} "
          f"imbalance={metrics.imbalance(g, part, args.k):.4f} "
          f"wall={wall:.1f}s")
    if args.output:
        _np.savetxt(args.output, part, fmt="%d")
        print(f"wrote {args.output}")
    if args.verify:
        ref = _solver().compute_partition(args.k, args.epsilon)
        identical = bool(_np.array_equal(ref, part))
        print(f"verify: bit-identical to uninterrupted run: {identical}")
        return 0 if identical else 1
    return 0


def _chaos_preemption(args) -> int:
    """``tools chaos --preemption`` (ISSUE 15 satellite): SIGTERM a deep
    run at a level boundary (the ``preempt`` injection point firing in a
    child process with KPTPU_CHECKPOINT armed), resume from the surviving
    checkpoint, verify bit-identity against the uninterrupted run, and
    append ``chaos_preempt_*`` keys under the ``tools regress``
    sentinel."""
    import json as _json
    import os as _os
    import signal as _signal
    import subprocess as _sub
    import sys as _sys
    import tempfile as _tempfile
    import time as _time

    import numpy as _np

    from ..kaminpar import KaMinPar
    from ..presets import create_context_by_preset_name
    from ..telemetry import ledger as led

    spec = args.graph
    g = _gen_graph(spec)

    def _solver():
        ctx = create_context_by_preset_name("default")
        ctx.seed = args.seed
        if args.climit:
            ctx.coarsening.contraction_limit = args.climit
        s = KaMinPar(ctx)
        s.set_graph(g)
        return s

    t0 = _time.monotonic()
    ref = _solver().compute_partition(args.k)
    full_wall = _time.monotonic() - t0

    ckpt_dir = _tempfile.mkdtemp(prefix="kptpu_preempt_")
    plan = f"preempt:execute-fault:after={args.boundary - 1}:n=1"
    env = dict(_os.environ)
    env.update({
        "KPTPU_CHECKPOINT": ckpt_dir,
        "KPTPU_CHECKPOINT_EVERY": "1",
        "KPTPU_FAULTS": plan,
    })
    child = _sub.run(
        [_sys.executable, "-m", "kaminpar_tpu.tools", "chaos",
         "--preempt-child", "--graph", spec, "-k", str(args.k),
         "--seed", str(args.seed), "--climit", str(args.climit)],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    killed = child.returncode == -_signal.SIGTERM
    ckpts = sorted(
        f for f in _os.listdir(ckpt_dir) if f.startswith("ckpt_deep_")
    )
    if not killed or not ckpts:
        print(f"preemption scenario FAILED: child rc={child.returncode} "
              f"(want {-_signal.SIGTERM}), checkpoints={ckpts}")
        print(child.stderr[-2000:])
        return 1

    t0 = _time.monotonic()
    resumed = _solver().compute_partition(args.k, resume=ckpt_dir)
    recover_s = _time.monotonic() - t0
    identical = bool(_np.array_equal(ref, resumed))

    record = {
        "backend": _backend_name(),
        "chaos_preempt_graph": spec,
        "chaos_preempt_boundary": args.boundary,
        # int, not bool: the ledger's metric extraction keeps numerics
        "chaos_preempt_killed": int(killed),
        "chaos_preempt_identical": int(identical),
        "chaos_preempt_checkpoints": len(ckpts),
        "chaos_preempt_recover_s": round(recover_s, 3),
        "chaos_preempt_full_wall_s": round(full_wall, 3),
    }
    if not args.no_ledger:
        led.append(led.build_entry(record, kind="chaos"),
                   args.runs or led.default_path())
    if args.as_json:
        print(_json.dumps(record))
    else:
        print(f"chaos preemption: {spec} k={args.k} seed={args.seed} "
              f"killed at boundary {args.boundary} (SIGTERM)")
        print(f"  checkpoints survived: {ckpts}")
        print(f"  resume bit-identical: {identical}")
        print(f"  time-to-recover: {record['chaos_preempt_recover_s']}s "
              f"(uninterrupted run: "
              f"{record['chaos_preempt_full_wall_s']}s)")
        if not args.no_ledger:
            print("  ledger: appended kind=chaos entry")
    return 0 if identical else 1


def _chaos_preempt_child(args) -> int:
    """Hidden child leg of the preemption scenario: run the deep
    pipeline with checkpointing + the preempt fault armed via env — the
    SIGTERM lands mid-run and this process dies at a level boundary
    whose checkpoint is already durable."""
    from ..kaminpar import KaMinPar
    from ..presets import create_context_by_preset_name

    g = _gen_graph(args.graph)
    ctx = create_context_by_preset_name("default")
    ctx.seed = args.seed
    if args.climit:
        ctx.coarsening.contraction_limit = args.climit
    s = KaMinPar(ctx)
    s.set_graph(g)
    s.compute_partition(args.k)
    # Reaching here means the plan never fired (too few boundaries for
    # the requested kill index) — report it as a distinct exit code so
    # the parent prints a useful verdict instead of "no checkpoints".
    print("preempt point never fired (run had fewer boundaries)")
    return 3


def chaos(argv) -> int:
    """Injected-fault soak (ISSUE 13): run a short serve burst under an
    armed fault plan and report recovery — per-request outcomes,
    time-to-recover (first success after the first fault), breaker
    trips, and degradation-ladder demotion counts — then append the
    metrics to RUNS.jsonl under the regress sentinel (kind="chaos"), so
    a recovery regression fails the gate like a perf regression.  Plans
    are seed-keyed (resilience/faults.py), so a soak replays
    bit-for-bit under the same --plan/--seed.

    ``--preemption`` (ISSUE 15) switches to the preemption scenario:
    kill a checkpointing deep run at a level boundary, resume, verify
    bit-identity + time-to-recover, and append ``chaos_preempt_*``
    ledger keys."""
    import json as _json
    import time as _time

    p = argparse.ArgumentParser(prog="chaos")
    p.add_argument("--preemption", action="store_true",
                   help="kill+resume scenario instead of the serve soak")
    p.add_argument("--preempt-child", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--graph", default="rmat:12:8:3",
                   help="(preemption) graph spec, default rmat:12:8:3")
    p.add_argument("--boundary", type=int, default=1,
                   help="(preemption) 1-based level boundary to kill at")
    p.add_argument("--climit", type=int, default=0,
                   help="(preemption) coarsening contraction-limit "
                        "override — small values force multi-level runs "
                        "on small graphs (0 = preset default)")
    p.add_argument("--plan", default="execute@engine_request:execute-fault:n=2",
                   help="fault plan (resilience/faults.py syntax; default "
                        "fails the first 2 engine executes)")
    p.add_argument("--seed", type=int, default=0, help="fault-plan seed")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--scale", type=int, default=7,
                   help="RMAT scale of the soak graphs")
    p.add_argument("-k", type=int, default=4)
    p.add_argument("-P", "--preset", default="serve")
    p.add_argument("--cooldown", type=float, default=1.0,
                   help="breaker cooldown for the soak engine (short, so "
                        "the half-open recovery is observed in-run)")
    p.add_argument("--runs", default=None, metavar="PATH",
                   help="ledger path (default RUNS.jsonl)")
    p.add_argument("--no-ledger", action="store_true")
    p.add_argument("--json", action="store_true", dest="as_json")
    args = p.parse_args(argv)

    if args.preempt_child:
        return _chaos_preempt_child(args)
    if args.preemption:
        return _chaos_preemption(args)

    from ..graph.generators import rmat_graph
    from ..presets import create_context_by_preset_name
    from ..resilience import breakers as rbreakers
    from ..resilience import faults as rfaults
    from ..resilience.errors import ResilienceError
    from ..serve.engine import PartitionEngine
    from ..telemetry import ledger as led

    rfaults.reset()
    rbreakers.reset_global_registry()
    ctx = create_context_by_preset_name(args.preset)
    ctx.resilience.fault_plan = args.plan
    ctx.resilience.fault_seed = args.seed
    ctx.resilience.breaker_cooldown_s = args.cooldown
    engine = PartitionEngine(
        ctx, warm_ladder=(), warm_ks=(),
        queue_bound=max(16, args.requests), max_batch=4,
    )
    engine.start(warmup=False)
    outcomes = []
    t_first_fault = t_recovered = None
    t0 = _time.monotonic()
    try:
        for i in range(args.requests):
            g = rmat_graph(args.scale, edge_factor=4, seed=100 + i)
            t_req = _time.monotonic()
            try:
                engine.partition(g, args.k)
                outcomes.append("ok")
                if t_first_fault is not None and t_recovered is None:
                    t_recovered = _time.monotonic()
            except ResilienceError as exc:
                outcomes.append(exc.failure_class)
                if t_first_fault is None:
                    t_first_fault = t_req
            except Exception as exc:  # noqa: BLE001 — soak verdicts must
                # name unexpected (unclassified) escapes, not crash on them
                outcomes.append(f"UNCLASSIFIED:{type(exc).__name__}")
                if t_first_fault is None:
                    t_first_fault = t_req
    finally:
        engine.shutdown(drain=True)
    wall = _time.monotonic() - t0

    snap = engine.stats()["resilience"]
    demotions: dict = {}
    for reg in (snap["engine"], snap["pipeline"]):
        for path, count in reg["demotions"].items():
            demotions[path] = demotions.get(path, 0) + count
    trips = sum(
        br["trips"]
        for reg in (snap["engine"], snap["pipeline"])
        for br in reg["breakers"].values()
    )
    injected = snap["faults"]["points"]
    recovered = bool(outcomes) and outcomes[-1] == "ok" and not any(
        o.startswith("UNCLASSIFIED") for o in outcomes
    )
    recover_s = (
        round(t_recovered - t_first_fault, 3)
        if (t_first_fault is not None and t_recovered is not None)
        else (0.0 if t_first_fault is None else None)
    )
    record = {
        "backend": _backend_name(),
        "chaos_plan": args.plan,
        "chaos_seed": args.seed,
        "chaos_requests": len(outcomes),
        "chaos_ok": sum(1 for o in outcomes if o == "ok"),
        "chaos_faulted": sum(1 for o in outcomes if o != "ok"),
        "chaos_injected_count": sum(r["injected"] for r in injected.values()),
        "chaos_demotion_count": sum(demotions.values()),
        "chaos_breaker_trips": trips,
        # int, not bool: the ledger's metric extraction keeps numerics only
        "chaos_recovered": int(recovered),
        "chaos_wall_s": round(wall, 3),
    }
    if recover_s is not None:
        record["chaos_recover_s"] = recover_s
    summary = {
        **record,
        "outcomes": outcomes,
        "demotions": demotions,
        "injected_by_point": injected,
        "watchdog": snap["watchdog"],
    }
    if not args.no_ledger:
        led.append(led.build_entry(record, kind="chaos"),
                   args.runs or led.default_path())
    if args.as_json:
        print(_json.dumps(summary))
    else:
        print(f"chaos soak: plan={args.plan!r} seed={args.seed} "
              f"({len(outcomes)} requests on {record['backend']})")
        print(f"  outcomes: {' '.join(outcomes)}")
        print(f"  injected: {record['chaos_injected_count']} "
              f"(by point: {injected})")
        print(f"  demotions: {demotions or '(none)'}  breaker trips: {trips}")
        print(f"  time-to-recover: {recover_s}s  wall: {record['chaos_wall_s']}s")
        print(f"  recovered: {recovered}")
        if not args.no_ledger:
            print(f"  ledger: appended kind=chaos entry")
    return 0 if recovered else 1


def _backend_name() -> str:
    try:
        import jax

        return jax.default_backend()
    except Exception:  # noqa: BLE001 — a dead backend is a valid soak env
        return "unknown"


def lint(argv) -> int:
    """kptlint (ISSUE 7): AST-level enforcement of the device-discipline
    contracts — sync budget, runtime isolation, phase registry, RNG and
    donation safety — over the whole package.  Pure stdlib AST: no jax
    import, so it runs in milliseconds.
    See kaminpar_tpu/analysis/ and the README "Static analysis" section."""
    from ..analysis.cli import run_lint

    return run_lint(argv)


REGISTRY = {
    "capacity": capacity,
    "chaos": chaos,
    "graph-properties": graph_properties,
    "ledger": ledger,
    "lint": lint,
    "partition-properties": partition_properties,
    "connected-components": connected_components,
    "compression": compression,
    "rearrange": rearrange,
    "regress": regress,
    "report": report,
    "resume": resume,
    "warmup": warmup,
    "trace": trace,
}
