"""Time warm partitions of a cell's graph at several sizes, in one process.

    python -m perfbench.probe_scales --workload rmat-k2 --param scale \\
        --values 16,15,14 --repeats 2 --seed 1

For each value of the configuration parameter ``--param`` it generates the
graph, partitions it once cold (compiling, or loading from the compile
cache) and ``--repeats`` times warm, as the window does, and prints the
times.  All sizes share one process and one compile cache.  It is how a
configuration's size is chosen (PERF.md), not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from perfbench import reference
from perfbench.run import (
    CompileCounter, NoChip, _timed_partition, chip_devices, configure_jax,
)
from perfbench.spec import ROOT, load_cell


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--param", required=True)
    p.add_argument("--values", required=True, help="comma-separated")
    p.add_argument("--repeats", type=int, default=2)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--allow-cpu", action="store_true")
    args = p.parse_args(argv)
    cell = load_cell(args.workload, ROOT)
    try:
        chip_devices(cell.chips, args.allow_cpu)
    except NoChip as exc:
        print(f"probe_scales: {exc}", file=sys.stderr)
        return 2
    configure_jax()
    compiles = CompileCounter.get()
    k, eps = int(cell.traffic["k"]), float(cell.traffic["epsilon"])
    for value in (int(v) for v in args.values.split(",")):
        params = dict(cell.config["params"], **{args.param: value})
        t0 = time.perf_counter()
        row_ptr, col_idx = cell.family.generate(params, args.seed)
        gen_s = time.perf_counter() - t0
        rows = []
        for i in range(1 + args.repeats):
            c0 = compiles.events
            rec = _timed_partition(row_ptr, col_idx, cell.traffic)
            check = reference.check(row_ptr, col_idx, rec.part,
                                    rec.reported_cut, k, eps) if rec.error is None else {}
            rows.append({
                "run": "cold" if i == 0 else "warm",
                "wall_s": rec.wall_s,
                "compile_events": compiles.events - c0,
                "phases": rec.phases,
                "syncs": rec.syncs,
                "error": rec.error and rec.error.splitlines()[-1],
                "cut": check.get("cut"),
                "cut_mismatch": check.get("cut_mismatch"),
                "max_block_weight": check.get("max_block_weight"),
                "limit": reference.max_block_weight(len(row_ptr) - 1, k, eps),
            })
        print(json.dumps({
            "param": args.param, "value": value, "n": len(row_ptr) - 1,
            "edges": len(col_idx) // 2, "generation_s": gen_s, "runs": rows,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
