"""The control of a cell's correctness check: the program asked for a looser
balance than the cell states, checked against the cell's own.

    python -m perfbench.control --workload rmat-k2 --seeds 11,12,13 \\
        --seconds 1 --epsilon 0.10

A partitioner that is handed more slack returns blocks heavier than the
stated limit allows, which is the step that would tempt a later change (a
lower cut bought with balance).  Every seed generates the cell's graph and
runs a window as a benchmark run does, without the warm-up partition, in
this one process, and prints its checks and whether the run came out
correct; the control has to come out not correct on every seed.  The
benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys

from perfbench.run import (
    NoChip, checks, chip_devices, configure_jax, result, run_cell,
)
from perfbench.spec import ROOT, load_cell


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--epsilon", type=float, required=True,
                   help="the balance the program is asked for")
    p.add_argument("--allow-cpu", action="store_true")
    args = p.parse_args(argv)
    cell = load_cell(args.workload, ROOT)
    try:
        devices = chip_devices(cell.chips, args.allow_cpu)
    except NoChip as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 2
    configure_jax()
    caught = 0
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        run = run_cell(cell, seed, args.seconds, False, devices,
                       epsilon=args.epsilon, warm_up=False)
        out = result(run, False, devices)
        caught += not out["correct"]
        print(json.dumps({
            "seed": seed, "program_epsilon": args.epsilon,
            "stated_epsilon": cell.traffic["epsilon"],
            "correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "checks": checks(run),
        }), flush=True)
    print(json.dumps({"control_caught": caught, "seeds": len(seeds)}))
    return 0 if caught == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
