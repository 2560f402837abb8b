#!/usr/bin/env python
"""Measure the reference CPU baseline and this repo's CLI on identical inputs.

Produces BASELINE_measured.md: cut + wall-clock for the reference binary
(`build_ref/apps/KaMinPar`, built from /root/reference) and for
`python -m kaminpar_tpu`, per graph/k/seed (VERDICT r1 next-step #2 — every
perf claim must be anchored to a *measured* reference run, not a guessed
constant).

Usage:  python scripts/measure_baseline.py [--quick]

Notes on comparability: this box exposes ONE cpu core, so the reference runs
single-threaded (-t 1); the reference's published numbers use 96 cores.  The
table is an apples-to-apples single-host comparison, not the north-star
TPU-vs-multicore target (BASELINE.md).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_BIN = os.path.join(REPO, "build_ref", "apps", "KaMinPar")

CONFIGS = [
    # (graph path, k, label)
    ("/root/reference/misc/rgg2d.metis", 4, "rgg2d k=4 (BASELINE eval 1)"),
    ("/root/reference/misc/rgg2d.metis", 64, "rgg2d k=64"),
    ("bench_data/rmat16.metis", 16, "rmat16 k=16"),
    ("bench_data/rmat18.metis", 16, "rmat18 k=16 (BASELINE eval 2 analog)"),
    ("bench_data/rmat18.metis", 64, "rmat18 k=64"),
]


def run_reference(graph: str, k: int, seed: int):
    t0 = time.perf_counter()
    out = subprocess.run(
        [REF_BIN, graph, str(k), "-P", "default", f"--seed={seed}", "-t", "1"],
        capture_output=True,
        text=True,
        timeout=3600,
        cwd=REPO,
    )
    wall = time.perf_counter() - t0
    if out.returncode != 0:
        raise RuntimeError(
            f"reference failed on {graph} k={k}:\n{out.stdout}\n{out.stderr}"
        )
    cut = int(re.search(r"Edge cut:\s+(\d+)", out.stdout).group(1))
    imb = float(re.search(r"Imbalance:\s+([\d.e-]+)", out.stdout).group(1))
    return {"cut": cut, "imbalance": imb, "wall_s": wall}


def run_ours(graph: str, k: int, seed: int):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    t0 = time.perf_counter()
    out = subprocess.run(
        [
            sys.executable, "-m", "kaminpar_tpu", graph, str(k),
            "-P", "default", "-s", str(seed), "-E",
        ],
        capture_output=True,
        text=True,
        timeout=3600,
        env=env,
        cwd=REPO,
    )
    wall = time.perf_counter() - t0
    m = re.search(r"RESULT cut=(\d+) imbalance=([\d.e-]+)", out.stdout)
    if not m:
        raise RuntimeError(f"no RESULT line:\n{out.stdout}\n{out.stderr}")
    return {"cut": int(m.group(1)), "imbalance": float(m.group(2)), "wall_s": wall}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="one seed, small configs")
    ap.add_argument("--out", default=os.path.join(REPO, "BASELINE_measured.md"))
    ap.add_argument("--json", default=os.path.join(REPO, "bench_data", "baseline.json"))
    args = ap.parse_args()

    seeds = [1] if args.quick else [1, 2, 3]
    configs = CONFIGS[:1] if args.quick else CONFIGS
    rows = []
    for graph, k, label in configs:
        if not os.path.exists(os.path.join(REPO, graph)) and not os.path.exists(graph):
            print(f"skip {label}: {graph} missing", file=sys.stderr)
            continue
        ref_runs = [run_reference(graph, k, s) for s in seeds]
        our_runs = [run_ours(graph, k, s) for s in seeds]
        best = min  # compare best cuts (both sides pick their best seed)
        row = {
            "label": label,
            "graph": graph,
            "k": k,
            "ref_cut_best": best(r["cut"] for r in ref_runs),
            "ref_cut_mean": sum(r["cut"] for r in ref_runs) / len(ref_runs),
            "ref_wall_mean": sum(r["wall_s"] for r in ref_runs) / len(ref_runs),
            "our_cut_best": best(r["cut"] for r in our_runs),
            "our_cut_mean": sum(r["cut"] for r in our_runs) / len(our_runs),
            "our_wall_mean": sum(r["wall_s"] for r in our_runs) / len(our_runs),
            "our_imb_max": max(r["imbalance"] for r in our_runs),
        }
        row["cut_ratio_mean"] = row["our_cut_mean"] / max(row["ref_cut_mean"], 1)
        rows.append(row)
        print(json.dumps(row), flush=True)

    os.makedirs(os.path.dirname(args.json), exist_ok=True)
    with open(args.json, "w") as f:
        json.dump(rows, f, indent=2)

    with open(args.out, "w") as f:
        f.write(
            "# BASELINE_measured — reference binary vs this repo (same box)\n\n"
            "Reference: KaMinPar v3.7.3 built from /root/reference "
            "(Release, TBB, `-t 1`; this box has ONE cpu core — the "
            "reference's published numbers use 96).  Ours: "
            "`python -m kaminpar_tpu -P default` on the CPU backend (same "
            "core).  Cuts are mean over seeds {1,2,3}; wall is end-to-end "
            "including IO and (for ours) jit compilation.\n\n"
            "| config | ref cut | our cut | cut ratio | ref wall s | our wall s | our imb |\n"
            "|---|---|---|---|---|---|---|\n"
        )
        for r in rows:
            f.write(
                f"| {r['label']} | {r['ref_cut_mean']:.0f} | {r['our_cut_mean']:.0f} "
                f"| {r['cut_ratio_mean']:.3f} | {r['ref_wall_mean']:.2f} "
                f"| {r['our_wall_mean']:.2f} | {r['our_imb_max']:.4f} |\n"
            )
        f.write(
            "\nCut ratio ≤ 1.05 is the BASELINE.md quality bar.  Wall-clock "
            "on this 1-core box is not the north-star comparison (that is "
            "TPU vs 96-core, BASELINE.md); it anchors correctness of the "
            "quality story and gives a measured lower bound for the "
            "reference's single-core throughput.\n"
        )
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
