#!/usr/bin/env python
"""A/B harness for the round-3 quality work (VERDICT r2 next-steps #2).

Generates the two gap fixtures (rgg64k deg-50, grid256), measures the
reference binary once (cached), then sweeps our coarsening levers in-process
(one JAX runtime, shared compile cache) and prints a per-variant cut table.

Usage: python scripts/quality_ab.py [--configs rgg64k,grid256] [--seeds 1,2,3]
       [--variants base,lightest,...] [--preset default]
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
DATA = os.path.join(REPO, "bench_data")

os.environ["JAX_PLATFORMS"] = "cpu"  # quality A/B runs on the CPU
sys.path.insert(0, REPO)

from kaminpar_tpu.utils.platform import force_cpu_devices  # noqa: E402

force_cpu_devices(1)


def fixtures():
    import numpy as np

    from kaminpar_tpu.graph.generators import grid2d_graph, rgg2d_graph, rmat_graph
    from kaminpar_tpu.io import write_metis

    os.makedirs(DATA, exist_ok=True)
    out = {}
    spec = {
        "rgg64k": lambda: rgg2d_graph(
            65536, radius=float(np.sqrt(50 / (np.pi * 65536))), seed=7
        ),
        "grid256": lambda: grid2d_graph(256, 256),
        "rgg4k": lambda: rgg2d_graph(
            4096, radius=float(np.sqrt(24 / (np.pi * 4096))), seed=7
        ),
        "rmat14": lambda: rmat_graph(14, edge_factor=14, seed=1),
    }
    for name, make in spec.items():
        path = os.path.join(DATA, f"{name}.metis")
        if not os.path.exists(path):
            g = make()
            write_metis(g, path)
            print(f"wrote {path} n={g.n} m={g.m}", file=sys.stderr)
        out[name] = path
    return out


def ref_cut(path: str, k: int, seed: int, preset: str = "default") -> int:
    cache = os.path.join(DATA, "ref_cache.json")
    db = {}
    if os.path.exists(cache):
        db = json.load(open(cache))
    key = f"{os.path.basename(path)}:{k}:{seed}:{preset}"
    if key not in db:
        out = subprocess.run(
            [REF_BIN, path, str(k), "-P", preset, f"--seed={seed}", "-t", "1"],
            capture_output=True, text=True, timeout=3600,
        )
        if out.returncode != 0:
            raise RuntimeError(f"ref failed: {out.stderr[-500:]}")
        db[key] = int(re.search(r"Edge cut:\s+(\d+)", out.stdout).group(1))
        json.dump(db, open(cache, "w"))
    return db[key]


VARIANTS = {
    "base": {},
    "lightest": {"tie": "lightest"},
    "overlay2": {"overlay": 2},
    "overlay3": {"overlay": 3},
    "light+ov2": {"tie": "lightest", "overlay": 2},
    "shrink2.5": {"shrink": 2.5},
    "shrink5": {"shrink": 5.0},
    "jetdef": {"jet": True},
    "light+jet": {"tie": "lightest", "jet": True},
    "ov2+jet": {"overlay": 2, "jet": True},
    "ov3+jet": {"overlay": 3, "jet": True},
    "iters10": {"lp_iters": 10},
    "ap75": {"active_prob": 0.75},
    "ov2+jet+it10": {"overlay": 2, "jet": True, "lp_iters": 10},
    "it10+ap75": {"lp_iters": 10, "active_prob": 0.75},
    "it10+jet": {"lp_iters": 10, "jet": True},
    "it15": {"lp_iters": 15},
    "it10+ov2": {"lp_iters": 10, "overlay": 2},
    "ov2+ap75": {"overlay": 2, "active_prob": 0.75},
    "it15+ap75": {"lp_iters": 15, "active_prob": 0.75},
    "ap60": {"active_prob": 0.6},
    "noboost": {"boost_factor": 1},
    "extreps2": {"ext_reps": 2},
    "extreps3": {"ext_reps": 3},
}


def our_cut(path: str, k: int, seed: int, variant: dict, preset: str) -> tuple:
    from kaminpar_tpu.context import RefinementAlgorithm, TieBreakingStrategy
    from kaminpar_tpu.graph import metrics
    from kaminpar_tpu.io import read_metis
    from kaminpar_tpu.kaminpar import KaMinPar
    from kaminpar_tpu.presets import create_context_by_preset_name

    ctx = create_context_by_preset_name(preset)
    ctx.seed = seed
    if variant.get("tie"):
        ctx.coarsening.lp.tie_breaking = TieBreakingStrategy(variant["tie"])
    if variant.get("overlay"):
        ctx.coarsening.overlay_levels = variant["overlay"]
    if variant.get("shrink"):
        ctx.coarsening.max_shrink_factor = variant["shrink"]
    if variant.get("lp_iters"):
        ctx.coarsening.lp.num_iterations = variant["lp_iters"]
    if variant.get("active_prob"):
        ctx.coarsening.lp.active_prob = variant["active_prob"]
    if variant.get("boost_factor") is not None:
        ctx.coarsening.lp.low_degree_boost_factor = variant["boost_factor"]
    if variant.get("ext_reps"):
        ctx.initial_partitioning.nested_extension_reps = variant["ext_reps"]
    if variant.get("jet") and RefinementAlgorithm.JET not in ctx.refinement.algorithms:
        algs = list(ctx.refinement.algorithms)
        algs.insert(
            algs.index(RefinementAlgorithm.LP) + 1
            if RefinementAlgorithm.LP in algs else len(algs),
            RefinementAlgorithm.JET,
        )
        ctx.refinement.algorithms = tuple(algs)
    g = read_metis(path)
    solver = KaMinPar(ctx)
    solver.set_graph(g)
    t0 = time.perf_counter()
    part = solver.compute_partition(k, epsilon=0.03)
    wall = time.perf_counter() - t0
    return int(metrics.edge_cut(g, part)), wall


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="rgg64k:64,grid256:64")
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--variants", default="base,lightest,overlay2,light+ov2")
    ap.add_argument("--preset", default="default")
    args = ap.parse_args()

    paths = fixtures()
    seeds = [int(s) for s in args.seeds.split(",")]
    configs = []
    for c in args.configs.split(","):
        name, k = c.split(":")
        configs.append((name, int(k)))

    for name, k in configs:
        refs = [ref_cut(paths[name], k, s) for s in seeds]
        ref_mean = sum(refs) / len(refs)
        print(f"== {name} k={k}: ref mean {ref_mean:.0f} (seeds {refs})", flush=True)
        for vname in args.variants.split(","):
            variant = VARIANTS[vname]
            cuts, walls = [], []
            # Each variant recompiles the static-arg kernels; dropping the
            # old executables keeps the process under vm.max_map_count
            # (LLVM's JIT mmaps per executable; 65530 maps ~= 2 variants).
            import jax

            jax.clear_caches()
            for s in seeds:
                c, w = our_cut(paths[name], k, s, variant, args.preset)
                cuts.append(c)
                walls.append(w)
            mean = sum(cuts) / len(cuts)
            print(
                f"  {vname:12s} mean {mean:8.0f} ratio {mean / ref_mean:5.2f} "
                f"spread [{min(cuts)},{max(cuts)}] wall {sum(walls)/len(walls):6.1f}s",
                flush=True,
            )


if __name__ == "__main__":
    main()
