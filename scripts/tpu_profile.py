#!/usr/bin/env python
"""On-silicon microprofile of the LP hot path (run on a TPU host).

Separates the three candidate bottlenecks for the weak r5 TPU number
(12.7M e/s, hbm_frac 2e-4):
  * per-dispatch latency         — trivial jitted op, warm, timed solo
  * transfer bandwidth           — H2D/D2H of a 256 MiB buffer
  * device compute               — lp_round_bucketed at several scales
    (flat per-round time => latency-bound; linear in m => compute-bound),
    plus isolated primitives (row sort, segment_sum, gather) at scale-20
    shapes to name the slow one.

Prints one JSON line per measurement.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def emit(**kw):
    print(json.dumps({k: (round(v, 6) if isinstance(v, float) else v)
                      for k, v in kw.items()}), flush=True)


def main() -> None:
    t0 = time.perf_counter()
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    emit(event="init", platform=dev.platform, init_s=time.perf_counter() - t0)

    # -- dispatch latency --------------------------------------------------
    @jax.jit
    def triv(x):
        return x + 1

    x = jnp.zeros((8,), jnp.int32)
    int(triv(x)[0])  # compile + sync
    for _ in range(3):
        t = time.perf_counter()
        int(triv(x)[0])
        emit(event="dispatch_rtt", seconds=time.perf_counter() - t)

    # -- transfer bandwidth ------------------------------------------------
    import numpy as np

    buf = np.zeros(64 * 1024 * 1024, np.int32)  # 256 MiB
    t = time.perf_counter()
    dbuf = jax.device_put(buf)
    dbuf.block_until_ready()
    h2d = time.perf_counter() - t
    t = time.perf_counter()
    _ = np.asarray(dbuf)
    d2h = time.perf_counter() - t
    emit(event="transfer", h2d_gbps=0.25 / max(h2d, 1e-9),
         d2h_gbps=0.25 / max(d2h, 1e-9), h2d_s=h2d, d2h_s=d2h)
    del dbuf, buf

    # -- primitive compute at scale-20-ish shapes -------------------------
    key = jax.random.PRNGKey(0)
    for name, shape, fn in [
        ("row_sort_64", (1 << 19, 64),
         lambda a: jax.lax.sort(a, dimension=1)),
        ("segment_sum_32m", (1 << 25,),
         lambda a: jax.ops.segment_sum(a, jnp.abs(a) % (1 << 20),
                                       num_segments=1 << 20)),
        ("gather_32m", (1 << 25,),
         lambda a: a[jnp.abs(a) % (1 << 25)]),
        ("sort1d_4m", (1 << 22,), lambda a: jax.lax.sort(a)),
    ]:
        a = jax.random.randint(key, shape, 0, 1 << 20, jnp.int32)
        f = jax.jit(fn)
        r = f(a)
        jax.tree_util.tree_leaves(r)[0].block_until_ready()
        t = time.perf_counter()
        for _ in range(3):
            r = f(a)
        jax.tree_util.tree_leaves(r)[0].block_until_ready()
        emit(event="primitive", name=name,
             seconds_per_call=(time.perf_counter() - t) / 3)
        del a, r

    # -- LP round scaling --------------------------------------------------
    from kaminpar_tpu.coarsening.max_cluster_weights import (
        compute_max_cluster_weight,
    )
    from kaminpar_tpu.context import Context
    from kaminpar_tpu.graph.generators import rmat_graph
    from kaminpar_tpu.ops import lp
    from kaminpar_tpu.utils import RandomState, next_key

    for scale in (16, 18, 20):
        RandomState.reseed(0)
        t = time.perf_counter()
        graph = rmat_graph(scale, edge_factor=16, seed=1)
        gen_s = time.perf_counter() - t
        pv = graph.padded()
        bv = graph.bucketed()
        ctx = Context()
        max_cw = compute_max_cluster_weight(
            ctx.coarsening, graph.n, graph.total_node_weight, 16, 0.03
        )
        idt = pv.row_ptr.dtype
        labels = jnp.concatenate(
            [jnp.arange(pv.n, dtype=idt),
             jnp.full(pv.n_pad - pv.n, pv.anchor, dtype=idt)]
        )
        state = lp.init_state(labels, pv.node_w, pv.n_pad)
        max_w = jnp.asarray(max_cw, dtype=idt)

        def one(state):
            return lp.lp_round_bucketed(
                state, next_key(), bv.buckets, bv.heavy, bv.gather_idx,
                pv.node_w, max_w, num_labels=pv.n_pad,
            )

        t = time.perf_counter()
        state = one(state)
        int(state.num_moved)
        compile_s = time.perf_counter() - t
        times = []
        for _ in range(3):
            t = time.perf_counter()
            state = one(state)
            int(state.num_moved)
            times.append(time.perf_counter() - t)
        emit(event="lp_round", scale=scale, m=graph.m, gen_s=gen_s,
             compile_plus_first_s=compile_s, round_s=min(times),
             edges_per_sec=graph.m / min(times),
             num_buckets=len(bv.buckets))
        del graph, pv, bv, state


if __name__ == "__main__":
    main()
