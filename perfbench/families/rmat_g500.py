"""Graph500 Kronecker (R-MAT) graph.

The generator of the Graph500 specification's reference code
(``kronecker_generator.m``): ``edge_factor * 2**scale`` edges, each placed
bit by bit with initiator probabilities A, B, C (D = 1 - A - B - C), then
vertex ids permuted and the edge list shuffled.  The partitioner gets the
simple undirected graph: self-loops and duplicate edges removed, unit
weights (Graph500's kernel-1 graph).
"""

from __future__ import annotations

import numpy as np

from perfbench.families.csr import undirected_csr


def generate(params: dict, seed: int):
    scale = int(params["scale"])
    edge_factor = int(params["edge_factor"])
    a, b, c = (float(params[x]) for x in ("A", "B", "C"))
    n = 1 << scale
    m = edge_factor * n
    rng = np.random.default_rng(seed)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ii = np.zeros(m, dtype=np.int64)
    jj = np.zeros(m, dtype=np.int64)
    for bit in range(scale):
        ii_bit = rng.random(m) > ab
        jj_bit = rng.random(m) > np.where(ii_bit, c_norm, a_norm)
        ii |= ii_bit.astype(np.int64) << bit
        jj |= jj_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    order = rng.permutation(m)
    return undirected_csr(n, perm[ii[order]], perm[jj[order]])
