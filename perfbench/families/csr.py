"""Edge list to symmetric CSR with unit weights (NumPy only)."""

from __future__ import annotations

import numpy as np


def undirected_csr(n: int, u: np.ndarray, v: np.ndarray):
    """``(row_ptr, col_idx)`` of the simple undirected graph on ``n`` nodes
    with an edge between ``u[i]`` and ``v[i]``: self-loops dropped,
    duplicates merged, both directions stored, rows sorted by neighbour."""
    u = np.asarray(u, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    keep = u != v
    src = np.concatenate([u[keep], v[keep]])
    dst = np.concatenate([v[keep], u[keep]])
    key = np.unique(src * n + dst)
    src, dst = key // n, key % n
    row_ptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=row_ptr[1:])
    idt = np.int32 if len(dst) < 2**31 and n < 2**31 else np.int64
    return row_ptr.astype(idt), dst.astype(idt)
