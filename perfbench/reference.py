"""The plain reference: cut and balance of a partition in NumPy.

It works from the host CSR the benchmark generated and the partition the
program returned, and imports nothing of the program.  The balance limit
is KaMinPar's: a block may weigh ``max(floor((1 + eps) * ceil(W / k)),
ceil(W / k) + max node weight)``.
"""

from __future__ import annotations

import math

import numpy as np


def edge_cut(row_ptr, col_idx, part) -> int:
    """Undirected edges (unit weights) whose ends lie in different blocks."""
    src = np.repeat(np.arange(len(row_ptr) - 1), np.diff(row_ptr))
    return int(np.count_nonzero(part[src] != part[col_idx])) // 2


def max_block_weight(n: int, k: int, epsilon: float) -> int:
    """Balance limit of one block for ``n`` unit-weight nodes."""
    perfect = -(-n // k)
    return max(math.floor((1.0 + epsilon) * perfect), perfect + 1)


def check(row_ptr, col_idx, part, reported_cut, k: int, epsilon: float) -> dict:
    """Readings of one partition: ``valid`` (right length and block ids in
    range), ``cut``, ``cut_mismatch`` (|reported - NumPy cut|),
    ``max_block_weight`` and ``empty_blocks``."""
    n = len(row_ptr) - 1
    part = np.asarray(part)
    valid = (
        part.shape == (n,)
        and np.issubdtype(part.dtype, np.integer)
        and bool(np.all((part >= 0) & (part < k)))
    )
    if not valid:
        return {"valid": False}
    part = part.astype(np.int64)
    cut = edge_cut(row_ptr, col_idx, part)
    weights = np.bincount(part, minlength=k)
    return {
        "valid": True,
        "cut": cut,
        "cut_mismatch": abs(int(reported_cut) - cut)
        if reported_cut is not None else None,
        "max_block_weight": int(weights.max()),
        "empty_blocks": int(np.count_nonzero(weights == 0)),
    }
