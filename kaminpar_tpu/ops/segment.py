"""Sort-reduce / reduce-by-key primitives — the universal TPU substrate.

Everything the reference implements with per-thread hash maps
(``RatingMap``/``FastResetArray``, kaminpar-common/datastructures/rating_map.h)
becomes, on TPU, a *sort by key + segmented reduction* over flat edge arrays:
dynamic hashing does not map to XLA, but an O(m log m) bitonic sort plus O(m)
scans/scatters does, with fully static shapes.  These helpers are shared by
the LP engine (ops/lp.py) and contraction (ops/contraction.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# XLA's TPU scans (cumsum / cummax) and narrow-minor gathers compile in a
# time that depends on the array's size and shape, and not monotonically: a
# row cumsum of an (8192, 128) int32 array takes about 31 s for a v5e, a
# 1-D cumsum of 2^20 elements about 27 s, a gather with (131072, 8) indices
# about 26 s, while most sizes take a second or less.  The forms below
# compile in about a second at any size.  Integer operands only, so the
# results are exact.

#: Row width of the blocked 1-D scans.
_SCAN_ROW = 1024


def _row_scan(x, combine, identity):
    """Inclusive scan along axis 1 by log-step doubling (``log2(w)``
    shifted combines)."""
    R, w = x.shape
    d = 1
    while d < w:
        shifted = jnp.concatenate(
            [jnp.full((R, d), identity, x.dtype), x[:, :-d]], axis=1
        )
        x = combine(x, shifted)
        d *= 2
    return x


def _scan(x, combine, identity):
    """Inclusive 1-D scan as row scans of an ``(R, W)`` layout plus the scan
    of the row totals."""
    n = x.shape[0]
    if n <= _SCAN_ROW:
        return _row_scan(x[None, :], combine, identity)[0]
    R = -(-n // _SCAN_ROW)
    pad = jnp.full(R * _SCAN_ROW - n, identity, x.dtype)
    rows = _row_scan(
        jnp.concatenate([x, pad]).reshape(R, _SCAN_ROW), combine, identity
    )
    before = jnp.concatenate([
        jnp.full(1, identity, x.dtype),
        _scan(rows[:, -1], combine, identity)[:-1],
    ])
    return combine(rows, before[:, None]).reshape(-1)[:n]


def _int(x):
    return x.astype(jnp.int32) if x.dtype == jnp.bool_ else x


def cumsum(x) -> jax.Array:
    """``jnp.cumsum`` of a 1-D integer or boolean array (bools count as
    int32)."""
    return _scan(_int(x), jnp.add, 0)


def cummax(x) -> jax.Array:
    """``lax.cummax`` of a 1-D integer array."""
    return _scan(x, jnp.maximum, jnp.iinfo(x.dtype).min)


def row_cumsum(x) -> jax.Array:
    """``jnp.cumsum(x, axis=1)`` of a 2-D integer or boolean array."""
    return _row_scan(_int(x), jnp.add, 0)


def row_cummax(x) -> jax.Array:
    """``lax.cummax(x, axis=1)`` of a 2-D integer array."""
    return _row_scan(x, jnp.maximum, jnp.iinfo(x.dtype).min)


def take(table, idx) -> jax.Array:
    """``table[idx]`` for a 1-D ``table`` and a 2-D index, gathered through
    the transposed index (see the note above)."""
    return table[idx.T].T


def take_rows(x, col) -> jax.Array:
    """``x[r, col[r]]`` for a 2-D ``x`` (``take_along_axis`` of one column
    per row), as a select-and-sum over the row rather than a gather."""
    hit = jnp.arange(x.shape[1], dtype=col.dtype)[None, :] == col[:, None]
    return jnp.sum(jnp.where(hit, x, 0), axis=1, dtype=x.dtype)


def compact(values, mask, size: int, fill_value=0) -> jax.Array:
    """The ``values`` where ``mask`` is set, in order, in a ``size``-slot
    array padded with ``fill_value`` (entries past ``size`` are dropped).

    One scatter-add with sorted indices: each slot receives one masked
    value and zeros.  XLA's TPU compile time of a scatter into a large
    output with unsorted indices is long (about 13 s into 2^23 slots on a
    v5e), and well under a second with sorted ones."""
    slot = cumsum(mask) - 1
    count = slot[-1] + 1 if mask.shape[0] else 0
    out = jax.ops.segment_sum(
        jnp.where(mask, values, 0), jnp.maximum(slot, 0),
        num_segments=size, indices_are_sorted=True,
    )
    return jnp.where(
        jnp.arange(size) < count, out, jnp.asarray(fill_value, out.dtype)
    )


def nonzero(mask, *, size: int, fill_value) -> jax.Array:
    """``jnp.nonzero(mask, size=size, fill_value=fill_value)[0]`` for a 1-D
    mask (see :func:`compact`)."""
    idx = jnp.arange(mask.shape[0], dtype=jnp.int32)
    return compact(idx, mask, size, fill_value)


def run_starts(sorted_key) -> jax.Array:
    """Boolean mask marking the first slot of every run of equal keys in a
    sorted array."""
    m = sorted_key.shape[0]
    if m == 0:
        return jnp.zeros(0, dtype=bool)
    return jnp.concatenate(
        [jnp.ones(1, dtype=bool), sorted_key[1:] != sorted_key[:-1]]
    )


def run_starts2(sorted_a, sorted_b) -> jax.Array:
    """run_starts for a composite (a, b) key, lexsorted."""
    m = sorted_a.shape[0]
    if m == 0:
        return jnp.zeros(0, dtype=bool)
    return jnp.concatenate(
        [
            jnp.ones(1, dtype=bool),
            (sorted_a[1:] != sorted_a[:-1]) | (sorted_b[1:] != sorted_b[:-1]),
        ]
    )


def run_ids(first_mask) -> jax.Array:
    """Dense run index per slot: [0, #runs)."""
    return cumsum(first_mask.astype(jnp.int32)) - 1


def reduce_runs(values, run_id, num_slots: int):
    """Sum `values` within each run (run_id from :func:`run_ids`).

    Returns an array of length ``num_slots`` (upper bound on #runs); entries
    past the last run are zero.
    """
    return jax.ops.segment_sum(values, run_id, num_segments=num_slots)


def segment_prefix_sum(values, first_mask):
    """Inclusive prefix sum of `values` restarting at every run start.

    For slots sorted by key: within-run running total, used for strict
    capacity-respecting move acceptance (the TPU stand-in for the reference's
    CAS loop at label_propagation.h:817-841).
    """
    cums = cumsum(values)
    # Value of the global cumsum just *before* each run begins.
    before = jnp.where(first_mask, cums - values, 0)
    rid = run_ids(first_mask)
    run_base = jax.ops.segment_max(before, rid, num_segments=values.shape[0])
    return cums - run_base[rid]
