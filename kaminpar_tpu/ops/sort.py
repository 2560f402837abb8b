"""Stable 1-D argsort whose TPU compile time does not grow with the input.

XLA's TPU sort unrolls a 1-D sort into one code block per bitonic stage, so
its compile time grows with the array: a stable two-key sort of 2^18
elements compiles for about 45 s for a v5e, one of 2^23 for about 110 s
(measured with the installed jaxlib against a described v5e).  The main
path sorts edges or heavy-row slots in every contraction level and LP
program, so a cold partition paid that for every level shape.  A sort along
the rows of a 2-D array with at most three operands compiles in one to two
seconds at any row count.

:func:`argsort` therefore lays the keys out as ``(R, W)`` rows and merges
sorted rows with a bitonic network: its cross-row stages run in a
``fori_loop`` (partner rows fetched by a row gather at a traced distance),
and its within-row stages are replaced by a row sort, since after the
cross-row stages of a merge each row already holds the elements it ends
with.  The loop body holds the only row sort, so the program compiles once
whatever ``R`` is.  The element index is the last key, so the order is
total and the permutation equals a stable sort's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: Row width of the merge layout.  Shorter arrays take ``lax.sort``
#: directly.  A row sort with few rows compiles slowly at larger widths
#: (about 15 s for a (12, 2048) three-operand sort on a v5e, against 1 s
#: at width 512), which matters when the sort is vmapped over a few lanes.
ROW = 512


def _less(a, b):
    """Lexicographic ``a < b`` over tuples of key arrays."""
    lt = a[-1] < b[-1]
    for x, y in zip(reversed(a[:-1]), reversed(b[:-1])):
        lt = (x < y) | ((x == y) & lt)
    return lt


def _exchange(ops, partner_row, lower, reverse: bool, active=None):
    """One compare-exchange stage: element (r, c) meets the element at
    (partner_row[r], c) — or at column W-1-c with ``reverse`` — and keeps the
    smaller one where ``lower`` (per row) is set, else the larger one.  With
    ``active`` (a traced flag) false every element keeps its own value."""
    other = tuple(jnp.take(x, partner_row, axis=0) for x in ops)
    if reverse:
        other = tuple(x[:, ::-1] for x in other)
    keep = _less(ops, other) == lower[:, None]
    if active is not None:
        keep = keep | ~active
    return tuple(jnp.where(keep, x, y) for x, y in zip(ops, other))


def rank_below(keys, order, p) -> jax.Array:
    """Mask of the elements whose position in ``order`` (the stable
    :func:`argsort` of ``keys``) is below ``p``: the first ``p`` elements
    of the sorted order, found by comparing against the ``p``-th key
    instead of scattering through the inverse permutation (a scatter that
    compiles slowly when vmapped)."""
    n = order.shape[0]
    last = order[jnp.clip(p - 1, 0, n - 1)]
    below = jnp.arange(n, dtype=order.dtype) <= last
    for k in reversed(tuple(keys)):
        kl = k[last]
        below = (k < kl) | ((k == kl) & below)
    return below & (p > 0)


def argsort(keys) -> jax.Array:
    """Stable permutation ordering the 1-D integer ``keys``
    lexicographically, the primary key first: ``jnp.lexsort(keys[::-1])``
    (module doc)."""
    keys = tuple(keys)
    n = keys[0].shape[0]
    nk = len(keys) + 1
    if n <= ROW:
        iota = jnp.arange(n, dtype=jnp.int32)
        return jax.lax.sort((*keys, iota), num_keys=nk, is_stable=False)[-1]
    R = 1 << (-(-n // ROW) - 1).bit_length()  # rows: next power of two
    N = R * ROW
    # Pad slots sort last: maximal keys, and indices above every real one.
    ops = tuple(
        jnp.concatenate([k, jnp.full(N - n, jnp.iinfo(k.dtype).max, k.dtype)])
        .reshape(R, ROW)
        for k in keys
    ) + (jnp.arange(N, dtype=jnp.int32).reshape(R, ROW),)
    rows = jnp.arange(R, dtype=jnp.int32)

    def merge(level, ops):
        # Merge pairs of sorted runs of ``half`` rows into runs of 2 * half,
        # then sort within rows.  Level 0 (half = 0) only sorts the rows.
        half = (jnp.int32(1) << level) >> 1
        ops = _exchange(
            ops, rows ^ jnp.maximum(2 * half - 1, 0), (rows & half) == 0,
            reverse=True, active=half > 0,
        )

        def clean(s, ops):
            d = half >> (s + 1)
            return _exchange(ops, rows ^ d, (rows & d) == 0, reverse=False)

        ops = jax.lax.fori_loop(0, jnp.maximum(level - 1, 0), clean, ops)
        return tuple(
            jax.lax.sort(ops, dimension=1, num_keys=nk, is_stable=False)
        )

    ops = jax.lax.fori_loop(0, R.bit_length(), merge, ops)
    return ops[-1].reshape(N)[:n]
