"""The fast-compiling sort / scan / gather forms (ops/sort.py,
ops/segment.py) against their jnp / NumPy references, element for
element."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaminpar_tpu.ops import segment
from kaminpar_tpu.ops.sort import ROW, argsort

SIZES = [1, 7, ROW, ROW + 1, 3 * ROW + 5, 16 * ROW]


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("hi", [3, 2**31 - 1])
def test_argsort_matches_stable_lexsort(n, hi):
    rng = np.random.default_rng(n + hi % 97)
    a = jnp.asarray(rng.integers(0, hi, n), jnp.int32)
    b = jnp.asarray(rng.integers(-4, 4, n), jnp.int32)
    got = jax.jit(argsort)((a, b))
    assert np.array_equal(got, jnp.lexsort((b, a)))
    got1 = jax.jit(argsort)((a,))
    assert np.array_equal(got1, jnp.argsort(a, stable=True))


@pytest.mark.parametrize("external_only,respect_caps", [(False, False), (True, True)])
def test_flat_best_moves_same_in_both_forms(external_only, respect_caps):
    """The fast-compile sort/scan forms give the flat kernel's exact moves."""
    from kaminpar_tpu.ops.bucketed_gains import flat_best_moves

    rng = np.random.default_rng(3)
    S, rows, L = 3 * ROW + 5, 300, 40
    i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    args = (
        jax.random.PRNGKey(0), i32(rng.integers(0, rows, S)),
        i32(rng.integers(0, L, S)), i32(rng.integers(0, 5, S)),
        i32(rng.integers(0, L, rows)), i32(rng.integers(1, 4, rows)),
        i32(rng.integers(0, 30, L)), i32(rng.integers(20, 40, L)),
    )
    kw = dict(num_rows=rows, external_only=external_only,
              respect_caps=respect_caps)
    want = flat_best_moves(*args, **kw)
    got = flat_best_moves(*args, **kw, fast_compile=True)
    for w, g in zip(want, got):
        assert np.array_equal(w, g)


@pytest.mark.parametrize("n", [1, 5, 1024, 1025, 3000, 300000])
def test_scans_match_numpy(n):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.integers(-50, 50, n), jnp.int32)
    m = jnp.asarray(rng.random(n) < 0.3)
    assert np.array_equal(segment.cumsum(x), np.cumsum(x))
    assert np.array_equal(segment.cummax(x), np.maximum.accumulate(x))
    assert np.array_equal(segment.cumsum(m), np.cumsum(m))


@pytest.mark.parametrize("shape", [(3, 1), (5, 8), (7, 128), (2, 2048)])
def test_row_scans_and_gathers_match_numpy(shape):
    rng = np.random.default_rng(shape[1])
    x = jnp.asarray(rng.integers(-50, 50, shape), jnp.int32)
    assert np.array_equal(segment.row_cumsum(x), np.cumsum(x, axis=1))
    assert np.array_equal(
        segment.row_cummax(x), np.maximum.accumulate(x, axis=1)
    )
    table = jnp.asarray(rng.integers(0, 1000, 500), jnp.int32)
    idx = jnp.asarray(rng.integers(0, 500, shape), jnp.int32)
    assert np.array_equal(segment.take(table, idx), table[idx])
    col = jnp.asarray(rng.integers(0, shape[1], shape[0]), jnp.int32)
    assert np.array_equal(
        segment.take_rows(x, col),
        jnp.take_along_axis(x, col[:, None], axis=1)[:, 0],
    )


@pytest.mark.parametrize("n", [1, 5, 1025, 300000])
def test_nonzero_and_compact_match_jnp(n):
    rng = np.random.default_rng(n)
    m = jnp.asarray(rng.random(n) < 0.3)
    vals = jnp.asarray(rng.integers(1, 100, n), jnp.int32)
    for size in (1, n // 3 + 1, n):
        want = jnp.nonzero(m, size=size, fill_value=7)[0]
        assert np.array_equal(segment.nonzero(m, size=size, fill_value=7), want)
        kept = np.asarray(vals)[np.asarray(m)][:size]
        got = np.asarray(segment.compact(vals, m, size))
        assert np.array_equal(got[: len(kept)], kept)
        assert not got[len(kept):].any()


@pytest.mark.parametrize("n", [1, 9, 3 * ROW + 5])
def test_rank_below_matches_inverse_permutation(n):
    from kaminpar_tpu.ops.sort import rank_below

    rng = np.random.default_rng(n)
    keys = (jnp.asarray(rng.integers(0, 4, n), jnp.int32),
            jnp.asarray(rng.integers(-3, 3, n), jnp.int32))
    order = argsort(keys)
    for p in (0, 1, n // 2, n):
        want = jnp.zeros(n, bool).at[order].set(jnp.arange(n) < p)
        assert np.array_equal(rank_below(keys, order, p), want)
