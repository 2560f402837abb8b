"""Main-path programs compiled by the TPU compiler for a described v5e chip.

Nothing here runs: each test lowers and compiles a program for one chip of
a described ``v5e:2x2`` topology (no TPU attached), at the shapes of the
finest level of ``rmat_graph(18, edge_factor=16, seed=1)`` — the graph
``chip_smoke.py`` partitions.  A compile that the chip's compiler refuses,
or that does not fit the chip's 16 GiB, fails here at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all import
this file.  The persistent compilation cache is off around the compiles (an
entry written for a described chip cannot be read back without one).

Programs whose compile takes well over ten seconds here (the LP sweep loop,
the balancer round) are compiled by the rehearsal recorded in CHANGES.md,
not by these tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from kaminpar_tpu.graph import bucketed as bk
from kaminpar_tpu.graph.csr import _next_bucket
from kaminpar_tpu.ops import lp, pallas_lp
from kaminpar_tpu.utils.intmath import next_pow2

#: One TPU v5e chip's HBM.
HBM_BYTES = 16 * 2**30

# Finest level of rmat_graph(18, edge_factor=16, seed=1) after isolated-node
# removal: nodes, directed edges, rows per bucket width, heavy rows and
# their slots.  Padded sizes come from the shape ladder.
N, M = 174087, 7610904
BUCKET_ROWS = {8: 104880, 16: 16043, 32: 21932, 64: 14374, 128: 4242,
               256: 8568, 1024: 3060, 2048: 816}
HEAVY_ROWS, HEAVY_SLOTS = 172, 911609
N_PAD, M_PAD = _next_bucket(N), _next_bucket(M)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture
def tpu_kernels(monkeypatch):
    """Lower the Pallas kernels for the chip (interpret mode is what the
    CPU backend would otherwise pick)."""
    monkeypatch.setattr(pallas_lp, "_interpret", lambda: False)


def _shape(one_chip, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)


def _key(one_chip):
    k = jax.eval_shape(lambda: jax.random.key(0))
    return jax.ShapeDtypeStruct(k.shape, k.dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used <= HBM_BYTES, used
    return compiled


def _bucket(one_chip, w):
    R = next_pow2(BUCKET_ROWS[w], 8)
    return bk.Bucket(
        _shape(one_chip, (R,)), _shape(one_chip, (R, w)),
        _shape(one_chip, (R, w)),
    )


def test_contract_core_compiles(one_chip, no_cache):
    from kaminpar_tpu.ops.contraction import _contract_device

    s = lambda *shape: _shape(one_chip, shape)  # noqa: E731
    _compile(
        _contract_device, s(N_PAD), s(M_PAD), s(M_PAD), s(M_PAD), s(N_PAD)
    )


@pytest.mark.parametrize("w", sorted(BUCKET_ROWS))
def test_device_bucket_compiles(one_chip, no_cache, w):
    s = lambda *shape: _shape(one_chip, shape)  # noqa: E731
    R_pad = next_pow2(BUCKET_ROWS[w], 8)
    _compile(
        lambda *a: bk._device_bucket(*a, w=w, R_pad=R_pad),
        s(N_PAD + 1), s(M_PAD), s(M_PAD), s(N_PAD), s(), s(10), s(), s(),
    )


def test_device_heavy_compiles(one_chip, no_cache):
    s = lambda *shape: _shape(one_chip, shape)  # noqa: E731
    Hr_pad = next_pow2(HEAVY_ROWS + 1, 8)
    Hs_pad = next_pow2(HEAVY_SLOTS, 8)
    _compile(
        lambda *a: bk._device_heavy(*a, Hr_pad=Hr_pad, Hs_pad=Hs_pad),
        s(N_PAD + 1), s(M_PAD), s(M_PAD), s(M_PAD), s(N_PAD), s(), s(), s(),
    )


def test_edge_argsort_compiles(one_chip, no_cache):
    """The two-key edge sort of contraction at the full edge count (the
    program whose XLA 1-D sort compile grew with its length)."""
    from kaminpar_tpu.ops.sort import argsort

    s = lambda *shape: _shape(one_chip, shape)  # noqa: E731
    _compile(lambda a, b: argsort((a, b)), s(M_PAD), s(M_PAD))


def _refused(fn, *args):
    """The chip's compiler refuses the fused Pallas kernels on this jaxlib;
    ``resolve_lp_kernel`` keeps them off the TPU for that reason."""
    with pytest.raises(Exception, match=pallas_lp.MOSAIC_REFUSAL):
        jax.jit(fn).lower(*args).compile()


def test_pallas_rate_kernel_refused(one_chip, no_cache, tpu_kernels):
    s = lambda *shape: _shape(one_chip, shape)  # noqa: E731
    b = _bucket(one_chip, 8)
    R, w = b.cols.shape

    def rate(labels, node_w, lw, maxw, nodes, cols, wgts, tie):
        return pallas_lp._rate_bucket(
            labels, node_w, lw, maxw, (nodes, cols, wgts), tie,
            external_only=False, respect_caps=True, tie_break="uniform",
            maxw_scalar=True,
        )

    _refused(rate, s(N_PAD), s(N_PAD), s(N_PAD), s(1), b.nodes, b.cols,
             b.wgts, s(R, w))


def test_pallas_commit_kernel_refused(one_chip, no_cache, tpu_kernels):
    s = lambda *shape: _shape(one_chip, shape)  # noqa: E731
    state = lp.LPState(s(N_PAD), s(N_PAD), s())

    def commit(state, key, target, tconn, own, node_w, maxw):
        return pallas_lp.commit_moves(
            state, key, target, tconn, own, node_w, maxw, N_PAD
        )

    _refused(commit, state, _key(one_chip), s(N_PAD), s(N_PAD), s(N_PAD),
             s(N_PAD), s())


def test_pallas_compressed_rate_kernel_refused(one_chip, no_cache,
                                               tpu_kernels):
    """Shapes from a small graph's compressed device view: the refusal is
    in the kernel's gather, whatever the size."""
    from kaminpar_tpu.graph import generators
    from kaminpar_tpu.graph.compressed import compress
    from kaminpar_tpu.graph.device_compressed import DeviceCompressedView

    view = DeviceCompressedView(
        compress(generators.rmat_graph(10, 8, seed=2))
    )
    cb = view.buckets[0]
    spec = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=one_chip
    )
    stream, cb = jax.tree_util.tree_map(spec, (view.stream, cb))
    n_pad = view.n_pad
    s = lambda *shape: _shape(one_chip, shape)  # noqa: E731
    R, w = int(cb.nodes.shape[0]), int(cb.slot.shape[0])

    def rate(labels, node_w, lw, maxw, stream, cb, tie):
        return pallas_lp._rate_compressed_bucket(
            labels, node_w, lw, maxw, stream, cb, tie,
            external_only=False, respect_caps=True, tie_break="uniform",
            maxw_scalar=True,
        )

    _refused(rate, s(n_pad), s(n_pad), s(n_pad), s(1), stream, cb, s(R, w))


def test_pallas_round_refused(one_chip, no_cache, tpu_kernels):
    """The whole fused LP round (rate + commit kernels) for the chip."""
    s = lambda *shape: _shape(one_chip, shape)  # noqa: E731
    buckets = tuple(_bucket(one_chip, w) for w in sorted(BUCKET_ROWS))
    Hr_pad = next_pow2(HEAVY_ROWS + 1, 8)
    Hs_pad = next_pow2(HEAVY_SLOTS, 8)
    heavy = bk.HeavyPart(s(Hr_pad), s(Hs_pad), s(Hs_pad), s(Hs_pad))
    state = lp.LPState(s(N_PAD), s(N_PAD), s())

    def round_(state, key, buckets, heavy, gather_idx, node_w, maxw):
        return pallas_lp.lp_round_bucketed(
            state, key, buckets, heavy, gather_idx, node_w, maxw,
            num_labels=N_PAD,
        )

    _refused(round_, state, _key(one_chip), buckets, heavy, s(N_PAD),
             s(N_PAD), s())
