"""Fused Pallas LP kernels vs the XLA path — bit-identical contract.

The Pallas round (ops/pallas_lp.py) must return the SAME labels, label
weights, and admission decisions as the XLA round (ops/lp.py) — not
approximately, bit for bit: all random draws happen outside the kernels with
the XLA path's key schedule, and the in-kernel math is integer and
order-independent (the stable bitonic sort reproduces lax.sort exactly).
Off-TPU the kernels run with interpret=True, so these tests exercise the
exact kernel logic the TPU would compile.

Also here: the shape-bucket tests for the geometric padding ladder
(utils/intmath.next_shape_bucket) and the label-space bucket
(lp.num_labels_bucket).  Note on scope: full partitions are NOT invariant
to the padding policy because threefry draws depend on the array shape
(verified: jax.random.randint(key, (n,)) is not a prefix of (key, (n+p,))),
so the identity assertions target the stages where padding is exactly inert
(rating, contraction, label-space padding) and end-to-end checks assert
feasibility/quality instead.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaminpar_tpu.graph import generators
from kaminpar_tpu.ops import lp, pallas_lp
from kaminpar_tpu.utils import next_key, reseed


def _init(g, num_labels=None):
    pv = g.padded()
    bv = g.bucketed()
    idt = pv.row_ptr.dtype
    labels = jnp.concatenate(
        [jnp.arange(pv.n, dtype=idt), jnp.full(pv.n_pad - pv.n, pv.anchor, dtype=idt)]
    )
    state = lp.init_state(labels, pv.node_w, num_labels or pv.n_pad)
    return pv, bv, state


def _assert_state_equal(a: lp.LPState, b: lp.LPState, ctxmsg=""):
    assert bool(jnp.all(a.labels == b.labels)), f"labels diverge {ctxmsg}"
    assert bool(jnp.all(a.label_weights == b.label_weights)), (
        f"label weights diverge {ctxmsg}"
    )
    assert int(a.num_moved) == int(b.num_moved), f"num_moved diverges {ctxmsg}"


GRAPHS = {
    "rmat": lambda: generators.rmat_graph(9, 8, seed=2),
    "grid": lambda: generators.grid2d_graph(24, 24),
    "star": lambda: generators.star_graph(96),
}


def test_bitonic_matches_stable_sort(rng):
    for w in (8, 32, 128):
        L = jnp.asarray(rng.integers(0, 7, (16, w)).astype(np.int32))
        W = jnp.asarray(rng.integers(0, 100, (16, w)).astype(np.int32))
        Ls, Ws = jax.lax.sort((L, W), dimension=1, num_keys=1)
        Lb, Wb = pallas_lp._bitonic_sort_rows(L, W)
        assert bool(jnp.all(Ls == Lb)), w
        # Stability: equal keys keep original (value) order.
        assert bool(jnp.all(Ws == Wb)), w


@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_round_bit_identical_clustering(gname):
    g = GRAPHS[gname]()
    pv, bv, state = _init(g)
    st_x, st_p = state, state
    max_w = jnp.asarray(25, dtype=pv.row_ptr.dtype)
    for _ in range(3):
        key = next_key()
        st_x = lp.lp_round_bucketed(
            st_x, key, bv.buckets, bv.heavy, bv.gather_idx, pv.node_w,
            max_w, num_labels=pv.n_pad,
        )
        st_p = pallas_lp.lp_round_bucketed(
            st_p, key, bv.buckets, bv.heavy, bv.gather_idx, pv.node_w,
            max_w, num_labels=pv.n_pad,
        )
        _assert_state_equal(st_x, st_p, f"on {gname}")


@pytest.mark.parametrize("tie_break", ["uniform", "lightest"])
def test_round_bit_identical_refinement(rng, tie_break):
    """num_labels = k instantiation (block mode) with the refiner's option
    surface (active_prob, tie moves, per-block weight table)."""
    g = generators.rmat_graph(9, 8, seed=5)
    pv = g.padded()
    bv = g.bucketed()
    k = 8
    part = pv.pad_node_array(
        jnp.asarray(rng.integers(0, k, g.n).astype(np.int32)), 0
    )
    st_x = lp.init_state(part, pv.node_w, k)
    st_p = st_x
    max_w = jnp.full(k, int(g.total_node_weight / k * 1.3), dtype=pv.node_w.dtype)
    for _ in range(3):
        key = next_key()
        kwargs = dict(
            num_labels=k, active_prob=0.5, allow_tie_moves=True,
            tie_break=tie_break,
        )
        st_x = lp.lp_round_bucketed(
            st_x, key, bv.buckets, bv.heavy, bv.gather_idx, pv.node_w,
            max_w, **kwargs,
        )
        st_p = pallas_lp.lp_round_bucketed(
            st_p, key, bv.buckets, bv.heavy, bv.gather_idx, pv.node_w,
            max_w, **kwargs,
        )
        _assert_state_equal(st_x, st_p, f"tie_break={tie_break}")


def test_commit_admission_bit_identical(rng):
    """The fused commit kernel admits exactly the XLA auction's set — the
    admission mask is compared through the committed labels with contended
    capacities (many movers per target, tight caps)."""
    n, k = 512, 6
    labels = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    node_w = jnp.asarray(rng.integers(1, 4, n).astype(np.int32))
    state = lp.init_state(labels, node_w, k)
    target = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    tconn = jnp.asarray(rng.integers(0, 20, n).astype(np.int32))
    own_conn = jnp.asarray(rng.integers(0, 20, n).astype(np.int32))
    max_w = jnp.full(k, int(np.asarray(state.label_weights).max()) + 15,
                     dtype=jnp.int32)
    key = next_key()
    ref = lp._commit_moves(
        state, key, target, tconn, own_conn, node_w, max_w, k,
        active_prob=0.8, allow_tie_moves=True,
    )
    fused = pallas_lp.commit_moves(
        state, key, target, tconn, own_conn, node_w, max_w, k,
        active_prob=0.8, allow_tie_moves=True,
    )
    _assert_state_equal(ref, fused)
    # Strictness must hold for the fused kernel as well.
    assert int(jnp.max(fused.label_weights)) <= int(jnp.max(max_w))


def test_iterate_bit_identical():
    g = generators.rmat_graph(9, 8, seed=3)
    pv, bv, state = _init(g)
    max_w = jnp.asarray(40, dtype=pv.row_ptr.dtype)
    key = next_key()
    args = (bv.buckets, bv.heavy, bv.gather_idx, pv.node_w, max_w,
            jnp.int32(1), jnp.int32(4))
    # The iterate entry points donate their state carry — each call gets an
    # independently built state.
    st_x = lp.lp_iterate_bucketed(state, key, *args, num_labels=pv.n_pad)
    _, _, state2 = _init(g)
    st_p = pallas_lp.lp_iterate_bucketed(state2, key, *args, num_labels=pv.n_pad)
    _assert_state_equal(st_x, st_p)


def test_colored_round_bit_identical(rng):
    g = generators.grid2d_graph(16, 16)
    pv = g.padded()
    bv = g.bucketed()
    k = 4
    part = pv.pad_node_array(
        jnp.asarray(rng.integers(0, k, g.n).astype(np.int32)), 0
    )
    st_x = lp.init_state(part, pv.node_w, k)
    st_p = st_x
    active = jnp.asarray(rng.random(pv.n_pad) < 0.5)
    max_w = jnp.full(k, 100, dtype=pv.node_w.dtype)
    key = next_key()
    st_x = lp.lp_round_colored(
        st_x, key, bv.buckets, bv.heavy, bv.gather_idx, pv.node_w, max_w,
        active, num_labels=k,
    )
    st_p = pallas_lp.lp_round_colored(
        st_p, key, bv.buckets, bv.heavy, bv.gather_idx, pv.node_w, max_w,
        active, num_labels=k,
    )
    _assert_state_equal(st_x, st_p)


def test_clusterer_backend_switch_bit_identical():
    """The lp_kernel config knob routes the clusterer through the Pallas
    iterate and yields the exact same clustering."""
    from kaminpar_tpu.coarsening.lp_clusterer import LPClustering
    from kaminpar_tpu.context import LabelPropagationContext

    g = generators.rmat_graph(9, 8, seed=4)
    out = {}
    for kernel in ("xla", "pallas"):
        reseed(11)
        ctx = LabelPropagationContext(num_iterations=3, lp_kernel=kernel)
        out[kernel] = np.asarray(
            LPClustering(ctx).compute_clustering(g, max_cluster_weight=30)
        )
    assert np.array_equal(out["xla"], out["pallas"])


def test_resolve_lp_kernel():
    assert pallas_lp.resolve_lp_kernel("xla") == "xla"
    assert pallas_lp.resolve_lp_kernel("pallas") == "pallas"
    # auto is the XLA lowering on every backend: the fused kernels do not
    # compile for a TPU (tests/test_tpu_compile.py).
    assert pallas_lp.resolve_lp_kernel("auto") == "xla"
    with pytest.raises(ValueError, match="lp_kernel"):
        pallas_lp.resolve_lp_kernel("mosaic")


def test_resolve_lp_kernel_pallas_on_tpu_raises(monkeypatch):
    """On a TPU backend, "pallas" raises with the compiler's reason
    instead of failing mid-run (auto stays on XLA)."""
    monkeypatch.setattr(pallas_lp.jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match=pallas_lp.MOSAIC_REFUSAL):
        pallas_lp.resolve_lp_kernel("pallas")
    assert pallas_lp.resolve_lp_kernel("auto") == "xla"


def test_lp_kernel_config_roundtrip():
    from kaminpar_tpu.config import dump_toml, load_toml
    from kaminpar_tpu.context import Context

    ctx = Context()
    ctx.coarsening.lp.lp_kernel = "pallas"
    ctx2 = load_toml(dump_toml(ctx))
    assert ctx2.coarsening.lp.lp_kernel == "pallas"


# ---------------------------------------------------------------------------
# Shape buckets
# ---------------------------------------------------------------------------


def test_next_shape_bucket_ladder():
    from kaminpar_tpu.utils.intmath import next_shape_bucket

    prev = 0
    for x in [0, 1, 7, 255, 256, 300, 400, 511, 512, 700, 724, 1000, 5000,
              40347, 65536, 10**6]:
        b = next_shape_bucket(x, 256)
        assert b > x, (x, b)
        assert b >= 256
        # sqrt(2) ladder: never more than ~45% slack (alignment adds a hair)
        assert b <= max(256, int(x * 1.5) + 128), (x, b)
        assert b >= prev or x < prev  # monotone in x
    # O(log n) distinct buckets across 5 decades, ~2 per octave.
    buckets = {next_shape_bucket(x, 256) for x in range(1, 10**6, 997)}
    assert len(buckets) <= 2 * 21  # 2 rungs x log2(1e6) octaves


def test_contraction_invariant_to_padding(rng):
    """Pad slots/nodes are exactly inert in contraction: inflating the
    padding must produce the identical coarse graph."""
    import kaminpar_tpu.graph.csr as csr_mod
    from kaminpar_tpu.graph.csr import CSRGraph
    from kaminpar_tpu.ops.contraction import contract_clustering

    edges = rng.integers(0, 150, (400, 2))
    g1 = generators.from_edge_list(150, edges)
    labels = rng.integers(0, 150, 150).astype(np.int32)

    coarse1, _ = contract_clustering(
        g1, g1.padded().pad_node_array(jnp.asarray(labels), g1.padded().anchor)
    )
    orig = csr_mod._next_bucket
    try:
        csr_mod._next_bucket = lambda x, minimum=256: orig(x, 2048)
        g2 = CSRGraph(g1.row_ptr, g1.col_idx, g1.node_w, g1.edge_w)
        coarse2, _ = contract_clustering(
            g2, g2.padded().pad_node_array(jnp.asarray(labels), g2.padded().anchor)
        )
    finally:
        csr_mod._next_bucket = orig
    assert coarse1.n == coarse2.n and coarse1.m == coarse2.m
    for attr in ("row_ptr", "col_idx", "node_w", "edge_w"):
        assert np.array_equal(
            np.asarray(getattr(coarse1, attr)), np.asarray(getattr(coarse2, attr))
        ), attr


def test_num_labels_bucket_refinement_identical(rng):
    """Padding the label space (refinement k ladder -> one bucket) is
    bit-inert: the same round on num_labels=k and num_labels=bucket(k)
    commits identical labels."""
    g = generators.rmat_graph(9, 8, seed=6)
    pv = g.padded()
    bv = g.bucketed()
    k = 5
    k_pad = lp.num_labels_bucket(k)
    assert k_pad >= 64
    part = pv.pad_node_array(
        jnp.asarray(rng.integers(0, k, g.n).astype(np.int32)), 0
    )
    max_w = jnp.full(k, int(g.total_node_weight / k * 1.2), dtype=pv.node_w.dtype)
    max_w_pad = jnp.concatenate(
        [max_w, jnp.zeros(k_pad - k, dtype=max_w.dtype)]
    )
    st_a = lp.init_state(part, pv.node_w, k)
    st_b = lp.init_state(part, pv.node_w, k_pad)
    key = next_key()
    st_a = lp.lp_round_bucketed(
        st_a, key, bv.buckets, bv.heavy, bv.gather_idx, pv.node_w, max_w,
        num_labels=k,
    )
    st_b = lp.lp_round_bucketed(
        st_b, key, bv.buckets, bv.heavy, bv.gather_idx, pv.node_w, max_w_pad,
        num_labels=k_pad,
    )
    assert bool(jnp.all(st_a.labels == st_b.labels))
    assert bool(jnp.all(st_b.label_weights[k:] == 0))
    assert bool(jnp.all(st_a.label_weights == st_b.label_weights[:k]))


def _run_vcycle(scale: int, k: int = 16):
    from kaminpar_tpu.graph.metrics import edge_cut, is_feasible
    from kaminpar_tpu.kaminpar import KaMinPar
    from kaminpar_tpu.presets import create_context_by_preset_name
    from kaminpar_tpu.utils import compile_stats

    g = generators.rmat_graph(scale, edge_factor=8, seed=1)
    ctx = create_context_by_preset_name("vcycle")
    ctx.vcycles = (4,)
    s = KaMinPar(ctx)
    s.set_graph(g)
    compile_stats.reset()
    part = s.compute_partition(k=k, epsilon=0.03)
    assert is_feasible(g, part, k, s.ctx.partition.max_block_weights)
    return compile_stats.snapshot(), int(edge_cut(g, part))


def test_vcycle_shape_bucket_count_small():
    """Fast census bound: a small v-cycle touches O(log n) padded
    LP/contraction shape buckets."""
    snap, _ = _run_vcycle(11)
    assert snap.get("padded_bucket", 0) <= 12, snap


@pytest.mark.slow
def test_vcycle_shape_bucket_count_scale16():
    """Acceptance bound (ISSUE 1): a scale-16 CPU v-cycle stays within 12
    distinct LP/contraction shape buckets; executable-level specialization
    counts are reported by bench.py alongside."""
    snap, _ = _run_vcycle(16)
    assert snap.get("padded_bucket", 0) <= 12, snap
