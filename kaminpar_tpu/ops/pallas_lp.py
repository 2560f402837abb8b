"""Fused Pallas TPU kernels for the LP round — the post-XLA-ceiling path.

On-silicon profiling (TPU_NOTES.md r5) pinned the XLA LP round at
~15 M edges/s: the lowering materializes every intermediate in HBM, so each
round pays two m-sized irregular gathers (neighbor labels at 15.6 ns/elem,
cluster weights), a row sort, and 6 histogram segment-scatters (7.6 ns/elem)
as *separate* HBM round trips — a realistic XLA-op ceiling of ~25-30 M e/s.
This module replaces that pipeline with two fused kernels that stream the
degree-bucketed CSR layout (graph/bucketed.py) once per round:

- :func:`_rate_bucket` — per (R, w) degree bucket, one grid pass over row
  blocks: gather neighbor labels and cluster weights from VMEM-resident
  tables, sort each row with an in-register bitonic network (width is a
  power of two by construction), reduce runs to ratings with a row cumsum,
  and emit per-row (target, tconn, own_conn, has).  The two gathers, the
  sort, and the reduction never leave VMEM.
- :func:`_commit` — one pass over the n-sized move arrays fusing the mover
  computation with the radix-32 capacity auction (6 in-VMEM histogram
  levels) and the label/weight state update, so no (n,) intermediate
  (desired/moved/accept) round-trips HBM between rating and commit.

Bit-identical contract (asserted by tests/test_pallas_lp.py): all random
draws (tie-breaks, auction priorities, active subsets) are generated
*outside* the kernels with exactly the key schedule of the XLA path
(ops/lp.py, ops/bucketed_gains.py) and passed in as operands, and every
in-kernel reduction is integer math in the same associative order — so the
Pallas round returns the same labels, label weights, and admission masks as
the XLA round, bit for bit.  Heavy rows (degree > MAX_WIDTH) keep the flat
edge-parallel path (they are rare and already sort-bound), mirroring the
reference's two-phase LP split (label_propagation.h:571-601).

Backend selection: ``LabelPropagationContext.lp_kernel`` = ``"xla"`` |
``"pallas"`` | ``"auto"``.  Off-TPU the kernels run with ``interpret=True``,
so tier-1 CPU tests exercise the kernel logic.  They do not compile for a
TPU with the installed jaxlib: Mosaic refuses the rate kernel's 1-D VMEM
table gather (:data:`MOSAIC_REFUSAL`, asserted against a described v5e by
tests/test_tpu_compile.py), and the commit kernel's histogram scatter-adds
have no Mosaic lowering either.  So ``"auto"`` resolves to ``"xla"``, and
``"pallas"`` on a TPU backend raises with that reason instead of failing
mid-run.

VMEM blocking notes (see TPU_NOTES.md): the label / cluster-weight /
node-weight tables are kept VMEM-resident, which bounds the single-kernel
clustering instantiation to n_pad <~ 1M int32 nodes per core (3 tables +
block operands inside ~16 MB); coarse levels and refinement (num_labels = k)
always fit.  Finest-level clustering beyond that needs an HBM+DMA variant —
deliberately out of scope for the first fused kernel.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import lp as lp_ops
from .bucketed_gains import _heavy_moves, assemble_moves
from .lp import LPState, _PRIO_BITS, _RADIX, _RADIX_BITS

_I32MAX = 2**31 - 1
# Row-block budget: blk_rows * width slots per operand block.  2^15 slots x
# ~6 int32 operands ~ 768 KB of VMEM per stage — safely inside 16 MB beside
# the resident tables.
_BLOCK_SLOTS = 1 << 15
# The commit kernel's radix histogram ((num_labels, 32) in the promoted
# weight dtype) lives in VMEM, not HBM — so the XLA auction's 512 MB
# transient budget (lp.use_radix_auction) is NOT the binding constraint
# here.  Past this bound the kernel uses the bitwise bisection, whose only
# per-label state is (num_labels,)-sized (same class as the resident
# weight tables).  Radix and bitwise resolve the same maximal priority
# threshold, so admission stays bit-identical to the XLA path either way.
_COMMIT_HIST_VMEM_BYTES = 1 << 22  # 4 MB


#: What Mosaic reports when it compiles the rate kernel's VMEM label-table
#: gather (``labels[cols]``) for a TPU with the installed jaxlib.
MOSAIC_REFUSAL = "Only 2D gather is supported"


def resolve_lp_kernel(choice: str) -> str:
    """Map the ``lp_kernel`` config knob to a concrete backend."""
    if choice not in ("xla", "pallas", "auto"):
        raise ValueError(
            f"lp_kernel must be 'xla', 'pallas' or 'auto', got {choice!r}"
        )
    if choice == "pallas" and jax.default_backend() == "tpu":
        raise NotImplementedError(
            "lp_kernel='pallas' cannot compile for TPU: Mosaic refuses the "
            f"fused rate kernel's VMEM table gather ({MOSAIC_REFUSAL!r}); "
            "use lp_kernel='xla'"
        )
    return "xla" if choice == "auto" else choice


def _interpret() -> bool:
    """Interpret off-TPU so CPU CI runs the kernel logic (dataflow, masks,
    integer reductions)."""
    return jax.default_backend() != "tpu"


def select_lp_ops(choice: str):
    """(iterate, colored_round, colored_iterate) triple for the configured
    ``lp_kernel`` knob — the single dispatch point shared by lp_clusterer /
    lp_refiner / clp_refiner."""
    if resolve_lp_kernel(choice) == "pallas":
        return lp_iterate_bucketed, lp_round_colored, clp_iterate_colors
    return (
        lp_ops.lp_iterate_bucketed,
        lp_ops.lp_round_colored,
        lp_ops.clp_iterate_colors,
    )


# --------------------------------------------------------------------------
# In-kernel stable row sort: bitonic network on the composite key
# (label, original position).  Composite keys are unique, so the network
# output is exactly the stable `lax.sort((L, W), num_keys=1)` of the XLA
# path — same sorted labels, same carried weights, same slot positions (the
# positions the tie-break randoms are indexed by).
# --------------------------------------------------------------------------


def _partner(x, j):
    """Value at lane index (i XOR j) — a static half-swap within groups of
    2j lanes (reshape + flip), the Mosaic-friendly exchange."""
    R, w = x.shape
    return jnp.flip(x.reshape(R, w // (2 * j), 2, j), axis=2).reshape(R, w)


def _bitonic_sort_rows(L, W):
    R, w = L.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (R, w), 1)
    I = pos
    k = 2
    while k <= w:
        j = k // 2
        while j >= 1:
            Lp, Wp, Ip = _partner(L, j), _partner(W, j), _partner(I, j)
            is_lo = (pos & j) == 0
            up = (pos & k) == 0
            a_less = (L < Lp) | ((L == Lp) & (I < Ip))
            take = jnp.where(is_lo == up, ~a_less, a_less)
            L = jnp.where(take, Lp, L)
            W = jnp.where(take, Wp, W)
            I = jnp.where(take, Ip, I)
            j //= 2
        k *= 2
    return L, W


# --------------------------------------------------------------------------
# Kernel 1: fused gather + rate per degree bucket.
# --------------------------------------------------------------------------


def _rate_rows_body(labels, node_w_tab, lw_tab, maxw_ref, nodes, cols, W, tie,
                    *, external_only: bool, respect_caps: bool,
                    tie_break: str, maxw_scalar: bool):
    """The shared in-VMEM rating math of the dense and decode-fused rate
    kernels: gather neighbor labels, bitonic row sort, run reduction,
    cap/tie filtering.  Factoring it keeps the compressed kernel
    byte-compatible with the dense one past the decode."""
    own = labels[nodes]
    nw = node_w_tab[nodes]
    L = labels[cols]  # fused gather 1: neighbor labels
    own_conn = jnp.sum(jnp.where(L == own[:, None], W, 0), axis=1)

    Ls, Ws = _bitonic_sort_rows(L, W)
    R = Ls.shape[0]
    c = jnp.cumsum(Ws, axis=1)
    change = Ls[:, 1:] != Ls[:, :-1]
    start = jnp.concatenate([jnp.ones((R, 1), bool), change], axis=1)
    end = jnp.concatenate([change, jnp.ones((R, 1), bool)], axis=1)
    # Run rating at run ends: cumsum minus the run's base, propagated by
    # a row cummax (monotone — weights are non-negative).
    base = jnp.where(start, c - Ws, 0)
    run_base = jax.lax.cummax(base, axis=1)
    rating = c - run_base

    is_cur = Ls == own[:, None]
    ok = end & (rating > 0)
    if external_only:
        ok = ok & ~is_cur
    lw_s = None
    if respect_caps or tie_break == "lightest":
        lw_s = lw_tab[Ls]  # fused gather 2: cluster weights
    if respect_caps:
        cap = maxw_ref[0] if maxw_scalar else maxw_ref[...][Ls]
        fits = lw_s + nw[:, None] <= cap
        ok = ok & fits if external_only else ok & (is_cur | fits)

    score = jnp.where(ok, rating, -1)
    best = jnp.max(score, axis=1)
    has = best >= 0
    eligible = ok & (rating == best[:, None]) & has[:, None]
    if tie_break == "lightest":
        lw_m = jnp.where(eligible, lw_s, jnp.iinfo(lw_s.dtype).max)
        eligible = eligible & (lw_m == jnp.min(lw_m, axis=1)[:, None])
    tie_m = jnp.where(eligible, tie, -1)
    slot = jnp.argmax(tie_m, axis=1)
    target = jnp.where(
        has, jnp.take_along_axis(Ls, slot[:, None], axis=1)[:, 0], own
    )
    tconn = jnp.where(has, best, 0)
    return target, tconn, own_conn, has


def _make_rate_kernel(external_only: bool, respect_caps: bool, tie_break: str,
                      maxw_scalar: bool):
    def kernel(labels_ref, node_w_ref, lw_ref, maxw_ref,
               nodes_ref, cols_ref, wgts_ref, tie_ref,
               target_ref, tconn_ref, own_ref, has_ref):
        target, tconn, own_conn, has = _rate_rows_body(
            labels_ref[...], node_w_ref[...], lw_ref[...], maxw_ref,
            nodes_ref[...], cols_ref[...], wgts_ref[...], tie_ref[...],
            external_only=external_only, respect_caps=respect_caps,
            tie_break=tie_break, maxw_scalar=maxw_scalar,
        )
        target_ref[...] = target
        tconn_ref[...] = tconn
        own_ref[...] = own_conn
        has_ref[...] = has

    return kernel


def _rate_bucket(labels, node_w, label_weights, maxw_arr, bucket, tie, *,
                 external_only: bool, respect_caps: bool, tie_break: str,
                 maxw_scalar: bool):
    nodes, cols, wgts = bucket
    R, w = cols.shape
    blk = max(1, min(R, _BLOCK_SLOTS // w))
    # R and the budget are powers of two, so blk | R.
    kernel = _make_rate_kernel(external_only, respect_caps, tie_break, maxw_scalar)

    def full(arr):
        # The label/weight tables stay VMEM-resident across the whole grid
        # pass — the point of the fusion (gathers hit VMEM, not HBM).
        return pl.BlockSpec(
            arr.shape, lambda i: (0,) * arr.ndim, memory_space=pltpu.VMEM
        )

    row = pl.BlockSpec((blk,), lambda i: (i,))
    mat = pl.BlockSpec((blk, w), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(R // blk,),
        in_specs=[full(labels), full(node_w), full(label_weights),
                  full(maxw_arr), row, mat, mat, mat],
        out_specs=(row, row, row, row),
        out_shape=(
            jax.ShapeDtypeStruct((R,), labels.dtype),
            jax.ShapeDtypeStruct((R,), wgts.dtype),
            jax.ShapeDtypeStruct((R,), wgts.dtype),
            jax.ShapeDtypeStruct((R,), jnp.bool_),
        ),
        interpret=_interpret(),
    )(labels, node_w, label_weights, maxw_arr, nodes, cols, wgts, tie)


def pallas_best_moves(
    key,
    labels,
    buckets,
    heavy,
    gather_idx,
    node_w,
    label_weights,
    max_label_weights,
    *,
    external_only: bool = True,
    respect_caps: bool = True,
    tie_break: str = "uniform",
):
    """Drop-in, bit-identical equivalent of bucketed_gains.bucketed_best_moves
    with the per-bucket work running in the fused Pallas kernel."""
    maxw = jnp.asarray(max_label_weights)
    maxw_scalar = maxw.ndim == 0
    maxw_arr = maxw.reshape(1) if maxw_scalar else maxw
    outs = []
    for i, b in enumerate(buckets):
        bk = jax.random.fold_in(key, i)
        R, w = b.cols.shape
        # Tie-break randoms drawn OUTSIDE the kernel with the XLA path's
        # exact key schedule (bucketed_gains._bucket_moves), indexed by
        # sorted slot position inside the kernel.
        tie = jax.random.randint(bk, (R, w), 0, _I32MAX, dtype=jnp.int32)
        outs.append(
            _rate_bucket(
                labels, node_w, label_weights, maxw_arr, b, tie,
                external_only=external_only, respect_caps=respect_caps,
                tie_break=tie_break, maxw_scalar=maxw_scalar,
            )
        )
    if heavy.nodes.shape[0] > 0:
        # Heavy rows keep the flat edge-parallel XLA path (reference
        # two-phase split); same folded key as the XLA bucketed path.
        outs.append(
            _heavy_moves(
                jax.random.fold_in(key, len(buckets)), labels, heavy,
                node_w, label_weights, max_label_weights,
                external_only=external_only, respect_caps=respect_caps,
                tie_break=tie_break,
            )
        )
    return assemble_moves(outs, gather_idx, labels)


# --------------------------------------------------------------------------
# Kernel 1b: decode-fused gather + rate off the compressed word stream
# (TeraPart compute tier).  Identical rating body as the dense kernel; the
# (R, w) neighbor matrix is materialized in VMEM from the packed gap stream
# — one gather of two consecutive words + shift/mask per edge + a row
# cumsum (graph/device_compressed.decode_rows; the encoding was designed so
# there is no data-dependent control flow).  The words table is VMEM-
# resident beside the label/weight tables, so a round streams the
# *compressed* bytes from HBM instead of the dense cols+wgts matrices.
# --------------------------------------------------------------------------


def _make_compressed_rate_kernel(w: int, external_only: bool,
                                 respect_caps: bool, tie_break: str,
                                 maxw_scalar: bool):
    from ..graph.device_compressed import CompressedStream, decode_rows

    def kernel(labels_ref, node_w_ref, lw_ref, maxw_ref, words_ref, ew_ref,
               nodes_ref, ws_ref, wd_ref, dg_ref, es_ref, tie_ref,
               target_ref, tconn_ref, own_ref, has_ref):
        node_w_tab = node_w_ref[...]
        nodes = nodes_ref[...]
        cols, W = decode_rows(
            CompressedStream(words_ref[...], ew_ref[...]), nodes,
            ws_ref[...], wd_ref[...], dg_ref[...], es_ref[...],
            w, node_w_tab.dtype,
        )
        target, tconn, own_conn, has = _rate_rows_body(
            labels_ref[...], node_w_tab, lw_ref[...], maxw_ref,
            nodes, cols, W, tie_ref[...],
            external_only=external_only, respect_caps=respect_caps,
            tie_break=tie_break, maxw_scalar=maxw_scalar,
        )
        target_ref[...] = target
        tconn_ref[...] = tconn
        own_ref[...] = own_conn
        has_ref[...] = has

    return kernel


def _rate_compressed_bucket(labels, node_w, label_weights, maxw_arr, stream,
                            cb, tie, *, external_only: bool,
                            respect_caps: bool, tie_break: str,
                            maxw_scalar: bool):
    w = int(cb.slot.shape[0])
    R = int(cb.nodes.shape[0])
    blk = max(1, min(R, _BLOCK_SLOTS // w))
    kernel = _make_compressed_rate_kernel(
        w, external_only, respect_caps, tie_break, maxw_scalar
    )

    def full(arr):
        return pl.BlockSpec(
            arr.shape, lambda i: (0,) * arr.ndim, memory_space=pltpu.VMEM
        )

    row = pl.BlockSpec((blk,), lambda i: (i,))
    mat = pl.BlockSpec((blk, w), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(R // blk,),
        in_specs=[full(labels), full(node_w), full(label_weights),
                  full(maxw_arr), full(stream.words), full(stream.edge_w),
                  row, row, row, row, row, mat],
        out_specs=(row, row, row, row),
        out_shape=(
            jax.ShapeDtypeStruct((R,), labels.dtype),
            jax.ShapeDtypeStruct((R,), node_w.dtype),
            jax.ShapeDtypeStruct((R,), node_w.dtype),
            jax.ShapeDtypeStruct((R,), jnp.bool_),
        ),
        interpret=_interpret(),
    )(labels, node_w, label_weights, maxw_arr, stream.words, stream.edge_w,
      cb.nodes, cb.wstart, cb.width, cb.deg, cb.estart, tie)


def pallas_compressed_best_moves(
    key,
    labels,
    cbuckets,
    stream,
    heavy,
    gather_idx,
    node_w,
    label_weights,
    max_label_weights,
    *,
    external_only: bool = True,
    respect_caps: bool = True,
    tie_break: str = "uniform",
):
    """Drop-in, bit-identical equivalent of lp.compressed_best_moves with
    the per-bucket decode + rating fused into one Pallas kernel."""
    maxw = jnp.asarray(max_label_weights)
    maxw_scalar = maxw.ndim == 0
    maxw_arr = maxw.reshape(1) if maxw_scalar else maxw
    outs = []
    for i, cb in enumerate(cbuckets):
        bk = jax.random.fold_in(key, i)
        R = int(cb.nodes.shape[0])
        w = int(cb.slot.shape[0])
        # Same tie-break key schedule as the XLA twin (_bucket_moves draws
        # (R, w) per bucket), indexed by sorted slot inside the kernel.
        tie = jax.random.randint(bk, (R, w), 0, _I32MAX, dtype=jnp.int32)
        outs.append(
            _rate_compressed_bucket(
                labels, node_w, label_weights, maxw_arr, stream, cb, tie,
                external_only=external_only, respect_caps=respect_caps,
                tie_break=tie_break, maxw_scalar=maxw_scalar,
            )
        )
    if heavy.nodes.shape[0] > 0:
        outs.append(
            _heavy_moves(
                jax.random.fold_in(key, len(cbuckets)), labels, heavy,
                node_w, label_weights, max_label_weights,
                external_only=external_only, respect_caps=respect_caps,
                tie_break=tie_break,
            )
        )
    return assemble_moves(outs, gather_idx, labels)


@partial(
    jax.jit,
    static_argnames=("num_labels", "active_prob", "allow_tie_moves", "tie_break"),
)
def lp_round_compressed(
    state: LPState,
    key,
    cbuckets,
    stream,
    heavy,
    gather_idx,
    node_w,
    max_label_weights,
    *,
    num_labels: int,
    active_prob: float = 1.0,
    allow_tie_moves: bool = False,
    tie_break: str = "uniform",
) -> LPState:
    """One decode-fused LP round; bit-identical to lp.lp_round_compressed
    (and therefore to the dense round on the decompressed graph)."""
    kr, kp = jax.random.split(key)
    target, tconn, own_conn, _ = pallas_compressed_best_moves(
        kr, state.labels, cbuckets, stream, heavy, gather_idx, node_w,
        state.label_weights, max_label_weights,
        external_only=False, respect_caps=True, tie_break=tie_break,
    )
    return commit_moves(
        state, kp, target, tconn, own_conn, node_w, max_label_weights,
        num_labels, active_prob=active_prob, allow_tie_moves=allow_tie_moves,
    )


@partial(
    jax.jit,
    static_argnames=("num_labels", "active_prob", "allow_tie_moves", "tie_break"),
    donate_argnums=(0,),
)
def lp_iterate_compressed(
    state: LPState,
    key,
    cbuckets,
    stream,
    heavy,
    gather_idx,
    node_w,
    max_label_weights,
    min_moved,
    max_iterations,
    *,
    num_labels: int,
    active_prob: float = 1.0,
    allow_tie_moves: bool = False,
    tie_break: str = "uniform",
) -> LPState:
    """On-device sweep loop over the decode-fused kernels — the Pallas
    analog of lp.lp_iterate_compressed (same early-exit, same key
    folding, one dispatch per clustering)."""
    from ..utils import compile_stats

    compile_stats.record(
        "lp_iterate_compressed",
        arrays=[node_w, stream.words, *(b.nodes for b in cbuckets), heavy.cols],
        statics=(
            "pallas", num_labels, active_prob, allow_tie_moves, tie_break,
            jnp.asarray(max_label_weights).ndim,
        ),
    )
    max_iterations = jnp.asarray(max_iterations, dtype=jnp.int32)

    def cond(carry):
        i, st = carry
        return (i < max_iterations) & (st.num_moved > min_moved)

    def body(carry):
        i, st = carry
        st = lp_round_compressed(
            st, jax.random.fold_in(key, i), cbuckets, stream, heavy,
            gather_idx, node_w, max_label_weights, num_labels=num_labels,
            active_prob=active_prob, allow_tie_moves=allow_tie_moves,
            tie_break=tie_break,
        )
        return i + 1, st

    state = state._replace(num_moved=jnp.int32(jnp.iinfo(jnp.int32).max))
    rounds, state = jax.lax.while_loop(cond, body, (jnp.int32(0), state))
    return state._replace(rounds=rounds)


def select_compressed_iterate(choice: str):
    """The compressed-stream LP sweep loop for the ``lp_kernel`` knob —
    the decode-fused dispatch point shared by the compressed clusterer
    path and the finest-level LP refinement pass."""
    if resolve_lp_kernel(choice) == "pallas":
        return lp_iterate_compressed
    return lp_ops.lp_iterate_compressed


# --------------------------------------------------------------------------
# Kernel 2: fused commit — movers + radix capacity auction + state update.
# --------------------------------------------------------------------------


def _make_commit_kernel(num_labels: int, active_prob: float,
                        allow_tie_moves: bool, has_active: bool,
                        maxw_scalar: bool, radix: bool, wdt):
    def kernel(labels_ref, node_w_ref, lw_ref, maxw_ref, target_ref,
               tconn_ref, own_ref, prio_ref, coin_ref, act_ref, color_ref,
               new_labels_ref, new_weights_ref, moved_ref):
        labels = labels_ref[...]
        node_w = node_w_ref[...]
        lw = lw_ref[...]
        target = target_ref[...]
        tconn = tconn_ref[...]
        own_conn = own_ref[...]
        prio = prio_ref[...]

        better = tconn > own_conn
        if allow_tie_moves:
            better = better | ((tconn == own_conn) & coin_ref[...])
        desired = jnp.where(better, target, labels)
        moved = desired != labels
        if has_active:
            moved = moved & color_ref[...]
        if active_prob < 1.0:
            moved = moved & act_ref[...]

        # --- capacity auction (ops/lp.py capacity_auction, fused) ---
        t_idx = jnp.where(moved, desired, 0)
        w_mover = jnp.where(moved, node_w, 0).astype(wdt)
        if maxw_scalar:
            max_w_l = maxw_ref[0].astype(wdt)
        else:
            max_w_l = maxw_ref[...].astype(wdt)
        slack = max_w_l - lw.astype(wdt)

        if radix:
            def level(i, carry):
                thr, admitted = carry
                shift = _PRIO_BITS - _RADIX_BITS - i * _RADIX_BITS
                thr_t = thr[t_idx]
                in_window = moved & (
                    (prio >> (shift + _RADIX_BITS))
                    == (thr_t >> (shift + _RADIX_BITS))
                ) & (prio >= thr_t)
                digit = (prio >> shift) & (_RADIX - 1)
                seg = jnp.where(
                    in_window, t_idx * _RADIX + digit, num_labels * _RADIX
                ).astype(jnp.int32)
                hist = (
                    jnp.zeros(num_labels * _RADIX + 1, dtype=wdt)
                    .at[seg].add(jnp.where(in_window, w_mover, 0))
                )[:-1].reshape(num_labels, _RADIX)
                cum = jnp.cumsum(hist, axis=1)
                room = (slack - admitted)[:, None]
                j = jnp.sum((cum <= room) & (room >= 0), axis=1)
                gained = jnp.where(
                    j > 0,
                    jnp.take_along_axis(
                        cum, jnp.maximum(j - 1, 0)[:, None], axis=1
                    )[:, 0],
                    0,
                )
                return thr + (j << shift).astype(jnp.int32), admitted + gained

            levels = _PRIO_BITS // _RADIX_BITS
            thr, _ = jax.lax.fori_loop(
                0, levels, level,
                (jnp.zeros(num_labels, jnp.int32), jnp.zeros(num_labels, wdt)),
            )
        else:
            def body(i, thr):
                bit = jnp.int32(1) << (jnp.int32(_PRIO_BITS - 1) - i)
                cand = thr + bit
                adm = moved & (prio < cand[t_idx])
                demand = (
                    jnp.zeros(num_labels, dtype=wdt)
                    .at[t_idx].add(jnp.where(adm, w_mover, 0))
                )
                return jnp.where(demand <= slack, cand, thr)

            thr = jax.lax.fori_loop(
                0, _PRIO_BITS, body, jnp.zeros(num_labels, jnp.int32)
            )

        accept = moved & (prio < thr[t_idx])
        commit = moved & accept
        new_labels = jnp.where(commit, desired, labels)
        new_labels_ref[...] = new_labels
        new_weights_ref[...] = (
            jnp.zeros(num_labels, dtype=node_w.dtype).at[new_labels].add(node_w)
        )
        moved_ref[...] = jnp.sum(commit).astype(jnp.int32).reshape(1)

    return kernel


def commit_moves(
    state: LPState,
    kp,
    target,
    tconn,
    own_conn,
    node_w,
    max_label_weights,
    num_labels: int,
    *,
    active_prob: float = 1.0,
    allow_tie_moves: bool = False,
    active=None,
):
    """Bit-identical fused replacement for lp._commit_moves: same key
    schedule (split + per-purpose draws), same integer auction, one kernel."""
    labels, label_weights = state.labels, state.label_weights
    kp, ka, kt = jax.random.split(kp, 3)
    n = labels.shape[0]
    coin = (
        jax.random.bernoulli(kt, 0.5, tconn.shape)
        if allow_tie_moves else jnp.zeros(n, dtype=bool)
    )
    act = (
        jax.random.bernoulli(ka, active_prob, (n,))
        if active_prob < 1.0 else jnp.zeros(n, dtype=bool)
    )
    color = active if active is not None else jnp.zeros(n, dtype=bool)
    prio = jax.random.randint(
        kp, (n,), 0, (1 << _PRIO_BITS) - 1, dtype=jnp.int32
    )

    maxw = jnp.asarray(max_label_weights)
    maxw_scalar = maxw.ndim == 0
    maxw_arr = maxw.reshape(1) if maxw_scalar else maxw
    wdt = jnp.promote_types(jnp.asarray(node_w).dtype, label_weights.dtype)
    radix = lp_ops.use_radix_auction(num_labels, wdt) and (
        num_labels * _RADIX * jnp.dtype(wdt).itemsize <= _COMMIT_HIST_VMEM_BYTES
    )

    kernel = _make_commit_kernel(
        num_labels, active_prob, allow_tie_moves, active is not None,
        maxw_scalar, radix, wdt,
    )
    spec = pl.BlockSpec(memory_space=pltpu.VMEM)
    new_labels, new_weights, moved = pl.pallas_call(
        kernel,
        in_specs=[spec] * 11,
        out_specs=(spec, spec, spec),
        out_shape=(
            jax.ShapeDtypeStruct((n,), labels.dtype),
            jax.ShapeDtypeStruct((num_labels,), jnp.asarray(node_w).dtype),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ),
        interpret=_interpret(),
    )(labels, node_w, label_weights, maxw_arr, target, tconn, own_conn,
      prio, coin, act, color)
    return LPState(new_labels, new_weights, moved[0])


# --------------------------------------------------------------------------
# Round / iterate entry points — signature-compatible with ops/lp.py.
# --------------------------------------------------------------------------


@partial(
    jax.jit,
    static_argnames=("num_labels", "active_prob", "allow_tie_moves", "tie_break"),
)
def lp_round_bucketed(
    state: LPState,
    key,
    buckets,
    heavy,
    gather_idx,
    node_w,
    max_label_weights,
    *,
    num_labels: int,
    active_prob: float = 1.0,
    allow_tie_moves: bool = False,
    tie_break: str = "uniform",
) -> LPState:
    """One fused-kernel LP round; bit-identical to lp.lp_round_bucketed."""
    kr, kp = jax.random.split(key)
    target, tconn, own_conn, _ = pallas_best_moves(
        kr, state.labels, buckets, heavy, gather_idx, node_w,
        state.label_weights, max_label_weights,
        external_only=False, respect_caps=True, tie_break=tie_break,
    )
    return commit_moves(
        state, kp, target, tconn, own_conn, node_w, max_label_weights,
        num_labels, active_prob=active_prob, allow_tie_moves=allow_tie_moves,
    )


@partial(jax.jit, static_argnames=("num_labels", "allow_tie_moves"))
def lp_round_colored(
    state: LPState,
    key,
    buckets,
    heavy,
    gather_idx,
    node_w,
    max_label_weights,
    active,
    *,
    num_labels: int,
    allow_tie_moves: bool = True,
) -> LPState:
    """Colored superstep (CLP) on the fused kernels; bit-identical to
    lp.lp_round_colored."""
    kr, kp = jax.random.split(key)
    target, tconn, own_conn, _ = pallas_best_moves(
        kr, state.labels, buckets, heavy, gather_idx, node_w,
        state.label_weights, max_label_weights,
        external_only=False, respect_caps=True,
    )
    return commit_moves(
        state, kp, target, tconn, own_conn, node_w, max_label_weights,
        num_labels, allow_tie_moves=allow_tie_moves, active=active,
    )


@partial(
    jax.jit,
    static_argnames=("num_labels", "allow_tie_moves"),
    donate_argnums=(0,),
)
def clp_iterate_colors(
    state: LPState,
    keys,
    buckets,
    heavy,
    gather_idx,
    node_w,
    max_label_weights,
    colors,
    num_colors,
    *,
    num_labels: int,
    allow_tie_moves: bool = True,
) -> LPState:
    """Fused-kernel CLP iteration: all color supersteps in one on-device
    fori_loop — bit-identical to lp.clp_iterate_colors (same per-superstep
    keys, same round math), one dispatch + one moved-count readback per
    iteration."""
    from ..utils import compile_stats

    compile_stats.record(
        "clp_iterate",
        arrays=[node_w, keys, *(b.cols for b in buckets), heavy.cols],
        statics=("pallas", num_labels, allow_tie_moves),
    )

    def body(c, carry):
        st, moved = carry
        st = lp_round_colored(
            st, keys[c], buckets, heavy, gather_idx, node_w,
            max_label_weights, colors == c, num_labels=num_labels,
            allow_tie_moves=allow_tie_moves,
        )
        return st, moved + st.num_moved

    state, moved = jax.lax.fori_loop(
        0, jnp.asarray(num_colors, dtype=jnp.int32), body,
        (state, jnp.int32(0)),
    )
    return state._replace(num_moved=moved)


@partial(
    jax.jit,
    static_argnames=("num_labels", "active_prob", "allow_tie_moves", "tie_break"),
    donate_argnums=(0,),
)
def lp_iterate_bucketed(
    state: LPState,
    key,
    buckets,
    heavy,
    gather_idx,
    node_w,
    max_label_weights,
    min_moved,
    max_iterations,
    *,
    num_labels: int,
    active_prob: float = 1.0,
    allow_tie_moves: bool = False,
    tie_break: str = "uniform",
) -> LPState:
    """On-device LP sweep loop over the fused kernels — the Pallas analog of
    lp.lp_iterate_bucketed (same early-exit condition, same per-round key
    folding, one dispatch per clustering)."""
    from ..utils import compile_stats

    compile_stats.record(
        "lp_iterate",
        arrays=[node_w, *(b.cols for b in buckets), heavy.cols],
        statics=(
            "pallas", num_labels, active_prob, allow_tie_moves, tie_break,
            jnp.asarray(max_label_weights).ndim,
        ),
    )
    max_iterations = jnp.asarray(max_iterations, dtype=jnp.int32)

    def cond(carry):
        i, st = carry
        return (i < max_iterations) & (st.num_moved > min_moved)

    def body(carry):
        i, st = carry
        st = lp_round_bucketed(
            st, jax.random.fold_in(key, i), buckets, heavy, gather_idx,
            node_w, max_label_weights, num_labels=num_labels,
            active_prob=active_prob, allow_tie_moves=allow_tie_moves,
            tie_break=tie_break,
        )
        return i + 1, st

    state = state._replace(num_moved=jnp.int32(jnp.iinfo(jnp.int32).max))
    rounds, state = jax.lax.while_loop(cond, body, (jnp.int32(0), state))
    return state._replace(rounds=rounds)
