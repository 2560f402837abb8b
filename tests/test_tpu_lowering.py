"""AOT TPU lowering of the full kernel set (VERDICT r3 next-steps #2).

This test guarantees every kernel — shm, 64-bit variants, and the
shard_map distributed rounds on the 8-device mesh — lowers cleanly through
``jax.export`` for ``platforms=['tpu']`` so first silicon contact measures
instead of debugging.  Lowering-rule failures (unsupported primitives, int64
sorts, degenerate shapes, collectives) surface here; the chip compiler's own
refusals are caught by tests/test_tpu_compile.py.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from kaminpar_tpu.utils.aot import (
    AotExportError,
    export_kernel_suite,
    suite_total_bytes,
)


def test_kernel_suite_lowers_for_tpu():
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:8]), ("nodes",)) if len(devs) >= 8 else None
    try:
        sizes = export_kernel_suite(
            platforms=("tpu",), include_dist=mesh is not None, mesh=mesh
        )
    except AotExportError as e:
        pytest.fail(str(e))
    # Full sweep: shm + x64 variants (+ dist rounds when the mesh exists).
    assert len(sizes) >= 32, sorted(sizes)
    assert all(n > 0 for n in sizes.values())
    # Spot-check the headline kernels are present.
    for name in (
        "lp_iterate_bucketed",
        "lp_round_bucketed_heavy",
        "contraction",
        "jet_move_round",
        "balance_round",
        "lp_iterate_bucketed_x64",
        "contraction_x64",
        # Serve batch kernels (ISSUE 3): engine warmup on silicon must not
        # be the first place they meet the TPU lowering rules.
        "serve_packed_metrics",
        # The lane-vmapped initial-bipartitioning pool (ISSUE 4), both
        # index widths — engine warmup compiles it per cell at startup.
        "ip_pool",
        "ip_pool_x64",
        # Decode-fused compressed kernels (ISSUE 10): both edge-stream
        # trace switches of the sweep loop, the flat decode, and
        # contraction-off-the-stream — the terapart device tier's cells,
        # counted in suite_total_bytes like every other family.
        "lp_iterate_compressed",
        "lp_iterate_compressed_uniform",
        "lp_two_hop_compressed",
        "decode_flat_padded",
        "contract_compressed",
    ):
        assert name in sizes
    # Cumulative serialized size is the suite's budget metric: a serialized
    # StableHLO module is never under ~1 KB, so a truncated/empty export
    # (the failure mode warmup would otherwise hit first on silicon) drags
    # the total below the per-kernel floor.
    assert suite_total_bytes(sizes) >= len(sizes) * 800
    if mesh is not None:
        for name in (
            "dist_lp_round",
            "dist_cluster_round",
            "dist_coloring",
            "dist_jet_round",
            "dist_contract_s1",
        ):
            assert name in sizes
