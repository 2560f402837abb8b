"""chip_smoke.py off the chip: it refuses a CPU backend without printing a
result, and its partition phase and checks hold at a small scale."""

import numpy as np
import pytest

import chip_smoke


def test_main_refuses_cpu(capsys):
    rc = chip_smoke.main([])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "'cpu'" in err
    assert out == ""


def test_partition_phase_scale10_checks_cut():
    rec = chip_smoke.partition_phase(10)
    row_ptr, col_idx, edge_w, _ = rec["host"]
    assert rec["cut"] == rec["reported_cut"]
    assert rec["cut"] == chip_smoke.numpy_cut(
        row_ptr, col_idx, edge_w, rec["part"].astype(np.int64)
    )
    assert rec["imbalance"] <= chip_smoke.EPSILON + 1e-9
    assert len(np.unique(rec["part"])) == chip_smoke.K


def test_check_partition_rejects_wrong_cut():
    from kaminpar_tpu.graph import generators

    g = generators.grid2d_graph(8, 8)
    host = tuple(np.asarray(a) for a in (g.row_ptr, g.col_idx, g.edge_w, g.node_w))
    part = np.arange(g.n) % 2
    cut = chip_smoke.numpy_cut(*host[:3], part)
    max_bw = np.full(2, g.n)
    assert chip_smoke.check_partition("grid", g, host, part, cut, max_bw, 2)
    with pytest.raises(chip_smoke.SmokeFailure, match="reported cut"):
        chip_smoke.check_partition("grid", g, host, part, cut + 1, max_bw, 2)


def test_resilience_checks_reject_host_pool_fallback():
    from kaminpar_tpu.context import Context
    from kaminpar_tpu.ops import bipartition as bip

    ctx = Context()
    ctx.initial_partitioning.ip_backend = "device"
    bip.reset_pool_stats()
    try:
        with bip._stats_lock:
            bip._pool_stats["calls"] = 1
        assert chip_smoke._resilience_checks(ctx)["ip_pool_fallbacks"] == 0
        bip.count_pool_fallback()
        with pytest.raises(chip_smoke.SmokeFailure, match="host pool"):
            chip_smoke._resilience_checks(ctx)
    finally:
        bip.reset_pool_stats()
