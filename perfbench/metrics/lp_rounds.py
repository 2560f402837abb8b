"""Kernels: label-propagation rounds per partition, the rounds of every
sweep loop (``lp_stats_snapshot()["rounds"]``) over every partition of the
run, the warm-up's included; the program is deterministic, so each
partition of a run runs the same rounds."""


def read(run):
    try:
        from kaminpar_tpu.ops.lp import lp_stats_snapshot
    except ImportError:
        return None
    partitions = len(run.partitions) + (1 if run.warmup else 0)
    return lp_stats_snapshot()["rounds"] / partitions if partitions else None
