"""Coarsening layer: timer scope ``partitioning/coarsening``, per partition."""
from perfbench.metrics._phase import mean_phase_seconds


def read(run):
    return mean_phase_seconds(run, "coarsening")
