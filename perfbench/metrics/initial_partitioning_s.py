"""Initial partitioning layer: timer scope
``partitioning/initial_partitioning``, per partition."""
from perfbench.metrics._phase import mean_phase_seconds


def read(run):
    return mean_phase_seconds(run, "initial_partitioning")
