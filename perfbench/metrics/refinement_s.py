"""Refinement layer: timer scopes ``partitioning/lp_refinement`` and both
balancers, per partition."""
from perfbench.metrics._phase import mean_phase_seconds


def read(run):
    return mean_phase_seconds(
        run, "lp_refinement", "overload_balancer", "underload_balancer"
    )
