"""Initial partitioning: device bipartition pool calls
(``pool_stats_snapshot()["calls"]``), per partition."""
from perfbench.metrics._phase import mean_counter


def read(run):
    return mean_counter(run, "pool_calls")
