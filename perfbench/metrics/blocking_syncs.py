"""Host-device boundary: blocking device-to-host pulls
(``sync_stats.snapshot()["count"]``), per partition."""
from perfbench.metrics._phase import mean_counter


def read(run):
    return mean_counter(run, "syncs")
