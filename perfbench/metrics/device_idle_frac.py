"""Share of the traced partition (the window's first, traced whole by
``run._PartitionTrace``) in which no program ran on the device; profiler
trace, mean over the chips used."""


def read(run):
    return None if run.trace is None else run.trace.idle_frac
