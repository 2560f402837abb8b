"""Device seconds of the label-propagation programs (``lp_iterate_bucketed``,
clustering and refinement) in the traced partition (``device_idle_frac``)."""


def read(run):
    if run.trace is None:
        return None
    times = [s for name, s in run.trace.programs.items()
             if "lp_iterate_bucketed" in name]
    return sum(times) if times else None
