"""Window wall time over the partitions completed (host clock).  The window
runs from the start of the first partition to the end of the last, so a
stall between partitions counts."""


def read(run):
    done = run.done
    return run.window_s / len(done) if done else None
