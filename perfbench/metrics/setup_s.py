"""Process start to the first timed partition: imports, graph generation,
compile-cache loads and the warm-up partition (host clock)."""


def read(run):
    return run.setup_s
