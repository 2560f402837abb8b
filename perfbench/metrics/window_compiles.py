"""Programs compiled or loaded from the compile cache inside the window
(JAX's backend-compile events); set-up should leave none."""


def read(run):
    return sum(p.compiles for p in run.partitions)
