"""Compile layer: seconds of loading programs from the persistent cache, in
set-up (compile ledger ``load_s``)."""
from perfbench.metrics._ledger import ledger_sum


def read(run):
    return ledger_sum("load_s")
