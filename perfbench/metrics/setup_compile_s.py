"""Compile layer: seconds of backend compiles that the persistent cache
missed, in set-up (compile ledger ``compile_s``)."""
from perfbench.metrics._ledger import ledger_sum


def read(run):
    return ledger_sum("compile_s")
