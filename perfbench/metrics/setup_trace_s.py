"""Compile layer: seconds JAX spent tracing programs to jaxprs and lowering
them to MLIR in set-up (compile ledger ``trace_s + lower_s``)."""
from perfbench.metrics._ledger import ledger_sum


def read(run):
    return ledger_sum("trace_s", "lower_s")
