"""Metric readers, one module per metric name: ``read(run)`` returns the value or None."""
