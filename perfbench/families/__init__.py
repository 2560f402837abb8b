"""Graph families, one module each: ``generate(params, seed)`` returns the
host CSR ``(row_ptr, col_idx)`` of an undirected graph with unit node and
edge weights.  A configuration file names its family; the harness loads
``families/<family>.py`` by that name."""
