"""Chip benchmark of the partitioner: one cell, one run, one JSON line.

``python -m perfbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``.  Everything that
belongs to one configuration, traffic mix or metric lives in a file of its
own (``configs/``, ``traffic/``, ``families/``, ``metrics/``), found by the
name ``BENCHMARK.json`` gives it.  The yardstick (graph generators, the
NumPy reference, the trace reduction and the table of peaks) is kept here
and imports nothing of the program.
"""
